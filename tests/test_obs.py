"""Observability layer: tracer, metrics registry, facades, run reports.

Covers the ISSUE 7 acceptance surface: Chrome-trace schema validity and span
nesting, cross-thread producer-tid pairing through the panel pipeline,
disabled-tracer no-op guarantees, exact snapshot/delta semantics, the
``StreamStats`` facade contract (in-place reset, live references, the
reset-vs-add race), and a RunReport built from a real tiny sequence run whose
byte totals must equal the legacy ``stream_stats()`` counters.
"""

import json
import threading

import numpy as np
import pytest

from repro.core import (
    CommuteConfig,
    SequenceDetector,
    SolverSpec,
    chain_product,
    reset_stream_stats,
    solve,
    stream_stats,
)
from repro.core.tiles import StreamStats
from repro.graphs import gmm_snapshot_sequence
from repro.obs import metrics as obs_metrics
from repro.obs import phase
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY, MetricsRegistry
from repro.obs.roofline import NOT_MEASURED, PEAKS, streamed_solve_roofline
from repro.obs.report import (
    RUN_REPORT_KIND,
    build_run_report,
    save_run_report,
    validate_chrome_trace,
    validate_run_report,
)
from repro.store import PanelPipeline


@pytest.fixture(autouse=True)
def _quiet_tracer():
    """Every test starts and ends with tracing disabled and a clean buffer."""
    obs_trace.disable_tracing()
    obs_trace.tracer().clear()
    yield
    obs_trace.disable_tracing()
    obs_trace.tracer().clear()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_snapshot_delta_exact():
    reg = MetricsRegistry()
    reg.add(**{})
    reg.add_named({"a.x": 2.0, "a.y": 3.0})
    snap = reg.snapshot()
    reg.add_named({"a.x": 5.0, "b.z": 1.0})
    d = reg.delta(snap)
    # exact increments; untouched counters (a.y) are omitted entirely
    assert d == {"a.x": 5.0, "b.z": 1.0}
    assert reg.value("a.x") == 7.0
    # a second delta from the same snapshot is cumulative, not consumed
    reg.inc("a.x")
    assert reg.delta(snap)["a.x"] == 6.0


def test_registry_prefix_reset_and_gauges():
    reg = MetricsRegistry()
    reg.add_named({"s.n": 1.0, "t.n": 1.0})
    reg.max_gauge("s.peak", 10)
    reg.max_gauge("s.peak", 4)  # high-water mark keeps the max
    assert reg.gauge("s.peak") == 10
    reg.reset("s.")
    assert reg.value("s.n") == 0.0
    assert reg.gauge("s.peak") == 0.0
    assert reg.value("t.n") == 1.0  # other prefixes untouched


def test_registry_series_bounded():
    reg = MetricsRegistry(series_cap=4)
    snap = reg.snapshot()
    reg.extend("r", [1.0, 2.0])
    assert reg.series_delta("r", snap) == (1.0, 2.0)
    reg.extend("r", [3.0, 4.0, 5.0, 6.0])  # overflow dropped, not resized
    assert reg.series("r") == (1.0, 2.0, 3.0, 4.0)


def test_scoped_measurement():
    reg = MetricsRegistry()
    with obs_metrics.scoped(reg) as sc:
        reg.inc("inner", 3.0)
    reg.inc("inner", 1.0)  # after the scope; delta() still reads live
    assert sc.delta()["inner"] == 4.0


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_noop():
    assert not obs_trace.tracing_enabled()
    sp = obs_trace.span("nothing", x=1)
    with sp:
        sp.annotate(y=2)
        sp.fence(object())
    h = obs_trace.begin("cross")
    obs_trace.end(h)
    assert h == 0
    assert obs_trace.tracer().events() == []
    # the shared null span means zero allocation on the hot path
    assert obs_trace.span("a") is obs_trace.span("b")


def test_span_nesting_and_chrome_schema():
    obs_trace.enable_tracing()
    with obs_trace.span("outer", level=1):
        with obs_trace.span("inner"):
            pass
    doc = obs_trace.tracer().to_chrome_trace()
    validate_chrome_trace(doc)
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert set(evs) == {"outer", "inner"}
    out, inn = evs["outer"], evs["inner"]
    # proper nesting: inner's interval is contained in outer's
    assert out["ts"] <= inn["ts"]
    assert inn["ts"] + inn["dur"] <= out["ts"] + out["dur"] + 1e-6
    assert out["args"] == {"level": 1}
    # thread-name metadata present for the recording thread
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in doc["traceEvents"])
    # round-trips through JSON
    json.loads(json.dumps(doc))


def test_cross_thread_span_keeps_producer_tid():
    obs_trace.enable_tracing()
    handles = {}

    def producer():
        handles["h"] = obs_trace.begin("xfer", item=7)
        handles["tid"] = threading.get_ident()

    t = threading.Thread(target=producer, name="producer-thread")
    t.start()
    t.join()
    obs_trace.end(handles["h"], staged=True)
    (ev,) = [e for e in obs_trace.tracer().events() if e["ph"] == "X"]
    # the event lands on the PRODUCER's track, with the consumer's tid noted
    assert ev["tid"] == handles["tid"]
    assert ev["args"]["item"] == 7
    assert ev["args"]["staged"] is True
    assert ev["args"]["end_tid"] == threading.get_ident()
    names = obs_trace.tracer().to_chrome_trace()["traceEvents"]
    assert any(e["ph"] == "M" and e["args"]["name"] == "producer-thread"
               for e in names)


def test_trace_save_is_loadable(tmp_path):
    obs_trace.enable_tracing()
    with obs_trace.span("s"):
        pass
    path = tmp_path / "trace.json"
    obs_trace.tracer().save(str(path))
    with open(path) as f:
        validate_chrome_trace(json.load(f))


def test_phase_counters_accumulate_without_tracing():
    snap = REGISTRY.snapshot()
    with phase("solve"):
        pass
    with phase("solve"):
        pass
    d = REGISTRY.delta(snap)
    assert d["phase.solve.calls"] == 2.0
    assert d["phase.solve.seconds"] > 0.0
    # with tracing disabled, no span events were recorded
    assert obs_trace.tracer().events() == []


# ---------------------------------------------------------------------------
# StreamStats facade
# ---------------------------------------------------------------------------


def test_bare_streamstats_is_isolated():
    st = StreamStats()
    st.add(panels=2, bytes_h2d=100)
    assert (st.panels, st.bytes_h2d) == (2, 100)
    assert stream_stats() is not st
    # the process-wide counters did not move
    assert stream_stats()._reg is REGISTRY
    with pytest.raises(AttributeError):
        st.add(nonsense=1)


def test_reset_keeps_references_live():
    st = stream_stats()
    reset_stream_stats()
    st.add(bytes_read=7)
    assert st.bytes_read == 7
    st2 = reset_stream_stats()
    # in-place reset: the same object, zeroed, still wired to the registry
    assert st2 is st
    assert st.bytes_read == 0
    st.add(bytes_read=3)
    assert stream_stats().bytes_read == 3


def test_reset_race_with_concurrent_adds():
    """Regression: reset during an active streamed pass must neither lose the
    object identity nor corrupt counters (the old dataclass-replace reset
    raced ``st.bytes_read += n`` read-modify-writes in the prefetch thread).
    """
    st = stream_stats()
    reset_stream_stats()
    stop = threading.Event()
    errors = []

    def hammer_reset():
        while not stop.is_set():
            reset_stream_stats()

    def hammer_add():
        try:
            for _ in range(4000):
                st.add(bytes_read=1, bytes_decoded=1)
        except Exception as e:  # pragma: no cover - the regression itself
            errors.append(e)

    resetter = threading.Thread(target=hammer_reset)
    adders = [threading.Thread(target=hammer_add) for _ in range(3)]
    resetter.start()
    [t.start() for t in adders]
    [t.join() for t in adders]
    stop.set()
    resetter.join()
    assert errors == []
    # multi-counter add is atomic vs reset: the pair moves together
    assert st.bytes_read == st.bytes_decoded
    reset_stream_stats()


def test_reset_race_during_streamed_pipeline_pass():
    """Hammer reset_stream_stats() while a real PanelPipeline pass is feeding
    the process-wide stats from its prefetch thread; the pass must complete
    with correct panel payloads and non-negative, consistent counters."""

    class Handle:
        def __init__(self, a, ph):
            self.a, self._ph = a, ph

        shape = property(lambda self: self.a.shape)
        dtype = property(lambda self: self.a.dtype)
        panel_rows = property(lambda self: self._ph)

        def read_panel(self, row0, height):
            return self.a[row0:row0 + height]

    n, ph = 256, 8
    a = np.random.default_rng(0).normal(size=(n, n)).astype(np.float32)
    origins = list(range(0, n, ph))
    st = stream_stats()
    reset_stream_stats()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            reset_stream_stats()

    t = threading.Thread(target=hammer)
    t.start()
    try:
        with PanelPipeline([Handle(a, ph)], origins, ph, stats=st) as pipe:
            for row0, (panel,) in pipe:
                np.testing.assert_array_equal(panel, a[row0:row0 + ph])
    finally:
        stop.set()
        t.join()
    assert st.bytes_read >= 0 and st.bytes_decoded >= 0
    reset_stream_stats()


# ---------------------------------------------------------------------------
# run reports (real tiny runs)
# ---------------------------------------------------------------------------


def _tiny_sequence(ctx1, *, oocore: bool, t_steps: int = 3, n: int = 32):
    cfg = CommuteConfig(k_override=4, q=3, d=3, oocore=oocore)
    det = SequenceDetector(ctx1, cfg, top_k=5)
    seq = gmm_snapshot_sequence(ctx1, n, t_steps, seed=0, inject_p=0.01)
    return cfg, det.run(seq.snapshots())


def test_run_report_end_to_end_oocore(ctx1, tmp_path):
    obs_trace.enable_tracing(fence=True)
    reset_stream_stats()
    cfg, res = _tiny_sequence(ctx1, oocore=True)
    doc = build_run_report(
        config={"n": 32}, result=res, n=32, k_rp=4,
        device_kind="TPU v5 lite", peaks=PEAKS["TPU v5 lite"],
    )
    validate_run_report(doc)

    # acceptance: report byte totals equal the legacy stream_stats() counters
    st = stream_stats()
    assert doc["totals"]["bytes"]["bytes_read"] == st.bytes_read
    assert doc["totals"]["bytes"]["bytes_h2d"] == st.bytes_h2d
    assert doc["totals"]["bytes"]["bytes_decoded"] == st.bytes_decoded
    assert doc["totals"]["panels"] == st.panels

    # per-transition structure: all four phases timed, bytes moved, solver
    # telemetry with a residual series of exactly `iterations` entries
    assert len(doc["transitions"]) == 2
    for tr in doc["transitions"]:
        assert tr["phases"]["chain"] > 0
        assert tr["phases"]["solve"] > 0
        assert tr["phases"]["score"] > 0
        assert tr["bytes"]["bytes_read"] > 0
        for s in tr["solves"]:
            assert s["streamed"] is True
            assert len(s["residuals"]) == s["iterations"]
    # per-transition byte deltas sum to the totals (warmup holds the rest)
    read_sum = sum(t["bytes"]["bytes_read"] for t in doc["transitions"])
    warm = doc["warmup"]["bytes"]["bytes_read"]
    assert read_sum + warm == doc["totals"]["bytes"]["bytes_read"]

    # pipeline + cache blocks reflect real activity
    assert doc["pipeline"]["panels_fetched"] > 0
    assert doc["pipeline"]["producer_fetch_seconds"] > 0
    assert doc["cache"]["hits"] > 0
    assert doc["roofline"] is not None and doc["roofline"]["bound_s"] > 0
    assert doc["roofline"]["device_kind"] == "TPU v5 lite"

    # the saved artifact and the trace both validate from disk
    rpath = tmp_path / "report.json"
    save_run_report(doc, str(rpath))
    from repro.obs.report import validate_file

    assert validate_file(str(rpath)) == RUN_REPORT_KIND
    tpath = tmp_path / "trace.json"
    obs_trace.tracer().save(str(tpath))
    assert validate_file(str(tpath)) == "chrome_trace"
    # phase spans made it into the trace with fencing enabled
    names = {e["name"] for e in obs_trace.tracer().events()}
    assert {"phase.chain", "phase.ingest", "phase.solve", "phase.score",
            "prefetch.panel", "solve", "sequence.push"} <= names


def test_run_report_resident_and_residual_series(ctx1):
    reset_stream_stats()
    cfg, res = _tiny_sequence(ctx1, oocore=False)
    doc = build_run_report(config={}, result=res)
    validate_run_report(doc)
    for tr in doc["transitions"]:
        assert tr["bytes"]["bytes_read"] == 0  # nothing streams resident
        for s in tr["solves"]:
            assert s["streamed"] is False
            # resident while_loop carries the residual ring out intact
            assert len(s["residuals"]) == s["iterations"]
            assert s["residuals"][-1] == pytest.approx(s["residual"])
    assert doc["roofline"] is None  # no streamed solves to attribute


def test_roofline_peaks_by_device_kind():
    """Only a published peak gives a roofline fraction: the v5e entry bounds
    the compute term, an unknown device records its kind, "not measured"."""
    traffic = dict(bytes_read=0.0, bytes_h2d=0.0, flops=197e12, seconds=2.0)
    v5e = streamed_solve_roofline(**traffic, device_kind="TPU v5 lite")
    assert v5e["bound"] == "compute"
    assert v5e["t_compute_s"] == pytest.approx(1.0)
    assert v5e["roofline_frac"] == pytest.approx(0.5)
    cpu = streamed_solve_roofline(**traffic, device_kind="cpu")
    assert cpu["device_kind"] == "cpu"
    assert cpu["roofline_frac"] == NOT_MEASURED
    assert "bound_s" not in cpu


def test_run_report_not_converged_warning(ctx1):
    a = np.abs(np.random.default_rng(3).normal(size=(24, 24))).astype(np.float32)
    a = (a + a.T) / 2
    np.fill_diagonal(a, 0)
    op = chain_product(ctx1, a, 3)
    b = np.random.default_rng(4).normal(size=(24, 4)).astype(np.float32)
    # unreachable tolerance under a 1-step cap -> NOT-CONVERGED report
    _, rep = solve(ctx1, op, b, SolverSpec(tolerance=1e-30, max_iters=1))
    assert not rep.converged

    class FakeResult:
        transitions = ()
        transition_seconds = ()
        n_snapshots = 0
        chain_builds = 0

    class FakeTransition:
        def __init__(self, rep):
            self.solve_reports = (rep,)
            self.top_idx = np.asarray([0])
            self.top_val = np.asarray([0.0])

    r = FakeResult()
    r.transitions = [FakeTransition(rep)]
    r.transition_seconds = [0.1]
    doc = build_run_report(config={}, result=r)
    (w,) = doc["warnings"]
    assert w["event"] == "solver_not_converged"
    assert w["level"] == "warning"
    assert w["transition"] == 0
    assert REGISTRY.value("solver.not_converged") >= 1.0


def test_validators_reject_malformed():
    with pytest.raises(ValueError, match="kind"):
        validate_run_report({"schema": 1})
    with pytest.raises(ValueError, match="transitions"):
        validate_run_report({
            "kind": RUN_REPORT_KIND, "schema": 1, "config": {},
            "n_snapshots": 0, "totals": {}, "cache": {}, "pipeline": {},
            "solver": {}, "warnings": [], "transitions": [],
        })
    with pytest.raises(ValueError, match="no complete"):
        validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "ts": 1.0, "pid": 1, "tid": 1}
        ]})

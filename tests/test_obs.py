"""Observability layer: tracer, metrics registry, facades, run reports.

Covers Chrome-trace schema validity and span nesting, the panel pipeline's
spans on the threads that run them, disabled-tracer no-op guarantees, spans
mirrored into the ``jax.profiler`` trace (nesting read back from the
``.xplane.pb``), the span vocabulary, the ``jit.*`` compile counters, exact
snapshot/delta semantics, the ``StreamStats`` facade contract (in-place
reset, live references, the reset-vs-add race), and a RunReport built from a
real tiny sequence run whose byte totals must equal the legacy
``stream_stats()`` counters.
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
from repro.obs import phase, timed
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
    assert obs_trace.tracer().events() == []
    # the shared null span means zero allocation on the hot path
    assert obs_trace.span("a") is obs_trace.span("b")


def test_span_nesting_and_chrome_schema():
    obs_trace.enable_tracing()
    with obs_trace.span("phase.outer", level=1):
        with obs_trace.span("phase.inner"):
            pass
    doc = obs_trace.tracer().to_chrome_trace()
    validate_chrome_trace(doc)
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert set(evs) == {"phase.outer", "phase.inner"}
    out, inn = evs["phase.outer"], evs["phase.inner"]
    # proper nesting: inner's interval is contained in outer's
    assert out["ts"] <= inn["ts"]
    assert inn["ts"] + inn["dur"] <= out["ts"] + out["dur"] + 1e-6
    assert out["args"] == {"level": 1}
    # thread-name metadata present for the recording thread
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in doc["traceEvents"])
    # round-trips through JSON
    json.loads(json.dumps(doc))


class _ListHandle:
    """A host matrix behind the snapshot-handle protocol."""

    def __init__(self, a, ph):
        self.a, self._ph = a, ph

    shape = property(lambda self: self.a.shape)
    dtype = property(lambda self: self.a.dtype)
    panel_rows = property(lambda self: self._ph)

    def read_panel(self, row0, height):
        return self.a[row0:row0 + height]


def test_pipeline_fetch_spans_carry_the_prefetch_tid():
    """Each side of the panel pipeline traces on its own thread: the fetch
    on the prefetch thread's track, the wait for it on the consumer's."""
    obs_trace.enable_tracing()
    a = np.arange(64 * 4, dtype=np.float32).reshape(64, 4)
    with PanelPipeline([_ListHandle(a, 16)], range(0, 64, 16), 16) as pipe:
        for row0, (panel,) in pipe:
            np.testing.assert_array_equal(panel, a[row0:row0 + 16])
    evs = obs_trace.tracer().events()
    fetch = [e for e in evs if e["name"] == "pipeline.fetch"]
    wait = [e for e in evs if e["name"] == "pipeline.wait"]
    assert [e["args"]["row0"] for e in fetch] == [0, 16, 32, 48]
    assert len(wait) == 4
    assert {e["tid"] for e in wait} == {threading.get_ident()}
    (producer,) = {e["tid"] for e in fetch}
    assert producer != threading.get_ident()
    names = obs_trace.tracer().to_chrome_trace()["traceEvents"]
    assert any(e["ph"] == "M" and e["tid"] == producer
               and e["args"]["name"] == "panel-prefetch" for e in names)


def test_trace_save_is_loadable(tmp_path):
    obs_trace.enable_tracing()
    with obs_trace.span("phase.s"):
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
# spans in the jax.profiler trace, span vocabulary, compile counters
# ---------------------------------------------------------------------------


def test_disabled_span_builds_no_annotation(monkeypatch):
    """The disabled path constructs no ``TraceAnnotation``: with one that
    raises, disabled spans, phases and timers still run and record nothing."""
    import jax

    def refuse(name, **kw):
        raise AssertionError(f"annotation built for {name!r}")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    with obs_trace.span("query.panel", row0=0):
        pass
    with phase("solve") as sp:
        sp.fence(object())
    with timed("pipeline.stage", row0=0) as sp:
        sp.fence(object())
    assert obs_trace.tracer().events() == []
    obs_trace.enable_tracing()
    with pytest.raises(AssertionError, match="query.panel"):
        with obs_trace.span("query.panel"):
            pass


def test_annotation_leaves_after_the_fence(monkeypatch):
    """A fenced span's annotation covers the wait for its device values."""
    import jax

    order = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            order.append(("enter", self.name))

        def __exit__(self, *exc):
            order.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    monkeypatch.setattr(obs_trace, "_block_until_ready", lambda v: order.append(("fence", v)))
    obs_trace.enable_tracing(fence=True)
    with obs_trace.span("phase.chain") as sp:
        with obs_trace.span("solver.solve"):
            pass
        sp.fence("x")
    assert order == [
        ("enter", "phase.chain"), ("enter", "solver.solve"), ("exit", "solver.solve"),
        ("fence", "x"), ("exit", "phase.chain"),
    ]


def test_span_names_take_a_program_prefix():
    """Every span, phase and timer named in the sources starts with one of
    the prefixes of the program's span vocabulary."""
    import pathlib
    import re

    src = pathlib.Path(obs_trace.__file__).parents[1]
    call = re.compile(r'\b(span|phase|timed)\(\s*"([^"]+)"')
    found = {}
    for path in src.rglob("*.py"):
        for kind, name in call.findall(path.read_text()):
            found[name] = "phase." + name if kind == "phase" else name
    assert {"sequence.push", "solver.solve", "tiles.stream", "pipeline.stage",
            "query.panel", "phase.publish", "phase.query"} <= set(found.values())
    bad = {k: v for k, v in found.items() if not v.startswith(obs_trace.PREFIXES)}
    assert not bad, bad


def _profiled_program_spans(tmp_path, body):
    """Run ``body`` under ``jax.profiler`` with tracing on; returns the
    program's spans per host thread line: ``[[(name, start, end), ...]]``."""
    import glob

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    obs_trace.enable_tracing(fence=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
        obs_trace.disable_tracing()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = []
    for pl in jax.profiler.ProfileData.from_file(path).planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            evs = [(e.name, e.start_ns, e.end_ns) for e in ln.events
                   if e.name.startswith(obs_trace.PREFIXES)]
            if evs:
                lines.append(sorted(evs, key=lambda e: e[1]))
    return lines


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_push_spans_nest_in_the_profiler_trace(ctx1, tmp_path):
    """A traced push leaves ``sequence.push`` enclosing its phases, publish
    included, on one host thread line of the ``.xplane.pb``."""
    from repro.store.embstore import EmbeddingStore

    cfg = CommuteConfig(k_override=4, q=3, d=3)
    store = EmbeddingStore.create(tmp_path / "emb", n=32, k=4, seed=cfg.seed)
    det = SequenceDetector(ctx1, cfg, top_k=5, emb_store=store)
    seq = gmm_snapshot_sequence(ctx1, 32, 2, seed=0, inject_p=0.01)
    snaps = list(seq.snapshots())
    det.push(snaps[0])

    lines = _profiled_program_spans(tmp_path / "prof", lambda: det.push(snaps[1]))
    (line,) = [ln for ln in lines if any(e[0] == "sequence.push" for e in ln)]
    (push,) = [e for e in line if e[0] == "sequence.push"]
    for name in ("phase.chain", "phase.ingest", "phase.solve", "phase.score",
                 "phase.publish", "solver.solve"):
        evs = [e for e in line if e[0] == name]
        assert evs and all(_inside(e, push) for e in evs), name


def test_query_spans_nest_in_the_profiler_trace(ctx1, tmp_path, host_resident):
    """A traced store query leaves ``phase.query`` enclosing its spans on its
    thread line: on the first query of the artifact, the fill around the
    consumer's wait, stage and per-panel dispatch, then the collect; on the
    next, the dispatches over the resident panels and the collect alone.
    The fetches run on the prefetch thread's line."""
    from repro.core.query import top_anomalies_from_store
    from repro.store.embstore import EmbeddingStore

    rng = np.random.default_rng(0)
    store = EmbeddingStore.create(tmp_path / "emb", n=96, k=8, seed=1, panel_rows=32)
    store.put_embedding("t0000", rng.normal(size=(96, 8)).astype(np.float32), 10.0,
                        rng.uniform(1, 2, 96).astype(np.float32))
    # compiled outside the trace, through another store: this one keeps nothing yet
    top_anomalies_from_store(EmbeddingStore.open(tmp_path / "emb"), 5)

    def body():
        top_anomalies_from_store(store, 5)
        top_anomalies_from_store(store, 5)

    lines = _profiled_program_spans(tmp_path / "prof", body)
    (line,) = [ln for ln in lines if any(e[0] == "phase.query" for e in ln)]
    queries = [e for e in line if e[0] == "phase.query"]
    assert len(queries) == 2
    names = ("query.resident.fill", "pipeline.wait", "pipeline.stage", "query.panel",
             "query.collect")
    counts = [{name: sum(e[0] == name and _inside(e, q) for e in line) for name in names}
              for q in queries]
    assert counts == [
        {"query.resident.fill": 1, "pipeline.wait": 3, "pipeline.stage": 3,
         "query.panel": 3, "query.collect": 1},
        {"query.resident.fill": 0, "pipeline.wait": 0, "pipeline.stage": 0,
         "query.panel": 3, "query.collect": 1},
    ]
    (fill,) = [e for e in line if e[0] == "query.resident.fill"]
    assert all(_inside(e, fill) for e in line if e[0].startswith("pipeline."))
    assert all(any(_inside(e, q) for q in queries) for e in line if e[0] != "phase.query")
    fetch_lines = [ln for ln in lines if any(e[0] == "pipeline.fetch" for e in ln)]
    assert fetch_lines and all(ln is not line for ln in fetch_lines)


def test_query_spans_share_the_query_id(ctx1, tmp_path, host_resident):
    """In the Chrome export the spans inside one ``phase.query`` carry its
    ``query`` id; the prefetch thread's spans do not."""
    from repro.core.query import nearest_neighbors
    from repro.store.embstore import EmbeddingStore

    rng = np.random.default_rng(1)
    store = EmbeddingStore.create(tmp_path, n=64, k=8, seed=1, panel_rows=32)
    store.put_embedding("t0000", rng.normal(size=(64, 8)).astype(np.float32), 10.0,
                        rng.uniform(1, 2, 64).astype(np.float32))
    obs_trace.enable_tracing()
    nearest_neighbors(store, 3, 4)  # streams and keeps the artifact
    nearest_neighbors(store, 5, 4)  # walks the resident copy
    evs = obs_trace.tracer().events()
    ids = [e["args"]["query"] for e in evs if e["name"] == "phase.query"]
    assert len(ids) == 2 and ids[0] != ids[1]
    kids = [[e["name"] for e in evs if e["args"].get("query") == qid] for qid in ids]
    assert sorted(set(kids[0])) == ["phase.query", "pipeline.stage", "pipeline.wait",
                                    "query.collect", "query.panel", "query.resident.fill"]
    assert sorted(set(kids[1])) == ["phase.query", "query.collect", "query.panel"]
    assert kids[0].count("query.panel") == kids[1].count("query.panel") == 2
    assert all("query" not in e["args"] for e in evs if e["name"] == "pipeline.fetch")


def test_timed_stage_counts_each_staged_panel():
    """``pipeline.stage`` is counted while tracing is enabled, once per panel
    put on a device; not at all with tracing off or for host-mode pipelines."""
    import jax

    a = np.ones((64, 4), np.float32)
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def staged(**kw):
        snap = REGISTRY.snapshot()
        with PanelPipeline([_ListHandle(a, 16)], range(0, 64, 16), 16, **kw) as pipe:
            list(pipe)
        return REGISTRY.delta(snap)

    assert "pipeline.stage.calls" not in staged(sharding=sharding)
    assert obs_trace.tracer().events() == []
    obs_trace.enable_tracing()
    assert "pipeline.stage.calls" not in staged()
    d = staged(sharding=sharding, span_args={"query": 7})
    assert d["pipeline.stage.calls"] == 4.0 and d["pipeline.stage.seconds"] > 0.0
    stage = [e for e in obs_trace.tracer().events() if e["name"] == "pipeline.stage"]
    assert [e["args"] for e in stage] == [{"row0": r, "query": 7} for r in range(0, 64, 16)]


def test_jit_compiles_count_a_new_program_once():
    """``jit.compiles`` rises on the first call of a new jit and not on its
    repeat, with tracing off."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.arange(7.0)
    snap = REGISTRY.snapshot()
    f(x).block_until_ready()
    first = REGISTRY.delta(snap)
    assert first.get("jit.compiles") == 1.0
    snap = REGISTRY.snapshot()
    f(x).block_until_ready()
    assert not {k for k in REGISTRY.delta(snap) if k.startswith("jit.")}


def test_compile_counter_installs_once():
    """A second install adds no second listener: one compile, one count."""
    import jax
    import jax.numpy as jnp

    from repro.obs import compiles

    compiles.install()
    compiles.install()
    x = jnp.arange(5.0)
    snap = REGISTRY.snapshot()
    jax.jit(lambda x: x - 2.0)(x).block_until_ready()
    assert REGISTRY.delta(snap).get("jit.compiles") == 1.0


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
            "pipeline.fetch", "solver.solve", "sequence.push"} <= names


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


def test_delta_spans_nest_under_the_chain_phase(ctx1, tmp_path):
    """A traced incremental push leaves ``delta.update`` inside
    ``phase.chain`` with ``delta.sketch``, ``delta.propagate`` and
    ``delta.correct`` inside it, on one host thread line of the
    ``.xplane.pb``; ``delta.update.seconds/.calls`` count only while tracing
    is on; the source scan finds the names under the ``delta.`` prefix."""
    import pathlib
    import re

    from repro.core import delta_chain

    cfg = CommuteConfig(
        k_override=4, q=3, d=3, schedule="xla", warm_start=True,
        incremental_chain=True, delta_budget=10.0,
    )
    det = SequenceDetector(ctx1, cfg, top_k=5)
    seq = gmm_snapshot_sequence(
        ctx1, 32, 3, seed=0, noise=0.02, inject_steps=set(), drift_nodes=3
    )
    snaps = list(seq.snapshots())
    det.push(snaps[0])  # the base build
    m0 = REGISTRY.snapshot()
    det.push(snaps[1])  # an untraced delta counts no span time
    assert "delta.update.calls" not in REGISTRY.delta(m0)

    m1 = REGISTRY.snapshot()
    lines = _profiled_program_spans(tmp_path / "prof", lambda: det.push(snaps[2]))
    counted = REGISTRY.delta(m1)
    assert counted["delta.update.calls"] == 1.0 and counted["delta.update.seconds"] > 0
    assert counted["chain.incremental_updates"] == 1.0
    (line,) = [ln for ln in lines if any(e[0] == "delta.update" for e in ln)]
    (chain,) = [e for e in line if e[0] == "phase.chain"]
    (update,) = [e for e in line if e[0] == "delta.update"]
    assert _inside(update, chain)
    for name in ("delta.sketch", "delta.propagate", "delta.correct"):
        (ev,) = [e for e in line if e[0] == name]
        assert _inside(ev, update), name

    call = re.compile(r'\b(?:span|timed)\(\s*"([^"]+)"')
    names = set(call.findall(pathlib.Path(delta_chain.__file__).read_text()))
    assert names == {"delta.update", "delta.sketch", "delta.propagate", "delta.correct"}
    assert all(name.startswith(obs_trace.PREFIXES) for name in names)

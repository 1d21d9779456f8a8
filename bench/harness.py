"""Runs one cell: set-up, the measured window, the check, the result line.

Two loops, chosen by the traffic file's ``loop``:

* ``write``: closed loop over ``SequenceDetector.push`` with an
  ``EmbeddingStore`` attached, as ``caddelag-run --emb-store`` runs it.
  Each snapshot's adjacency is built from its features by the program's
  graph builder inside the window.  Set-up pushes snapshots 0, 1 and 2, so
  every shape of the steady state (first embedding, first scoring, first
  merge of two top-k lists) is compiled before the window opens; the window
  pushes snapshot 3 onward until ``seconds`` have passed and the push in
  flight has finished.
* ``read``: open loop over ``repro.core.query`` on an opened on-disk
  ``EmbeddingStore``: queries due at the traffic's fixed rate, served in
  order, each timed from its due time to its answer.  Offered above the
  read path's capacity, the queue grows all through the window: the answers
  completed within ``seconds`` give the throughput, and the queries still
  queued when it closes are answered after it, for the check.

With ``trace`` the window runs with the program's fenced phase spans and
under ``jax.profiler``; the harness's own calls carry ``TraceAnnotation``
spans (``bench.*``), so idle gaps on the chip can be put down to them.
After the window the program's state is freed and its answers are compared
with :mod:`bench.reference` (:mod:`bench.check`).
"""

from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path

import jax
import numpy as np

from bench import check, reference, trace_reduce
from bench import traffic as tf
from bench.roofline import peak, query_bytes, transition_matmul_flops
from bench.spec import ROOT, Cell, resolve_cell


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Record:
    """What one run's window left behind, for the per-layer readers."""

    cell: Cell
    count: int  # transitions or queries completed in the window
    window_s: float
    registry: dict  # program counter increments over the window
    spans: list = field(default_factory=list)  # harness spans: (name, t0_s, t1_s)
    trace: trace_reduce.Trace | None = None
    trace_window: tuple[int, int] | None = None  # ns, the traced window
    chips: int = 1
    peaks: dict | None = None
    work: dict = field(default_factory=dict)  # operation and byte counts
    queries: list = field(default_factory=list)  # read: (due_s, start_s, end_s)

    def span_seconds(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.spans if n == name]

    def device_ops(self, chip: int = 0) -> list:
        if self.trace is None:
            return []
        return self.trace.devices.get(chip, [])


class Spans:
    """Harness spans on the host clock, mirrored into the profiler's trace."""

    def __init__(self, profile: bool):
        self.profile = profile
        self.done: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = jax.profiler.TraceAnnotation(name) if self.profile else contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.done.append((name, t0, time.perf_counter()))


def device_info(devices) -> dict:
    d = devices[0]
    peaks = [(x.memory_stats() or {}).get("peak_bytes_in_use", 0) for x in devices]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(max(peaks)),
    }


def mesh_ctx(config: dict, devices):
    from jax.sharding import Mesh

    from repro.core import make_context

    rows, cols = config["mesh"]
    grid = np.array(devices[: rows * cols]).reshape(rows, cols)
    return make_context(Mesh(grid, ("data", "model")))


def commute_config(config: dict, seed: int):
    from repro.core import CommuteConfig

    return CommuteConfig(
        eps_rp=float(config["eps_rp"]), d=int(config["d"]), q=int(config["q"]),
        seed=reference.projection_seed(seed), schedule=config["schedule"],
        solver=config["solver"], warm_start=bool(config["warm_start"]),
        incremental_chain=bool(config["incremental_chain"]),
    )


@contextlib.contextmanager
def _profiled(enabled: bool):
    """Run the block under ``jax.profiler`` and yield a holder that gets the
    loaded :class:`trace_reduce.Trace` once the block is over."""
    holder: dict = {}
    if not enabled:
        yield holder
        return
    from repro.obs import disable_tracing, enable_tracing, tracer

    logdir = tempfile.mkdtemp(prefix="caddelag-bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    enable_tracing(fence=True)
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
        disable_tracing()
        tracer().clear()
        holder["trace"] = trace_reduce.load(trace_reduce.find_xplane(logdir))
        shutil.rmtree(logdir, ignore_errors=True)


@contextlib.contextmanager
def compile_events():
    """Count JAX's lowerings, its requests for an executable and the
    persistent-cache hits among them while the block runs
    (``jax.monitoring``); requests less hits are XLA compiles."""
    counts = {"lowerings": 0, "compile_requests": 0, "cache_hits": 0}
    names = {
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
        "/jax/core/compile/backend_compile_duration": "compile_requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
    }

    def on_duration(name, secs, **kw):
        if name in names:
            counts[names[name]] += 1

    def on_event(name, **kw):
        if name in names:
            counts[names[name]] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def _registry_delta(since) -> dict:
    from repro.obs import REGISTRY

    return REGISTRY.delta(since)


def _registry_snapshot():
    from repro.obs import REGISTRY

    return REGISTRY.snapshot()


# ---------------------------------------------------------------------------
# write loop
# ---------------------------------------------------------------------------

SETUP_SNAPSHOTS = 3


def run_write(cell: Cell, seed: int, seconds: float, trace: bool, devices,
              t_start: float) -> tuple[dict, Record, dict, dict]:
    """One run of a write cell: ``(head, record, {device, metrics}, checks)``."""
    from repro.core import SequenceDetector
    from repro.store import EmbeddingStore

    cfg = cell.config
    ctx = mesh_ctx(cfg, devices)
    used = devices[: cell.chips]
    snaps = tf.snapshots(cell.traffic, cfg, seed)
    ccfg = commute_config(cfg, seed)
    n = snaps.n
    k = ccfg.k_rp(n)
    work_dir = tempfile.mkdtemp(prefix="caddelag-bench-emb-")
    try:
        store = EmbeddingStore.create(
            work_dir, n=n, k=k, seed=ccfg.seed, meta={"cell": cell.name}
        )
        det = SequenceDetector(ctx, ccfg, top_k=int(cfg["top_k"]), emb_store=store)
        for t in range(SETUP_SNAPSHOTS):
            det.push(snaps.adjacency(ctx, t))
        setup_s = time.perf_counter() - t_start

        spans = Spans(profile=trace)
        done: list[tuple[int, jax.Array, jax.Array]] = []
        failed = 0
        a = res = None
        m0 = _registry_snapshot()
        with _profiled(trace) as prof, compile_events() as compiled:
            with spans("bench.window"):
                w0 = time.perf_counter()
                t = SETUP_SNAPSHOTS
                while True:
                    try:
                        with spans("bench.graph"):
                            a = snaps.adjacency(ctx, t)
                            if trace:
                                a.block_until_ready()
                        with spans("bench.push"):
                            res = det.push(a)
                        done.append((t, res.scores, res.top_idx))
                    except Exception:  # a transition that fails is counted, then the run stops
                        traceback.print_exc()
                        failed += 1
                        break
                    t += 1
                    if time.perf_counter() - w0 >= seconds:
                        break
                window_s = time.perf_counter() - w0
        registry = _registry_delta(m0)
        device = device_info(used)
        print(f"window: {len(done)} transitions, {compiled}", file=sys.stderr)

        # The check: a sample of the window's transitions, drawn from the seed.
        rng = tf.host_rng(seed, 6)
        n_check = min(int(cell.traffic["check_transitions"]), len(done))
        picked = sorted(rng.choice(len(done), size=n_check, replace=False).tolist()) if done else []
        answers = [(done[i][0], np.asarray(done[i][1]), np.asarray(done[i][2])) for i in picked]
        count = len(done)
        del det, done, a, res
        store = None
        gc.collect()
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    sharding = ctx.sharding(ctx.matrix_spec)
    gap = 0.0 if answers else float("inf")
    for t2, got, top in answers:
        ref = reference.transition_scores(snaps, t2, cfg, sharding)
        g, parts = check.answer_gap(got, top, ref, int(cfg["top_k"]))
        gap = max(gap, g)
        print(f"transition {t2}: " + ", ".join(f"{k} {v!r}" for k, v in parts.items()),
              file=sys.stderr)
    ok, checks = check.judge({"answer_gap": gap}, cfg["limits"]["write"])

    rec = Record(
        cell=cell, count=count, window_s=window_s, registry=registry, spans=spans.done,
        chips=cell.chips,
        work={"matmul_flops": count * transition_matmul_flops(n, k, int(cfg["d"]), int(cfg["q"]))},
    )
    if trace:
        rec.trace = prof["trace"]
        rec.trace_window = rec.trace.window()
    head = {"correct": ok and failed == 0, "attempted": count + failed, "failed": failed}
    metrics = {}
    if count:
        metrics["transition_s"] = check.per_item(window_s, count)
    metrics["setup_s"] = setup_s
    return head, rec, {"device": device, "metrics": metrics}, checks


# ---------------------------------------------------------------------------
# read loop
# ---------------------------------------------------------------------------


def serve(handle, q: tf.Query):
    from repro.core.query import nearest_neighbors, top_anomalies_from_store

    if q.kind == "nearest_neighbors":
        return nearest_neighbors(handle, q.node, q.k)
    if q.kind == "top_anomalies":
        return top_anomalies_from_store(handle, q.k)
    raise ValueError(f"unknown query kind {q.kind!r}")


def run_read(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> tuple[dict, Record, dict, dict]:
    """One run of a read cell: ``(head, record, {device, metrics}, checks)``."""
    from repro.store import EmbeddingStore

    cfg = cell.config
    n = int(cfg["n"])
    k = reference.k_rp(n, float(cfg["eps_rp"]))
    used = devices[: cell.chips]
    z, vol, deg = tf.embedding_artifact(cell.traffic, n, k, seed)
    work_dir = tempfile.mkdtemp(prefix="caddelag-bench-emb-")
    try:
        EmbeddingStore.create(
            work_dir, n=n, k=k, panel_rows=int(cfg["panel_rows"]),
            seed=reference.projection_seed(seed), meta={"cell": cell.name},
        ).put_embedding("t0000", z, vol, deg)
        handle = EmbeddingStore.open(work_dir).latest()
        schedule = tf.query_schedule(cell.traffic, n, seed, seconds)
        for kind in sorted({q.kind for q in schedule}):
            warm = next(q for q in schedule if q.kind == kind)
            serve(handle, warm)
        setup_s = time.perf_counter() - t_start

        spans = Spans(profile=trace)
        answered, timing = [], []
        failed = 0
        m0 = _registry_snapshot()
        deadline = seconds + float(cell.traffic["grace_s"])
        with _profiled(trace) as prof, compile_events() as compiled:
            with spans("bench.window"):
                w0 = time.perf_counter()
                for i, q in enumerate(schedule):
                    due = w0 + q.due_s
                    now = time.perf_counter()
                    if now - w0 > deadline:
                        failed += len(schedule) - i  # never answered
                        break
                    if now < due:
                        with spans("bench.wait"):
                            time.sleep(due - now)
                    start = time.perf_counter()
                    try:
                        with spans("bench.query"):
                            res = serve(handle, q)
                    except Exception:  # a failed query counts in failed, not in a latency
                        traceback.print_exc()
                        failed += 1
                        continue
                    end = time.perf_counter()
                    answered.append((q, np.asarray(res.idx), np.asarray(res.val)))
                    timing.append((q.due_s, start - w0, end - w0))
                window_s = time.perf_counter() - w0
        registry = _registry_delta(m0)
        device = device_info(used)
        print(f"window: {len(answered)} queries, {compiled}", file=sys.stderr)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    gap = 0.0 if answered else float("inf")
    worst = {"score_gap": 0.0, "rank_gap": 0.0}
    z64 = z.astype(np.float64)
    for q, ids, vals in answered:
        ref = reference.query_answer(z64, vol, q.kind, q.node, q.k)
        g, parts = check.query_gap(ids, vals, ref, q.k, largest=q.kind == "top_anomalies")
        gap = max(gap, g)
        worst = {name: max(worst[name], v) for name, v in parts.items()}
    print("worst query: " + ", ".join(f"{k} {v!r}" for k, v in worst.items()), file=sys.stderr)
    ok, checks = check.judge({"query_gap": gap}, cfg["limits"]["read"])

    rec = Record(
        cell=cell, count=len(answered), window_s=window_s, registry=registry,
        spans=spans.done, chips=cell.chips, queries=timing,
        work={"query_bytes": query_bytes(n, k, np.dtype(np.float32).itemsize)},
    )
    if trace:
        rec.trace = prof["trace"]
        rec.trace_window = rec.trace.window()
    metrics = {}
    in_window = sum(end <= seconds for _, _, end in timing)
    if in_window:
        metrics["queries_per_s"] = check.rate(in_window, seconds)
    metrics["setup_s"] = setup_s
    head = {"correct": ok and failed == 0, "attempted": len(schedule), "failed": failed}
    return head, rec, {"device": device, "metrics": metrics}, checks


LOOPS = {"write": run_write, "read": run_read}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def _units(entries) -> dict:
    return {m["name"]: m["unit"] for m in entries}


def run(workload: str, seed: int, seconds: float, trace: bool, *, root: Path = ROOT,
        require_chip: bool = True, t_start: float | None = None) -> dict:
    """One run of one cell; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = resolve_cell(workload, root)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(
            f"cell {workload} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)"
        )
    if require_chip:
        from repro.launch.cache import enable_compile_cache

        enable_compile_cache()
        # Every program goes to the persistent cache, however fast it
        # compiled: only the first run of a cell in a checkout compiles.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    head, rec, rest, checks = LOOPS[cell.kind](cell, seed, seconds, trace, devices, t_start)
    device = rest["device"]
    out = dict(head)
    if trace:
        rec.peaks = peak(device["kind"]) if require_chip else None
        lo, hi = rec.trace_window
        busy = [
            trace_reduce.busy_ns(rec.device_ops(c), lo, hi) / 1e9 for c in range(cell.chips)
        ]
        device["busy_s"] = float(np.mean(busy))
        device["window_s"] = (hi - lo) / 1e9
        units = _units(cell.per_layer)
        metrics = {}
        for name in units:
            value = cell.reader(name)(rec)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": units[name]}
        out["metrics"] = metrics
        out["device"] = device
        ops = rec.device_ops(0)
        out["breakdown"] = {
            "device_ops": trace_reduce.top_ops(ops, lo, hi),
            "idle_gaps": trace_reduce.idle_by_span(ops, rec.trace.spans, lo, hi),
        }
    else:
        units = _units(cell.end_to_end)
        out["metrics"] = {
            name: {"value": float(v), "unit": units[name]}
            for name, v in rest["metrics"].items() if name in units
        }
        out["device"] = device
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return out

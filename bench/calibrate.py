#!/usr/bin/env python3
"""Readings that set the limits of ``correct`` beside a cell's sound runs,
and the read cell's knee.  Not run by the benchmark's own runs.

    python3 bench/calibrate.py --workload <cell> --control-seeds 1,2,3
    python3 bench/calibrate.py --workload <cell> --faults half_batch,... --fault-seeds 1,2,3
    python3 bench/calibrate.py --workload <read cell> --knee 4,6,8 --knee-seconds 20

The sound runs' readings are the ``checks`` of ``run_cell.py``'s own runs.
``--control-seeds``: the control, the reference computed with three-pass
bfloat16 products in the program's place, compared with the reference at the
cell's size as a run compares the program.  ``--faults``: a run of the cell
through the harness with one fault of :mod:`bench.faults` planted in the
program.  ``--knee``: the read loop at each fixed rate for
``--knee-seconds``; the knee is the highest rate whose p95 stays within the
configuration's latency limit.  One JSON line per reading on standard output.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "tpu")


def _emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def write_control(cell, seed: int, i: int) -> None:
    import jax
    import numpy as np

    from bench import check, harness, reference
    from bench import traffic as tf

    cfg = cell.config
    ctx = harness.mesh_ctx(cfg, jax.devices())
    sharding = ctx.sharding(ctx.matrix_spec)
    top_k = int(cfg["top_k"])
    t2 = harness.SETUP_SNAPSHOTS + i % 4  # with and without the event
    snaps = tf.snapshots(cell.traffic, cfg, seed)
    ref = reference.transition_scores(snaps, t2, cfg, sharding)
    t0 = time.perf_counter()
    ctl = reference.transition_scores(snaps, t2, cfg, sharding, passes=3)
    gap, parts = check.answer_gap(ctl, np.argsort(-ctl, kind="stable")[:top_k], ref, top_k)
    _emit(cell=cell.name, seed=seed, t=t2, role="control", answer_gap=gap, **parts,
          control_s=time.perf_counter() - t0)


def read_control(cell, seed: int, seconds: float) -> None:
    import numpy as np

    from bench import check, reference
    from bench import traffic as tf

    cfg = cell.config
    n = int(cfg["n"])
    k = reference.k_rp(n, float(cfg["eps_rp"]))
    z, vol, _ = tf.embedding_artifact(cell.traffic, n, k, seed)
    z64 = z.astype(np.float64)
    gap, worst = 0.0, {"score_gap": 0.0, "rank_gap": 0.0}
    schedule = tf.query_schedule(cell.traffic, n, seed, seconds)
    for q in schedule:
        largest = q.kind == "top_anomalies"
        ref = reference.query_answer(z64, vol, q.kind, q.node, q.k)
        ctl = reference.query_answer(z, vol, q.kind, q.node, q.k, passes=3)
        order = np.argsort(-ctl if largest else ctl, kind="stable")[: q.k]
        g, parts = check.query_gap(order, ctl[order], ref, q.k, largest=largest)
        gap = max(gap, g)
        worst = {name: max(worst[name], v) for name, v in parts.items()}
    _emit(cell=cell.name, seed=seed, role="control", queries=len(schedule), query_gap=gap, **worst)


def fault_runs(cell, names, seeds, seconds: float) -> None:
    from bench import faults, harness
    from repro.core.tiles import clear_program_cache

    planters = faults.for_cell(cell.kind, cell.chips)
    for name in names:
        for seed in seeds:
            clear_program_cache()  # no program built before the fault may hide it
            with faults.planted(planters[name]):
                out = harness.run(cell.name, seed, seconds, False)
            clear_program_cache()  # nor serve a later run
            _emit(cell=cell.name, seed=seed, role="fault", fault=name, correct=out["correct"],
                  checks=out["checks"])


def knee(cell, rates, seconds: float, seed: int) -> None:
    import jax

    from bench import check, harness

    for rate in rates:
        c = dataclasses.replace(cell, traffic={**cell.traffic, "rate_per_s": rate})
        head, rec, rest, _ = harness.run_read(
            c, seed, seconds, False, jax.devices(), time.perf_counter()
        )
        lat = check.latencies_ms([d for d, _, _ in rec.queries], [e for _, _, e in rec.queries])
        service = [1e3 * (e - s) for _, s, e in rec.queries]
        _emit(cell=cell.name, rate_per_s=rate, queries=len(lat), failed=head["failed"],
              p50_ms=check.percentile(lat, 50), p95_ms=check.percentile(lat, 95),
              service_p50_ms=check.percentile(service, 50),
              service_p95_ms=check.percentile(service, 95),
              last_wait_ms=lat[-1] - service[-1] if lat else None,
              window_s=rec.window_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--read-seconds", type=float, default=40.0,
                    help="a read cell's control answers this many seconds of its schedule")
    ap.add_argument("--faults", default="", help="comma-separated names from bench/faults.py")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--fault-seconds", type=float, default=5.0)
    ap.add_argument("--knee", default="", help="comma-separated query rates")
    ap.add_argument("--knee-seconds", type=float, default=20.0)
    ap.add_argument("--knee-seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    from bench.spec import resolve_cell
    from repro.launch.cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = resolve_cell(args.workload)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    if args.knee:
        knee(cell, [float(r) for r in args.knee.split(",")], args.knee_seconds, args.knee_seed)
    for i, seed in enumerate(ints(args.control_seeds)):
        if cell.kind == "write":
            write_control(cell, seed, i)
        else:
            read_control(cell, seed, args.read_seconds)
    if args.faults:
        fault_runs(cell, args.faults.split(","), ints(args.fault_seeds), args.fault_seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())

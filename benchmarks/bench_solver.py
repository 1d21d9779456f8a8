"""Solver methods head-to-head: iterations-to-tolerance and scratch bytes,
richardson vs chebyshev vs cg, resident vs out-of-core, 1x1 vs 2x2 mesh.

The solve phase is the dominant *recurring* cost of a snapshot sequence once
the chain is built -- and out-of-core, every solver iteration is a streamed
pass over the P2 scratch, so iterations ARE bytes.  This benchmark runs both
methods to the same relative-residual tolerance on the same operator and
reports, per (mesh, storage, method) cell: iterations, final residual, solve
seconds, and `stream_stats().bytes_read` during the solve.  The fixed-q
Richardson baseline (q = the adaptive run's iteration count) pins accuracy:
every method's solution must stay allclose (rtol <= 1e-4) to it.

Verdict (the PR-5 acceptance bar): on the out-of-core solve, Chebyshev must
cut BOTH the iteration count and the scratch `bytes_read` by >= 1.5x at equal
accuracy.

  PYTHONPATH=src python benchmarks/bench_solver.py --n 96 --d 4 --tol 1e-5 \
      --out benchmarks/bench_solver.json
"""

from __future__ import annotations

import os

# The 2x2 mesh needs fake CPU devices BEFORE jax initializes (no-op when the
# importing process already configured XLA_FLAGS, e.g. under pytest).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import (
    SolverSpec,
    chain_product,
    estimate_solution,
    make_context,
    reset_stream_stats,
    solve,
    stream_stats,
    trivial_context,
)
from repro.core.embedding import edge_projection
from repro.graphs import gmm_points, similarity_graph
from repro.store import TileStore

import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from roofline import streamed_solve_flops, streamed_solve_roofline  # noqa: E402

METHODS = ("richardson", "chebyshev", "cg")


def _contexts(n: int):
    """(label, ctx) for the 1x1 mesh and -- devices permitting -- the 2x2."""
    from jax.sharding import Mesh

    out = [("1x1", trivial_context())]
    devs = jax.devices()
    if len(devs) >= 4 and n % 2 == 0:
        out.append(("2x2", make_context(Mesh(np.array(devs[:4]).reshape(2, 2),
                                             ("data", "model")))))
    return out


def run(n=96, d=4, k=8, tol=1e-5, grid=8, seed=0, out_path=None, out=print):
    pts, _ = gmm_points(n, seed)
    rows, verdicts = [], []
    out(f"[bench_solver] n={n} d={d} k_RP={k} tol={tol:.0e} grid={grid}")
    out("[bench_solver]  mesh storage   method     | iters res      solve_s "
        "| read_MB | vs fixed-q")
    for mesh_label, ctx in _contexts(n):
        a_np = np.asarray(similarity_graph(ctx, pts))
        store = TileStore.create(None, n=n, grid=grid)
        h = store.put_snapshot("a", a_np)
        for storage in ("resident", "oocore"):
            src = ctx.put_matrix(a_np) if storage == "resident" else h
            op = chain_product(ctx, src, d, schedule="xla",
                               oocore=storage == "oocore")
            y = edge_projection(ctx, src, seed, k)
            cell = {}
            for method in METHODS:
                reset_stream_stats()
                t0 = time.perf_counter()
                x, rep = solve(ctx, op, y, SolverSpec(method=method, tolerance=tol))
                jax.block_until_ready(x)
                dt = time.perf_counter() - t0
                st = stream_stats()
                cell[method] = (np.asarray(x), rep, dt, st.bytes_read, st.bytes_h2d)
            # Accuracy pin: fixed-q Richardson at the adaptive run's count.
            q_fix = cell["richardson"][1].iterations + 1
            ref = np.asarray(estimate_solution(ctx, op, y, q_fix))
            for method in METHODS:
                x, rep, dt, bread, bh2d = cell[method]
                close = bool(np.allclose(x, ref, rtol=1e-4, atol=1e-3))
                row = {
                    "mesh": mesh_label, "storage": storage, "method": method,
                    "iterations": rep.iterations, "residual": rep.residual,
                    "converged": rep.converged, "rho": rep.rho,
                    "solve_s": dt, "bytes_read": bread,
                    "fixed_q_baseline": q_fix, "allclose_vs_fixed_q": close,
                }
                frac = ""
                if storage == "oocore":
                    roof = streamed_solve_roofline(
                        bytes_read=bread, bytes_h2d=bh2d,
                        flops=streamed_solve_flops(n, k, rep.iterations),
                        seconds=dt,
                        device_kind=jax.devices()[0].device_kind,
                    )
                    row["roofline"] = roof
                    frac = (f" roofline={roof['roofline_frac']} "
                            f"({roof.get('bound')}-bound)")
                rows.append(row)
                out(f"[bench_solver]  {mesh_label:>4s} {storage:8s} {method:10s} | "
                    f"{rep.iterations:5d} {rep.residual:8.1e} {dt:7.2f} | "
                    f"{bread / 1e6:7.2f} | allclose={close}{frac}")
            r_rep, c_rep = cell["richardson"][1], cell["chebyshev"][1]
            iters_ratio = r_rep.iterations / max(c_rep.iterations, 1)
            if storage == "oocore":
                bytes_ratio = cell["richardson"][3] / max(cell["chebyshev"][3], 1)
                ok = iters_ratio >= 1.5 and bytes_ratio >= 1.5 and all(
                    np.allclose(cell[m][0], ref, rtol=1e-4, atol=1e-3)
                    for m in METHODS
                )
                verdicts.append({
                    "mesh": mesh_label, "iters_ratio": iters_ratio,
                    "bytes_ratio": bytes_ratio, "target": 1.5, "pass": ok,
                })
                out(f"[bench_solver]  {mesh_label} oocore: chebyshev saves "
                    f"{iters_ratio:.1f}x iterations, {bytes_ratio:.1f}x scratch "
                    f"reads -> {'PASS' if ok else 'FAIL'} (>= 1.5x)")
            op.release_scratch()

    result = {
        "bench": "solver", "n": n, "d": d, "k_rp": k, "tol": tol, "grid": grid,
        "rows": rows, "verdicts": verdicts,
        "all_pass": all(v["pass"] for v in verdicts) if verdicts else False,
    }
    if out_path:
        Path(out_path).write_text(json.dumps(result, indent=2))
        out(f"[bench_solver] wrote {out_path}")
    return result


def trajectory(out_path, out=print):
    """Canonical perf-trajectory artifact (``BENCH_solver.json``).

    One fixed configuration -- n=96, d=4, out-of-core chebyshev through the
    fused kernel path on a bf16 scratch -- with a stable schema, so the weekly
    CI artifact is directly diffable across PRs: byte counters, solve seconds,
    iterations and the fraction-of-roofline all trend, none get renamed.
    """
    from repro.obs.metrics import registry as _obs_registry

    n, d, k, tol, grid = 96, 4, 8, 1e-5, 8
    ctx = trivial_context()
    pts, _ = gmm_points(n, 0)
    a_np = np.asarray(similarity_graph(ctx, pts))
    store = TileStore.create(None, n=n, grid=grid)
    h = store.put_snapshot("a", a_np)

    reset_stream_stats()
    m0 = _obs_registry().snapshot()
    t0 = time.perf_counter()
    op = chain_product(ctx, h, d, schedule="xla", oocore=True,
                       tile_codec="bf16", use_gemm_kernel=True)
    jax.block_until_ready(op.deg)
    build_s = time.perf_counter() - t0
    bst = stream_stats()
    build = {"seconds": build_s, "bytes_read": bst.bytes_read,
             "bytes_decoded": bst.bytes_decoded, "bytes_h2d": bst.bytes_h2d,
             "bytes_h2d_saved": bst.bytes_h2d_saved, "panels": bst.panels}

    y = edge_projection(ctx, h, 0, k)
    reset_stream_stats()
    t0 = time.perf_counter()
    x, rep = solve(ctx, op, y, SolverSpec(method="chebyshev", tolerance=tol))
    jax.block_until_ready(x)
    solve_s = time.perf_counter() - t0
    sst = stream_stats()
    op.release_scratch()
    roof = streamed_solve_roofline(
        bytes_read=sst.bytes_read, bytes_h2d=sst.bytes_h2d,
        flops=streamed_solve_flops(n, k, rep.iterations), seconds=solve_s,
        device_kind=jax.devices()[0].device_kind,
    )
    result = {
        "bench": "solver_trajectory", "schema": 1,
        "config": {"n": n, "d": d, "k_rp": k, "tol": tol, "grid": grid,
                   "codec": "bf16", "use_gemm_kernel": True,
                   "method": "chebyshev"},
        "build": build,
        "solve": {"seconds": solve_s, "iterations": rep.iterations,
                  "residual": rep.residual, "converged": rep.converged,
                  "bytes_read": sst.bytes_read,
                  "bytes_decoded": sst.bytes_decoded,
                  "bytes_h2d": sst.bytes_h2d,
                  "bytes_h2d_saved": sst.bytes_h2d_saved,
                  "panels": sst.panels},
        "roofline_frac": roof["roofline_frac"],
        "roofline_bound": roof.get("bound"),
        "roofline": roof,
        # Registry counter deltas over the whole bench (repro.obs.metrics):
        # phase/pipeline/cache/solver telemetry.  stream.* is excluded -- the
        # mid-bench reset_stream_stats() breaks delta monotonicity for it,
        # and the byte counters already live in the build/solve blocks.
        "metrics": {
            k_: v for k_, v in _obs_registry().delta(m0).items()
            if not k_.startswith("stream.")
        },
        "residuals": [float(r) for r in rep.residuals],
    }
    Path(out_path).write_text(json.dumps(result, indent=2))
    out(f"[bench_solver] trajectory: {rep.iterations} its in {solve_s:.2f}s, "
        f"{sst.bytes_h2d / 1e6:.1f} MB H2D "
        f"({sst.bytes_h2d_saved / 1e6:.1f} MB saved), roofline "
        f"{roof['roofline_frac']} ({roof.get('bound')}-bound); wrote {out_path}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=96)
    ap.add_argument("--d", type=int, default=4, help="chain length (smaller d "
                    "-> larger rho -> more iterations to accelerate)")
    ap.add_argument("--k", type=int, default=8, help="right-hand sides (k_RP)")
    ap.add_argument("--tol", type=float, default=1e-5)
    ap.add_argument("--grid", type=int, default=8, help="store tiles per side")
    ap.add_argument("--out", default=None, help="write results JSON here")
    ap.add_argument("--trajectory", default=None, metavar="PATH",
                    help="write the canonical fixed-config perf-trajectory "
                         "artifact (BENCH_solver.json) and exit")
    args = ap.parse_args()
    if args.trajectory:
        trajectory(args.trajectory)
        return
    run(n=args.n, d=args.d, k=args.k, tol=args.tol, grid=args.grid,
        out_path=args.out)


if __name__ == "__main__":
    main()

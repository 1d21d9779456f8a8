"""Out-of-core chain build: streamed (store-backed S/T/P) vs resident, the
max-n-under-budget table for the chain working set, and the panel-I/O sweep
(prefetch depth x tile codec x solver batch) with real bytes-moved columns.

The chain product is the O(n^3) hot spot AND (after the PR-2 snapshot store
removed the adjacency term) the remaining HBM bound: a resident build holds
~5 n^2 fp32 matrices (S, T, P, P1, P2).  The out-of-core build spills them
through a TileStore scratch and keeps only O(n * panel) on device; this
benchmark measures both paths, verifies the scores stay allclose, and emits
the max n that fits a given device budget for each mode as JSON.  The sweep
(``--sweep``) exercises the unified panel pipeline's knobs and reports
scratch reads (pre-codec), decoded bytes, and H2D traffic per combination,
so disk-traffic regressions across PRs are visible in the weekly artifact.

  PYTHONPATH=src python benchmarks/bench_oochain.py --n 256 --d 4 \
      --budget-mb 1.0 --sweep --out benchmarks/bench_oochain.json
"""

from __future__ import annotations

import argparse
import json
import math
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import (
    CommuteConfig,
    chain_product,
    detect_anomalies,
    estimate_solution,
    reset_stream_stats,
    solve,
    stream_stats,
    trivial_context,
)
from repro.core.embedding import edge_projection
from repro.store import TileStore
from repro.store.tilestore import _zstd_backend

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from roofline import streamed_solve_flops, streamed_solve_roofline  # noqa: E402


def _sym(n: int, seed: int) -> np.ndarray:
    a = np.abs(np.random.default_rng(seed).normal(size=(n, n))).astype(np.float32)
    a = (a + a.T) / 2.0
    np.fill_diagonal(a, 0.0)
    return a


def run(n=256, d=4, q=4, grid=None, budget_mb=1.0, do_sweep=False, out_path=None,
        out=print):
    ctx = trivial_context()
    budget = int(budget_mb * 1e6)
    a1, a2 = _sym(n, 0), _sym(n, 1)
    store = TileStore.create(None, n=n, grid=grid or 8)
    h1, h2 = store.put_snapshot("t0", a1), store.put_snapshot("t1", a2)
    work = TileStore.create(None, n=n, grid=grid or 8)
    ph = store.tile_rows
    cfg = CommuteConfig(eps_rp=1e-2, d=d, q=q, schedule="xla")
    cfg_oo = CommuteConfig(eps_rp=1e-2, d=d, q=q, schedule="xla", oocore=True)

    # -- resident chain build (warm both once for compile parity) ------------
    chain_product(ctx, ctx.put_matrix(a1), d, schedule="xla")
    t0 = time.perf_counter()
    op_r = chain_product(ctx, ctx.put_matrix(a1), d, schedule="xla")
    jax.block_until_ready(op_r.p2)
    resident_s = time.perf_counter() - t0
    resident_peak = 5 * n * n * 4  # S, T, P, P1, P2 fp32

    # -- out-of-core chain build --------------------------------------------
    chain_product(ctx, h1, d, schedule="xla", oocore=True,
                  oocore_work=work, oocore_panel_rows=ph)
    reset_stream_stats()
    t0 = time.perf_counter()
    op_o = chain_product(ctx, h1, d, schedule="xla", oocore=True,
                         oocore_work=work, oocore_panel_rows=ph)
    oocore_s = time.perf_counter() - t0
    st = stream_stats()

    np.testing.assert_allclose(op_o.p2.to_numpy(), np.asarray(op_r.p2),
                               rtol=1e-3, atol=1e-3)
    res_r = detect_anomalies(ctx, ctx.put_matrix(a1), ctx.put_matrix(a2), cfg, top_k=10)
    res_o = detect_anomalies(ctx, h1, h2, cfg_oo, top_k=10)
    close = bool(np.allclose(np.asarray(res_o.scores), np.asarray(res_r.scores),
                             rtol=1e-4, atol=1e-3))

    out(f"[bench_oochain] n={n} d={d} panel={ph} rows "
        f"({n * n * 4 / 1e6:.2f} MB/matrix, resident chain set "
        f"{resident_peak / 1e6:.2f} MB)")
    out(f"[bench_oochain] resident build: {resident_s:.2f}s, "
        f"peak chain residency {resident_peak / 1e6:.2f} MB "
        f"-> {'WITHIN' if resident_peak <= budget else 'OVER'} "
        f"{budget / 1e6:.2f} MB budget")
    out(f"[bench_oochain] oocore build:   {oocore_s:.2f}s, "
        f"peak device panel residency {st.peak_live_bytes / 1e6:.2f} MB "
        f"({st.panels} panels, {st.bytes_read / 1e6:.1f} MB scratch reads, "
        f"{st.bytes_decoded / 1e6:.1f} MB decoded, {st.bytes_h2d / 1e6:.1f} MB H2D) "
        f"-> {'WITHIN' if st.peak_live_bytes <= budget else 'OVER'} budget")
    out(f"[bench_oochain] end-to-end scores allclose: {close}")

    # -- max n within the device budget, per mode ----------------------------
    # resident: 5 n^2 * 4 bytes.  oocore with a g x g scratch grid: one
    # accumulator panel + one streamed panel + one block ~= 3 * (n/g) * n * 4.
    n_res = int(math.isqrt(budget // 20))
    table = []
    for g in (4, 8, 16, 32):
        n_oo = int(math.isqrt(budget * g // 12))
        table.append({"grid": g, "max_n_oocore": n_oo})
        out(f"[bench_oochain] budget {budget / 1e6:.2f} MB: max n resident ~{n_res}, "
            f"oocore grid={g} ~{n_oo} ({n_oo / max(n_res, 1):.1f}x)")

    sweep_rows = sweep(n=n, d=d, q=q, grid=grid, budget=budget, out=out) if do_sweep else None

    result = {
        "bench": "oochain",
        "n": n, "d": d, "q": q, "panel_rows": ph,
        "budget_mb": budget / 1e6,
        "resident_s": resident_s,
        "oocore_s": oocore_s,
        "resident_peak_mb": resident_peak / 1e6,
        "oocore_peak_mb": st.peak_live_bytes / 1e6,
        "oocore_panels": st.panels,
        "oocore_h2d_mb": st.bytes_h2d / 1e6,
        "oocore_read_mb": st.bytes_read / 1e6,
        "oocore_decoded_mb": st.bytes_decoded / 1e6,
        "resident_within_budget": resident_peak <= budget,
        "oocore_within_budget": st.peak_live_bytes <= budget,
        "scores_allclose": close,
        "max_n_resident": n_res,
        "max_n_oocore": table,
        "sweep": sweep_rows,
    }
    if out_path:
        Path(out_path).write_text(json.dumps(result, indent=2))
        out(f"[bench_oochain] wrote {out_path}")
    return result


def sweep(n=128, d=3, q=8, grid=None, budget=int(1e6), out=print):
    """Panel-I/O knob sweep: prefetch depth x tile codec x solver batch.

    One out-of-core build + Richardson solve per combination, with the
    build/solve phases' byte counters split out -- the bytes-moved columns
    are what the codec and the iteration batching are each supposed to bend
    (codec: bytes_read < bytes_decoded; solver_batch: solve-phase reads drop
    ~batch x), so a combination that stops bending them is a regression.
    """
    ctx = trivial_context()
    g = grid or 8
    a = _sym(n, 0)
    store = TileStore.create(None, n=n, grid=g)
    h = store.put_snapshot("t0", a)
    # Combination-invariant RHS, computed once OUTSIDE the sweep: its panel
    # traffic belongs to neither the build nor the solve phase and must not
    # pollute the per-combination counters or budget verdicts.
    y = edge_projection(ctx, h, 0, 8)
    ref = None

    codecs = ["raw", "bf16"] + (["zstd"] if _zstd_backend() is not None else [])
    if _zstd_backend() is None:
        out("[bench_oochain] sweep: no zstd backend installed; sweeping raw/bf16")
    rows = []
    out(f"[bench_oochain] sweep n={n} d={d} q={q} grid={g} "
        f"(budget {budget / 1e6:.2f} MB)")
    out("[bench_oochain]  depth codec batch | build_s solve_s | "
        "bread_MB sread_MB dec_MB h2d_MB | peak_MB verdict close")
    for codec in codecs:
        for depth in (1, 2, 4):
            for batch in (1, 4):
                work = TileStore.create(None, n=n, grid=g, codec=codec)
                reset_stream_stats()
                t0 = time.perf_counter()
                op = chain_product(ctx, h, d, oocore=True, oocore_work=work,
                                   prefetch_depth=depth)
                jax.block_until_ready(op.deg)
                build_s = time.perf_counter() - t0
                bst = stream_stats()
                build_read, build_dec, build_h2d = (
                    bst.bytes_read, bst.bytes_decoded, bst.bytes_h2d)

                reset_stream_stats()
                t0 = time.perf_counter()
                z = estimate_solution(ctx, op, y, q, solver_batch=batch,
                                      prefetch_depth=depth)
                jax.block_until_ready(z)
                solve_s = time.perf_counter() - t0
                sst = stream_stats()
                op.release_scratch()

                if ref is None:
                    ref = np.asarray(z)  # depth/batch never change numerics
                tol = 1e-4 if codec != "bf16" else 5e-2
                close = bool(np.allclose(np.asarray(z), ref, rtol=tol, atol=tol))
                peak = max(bst.peak_live_bytes, sst.peak_live_bytes)
                verdict = "WITHIN" if peak <= budget else "OVER"
                row = {
                    "prefetch_depth": depth, "codec": work.manifest.codec,
                    "solver_batch": batch,
                    "build_s": build_s, "solve_s": solve_s,
                    "build_read_mb": build_read / 1e6,
                    "build_decoded_mb": build_dec / 1e6,
                    "build_h2d_mb": build_h2d / 1e6,
                    "solve_read_mb": sst.bytes_read / 1e6,
                    "solve_decoded_mb": sst.bytes_decoded / 1e6,
                    "solve_h2d_mb": sst.bytes_h2d / 1e6,
                    "bytes_moved_mb": (build_read + sst.bytes_read) / 1e6,
                    "peak_mb": peak / 1e6,
                    "within_budget": peak <= budget,
                    "solution_close": close,
                }
                rows.append(row)
                out(f"[bench_oochain]  {depth:5d} {codec:>5s} {batch:5d} | "
                    f"{build_s:7.2f} {solve_s:7.2f} | "
                    f"{build_read / 1e6:8.2f} {sst.bytes_read / 1e6:8.2f} "
                    f"{(build_dec + sst.bytes_decoded) / 1e6:6.1f} "
                    f"{(build_h2d + sst.bytes_h2d) / 1e6:6.1f} | "
                    f"{peak / 1e6:7.2f} {verdict:>6s} {close}")
    return rows


def trajectory(out_path, out=print):
    """Canonical perf-trajectory artifact (``BENCH_oochain.json``).

    One fixed configuration -- n=128, d=3, q=6, grid 8, bf16 scratch through
    the fused kernel path -- with a stable schema (byte counters, phase
    seconds, iterations, fraction-of-roofline), so the weekly CI artifact
    trends across PRs without renames.
    """
    from repro.obs.metrics import registry as _obs_registry

    n, d, q, k, g = 128, 3, 6, 6, 8
    ctx = trivial_context()
    a = _sym(n, 0)
    store = TileStore.create(None, n=n, grid=g)
    h = store.put_snapshot("t0", a)

    reset_stream_stats()
    m0 = _obs_registry().snapshot()
    t0 = time.perf_counter()
    op = chain_product(ctx, h, d, oocore=True, tile_codec="bf16",
                       use_gemm_kernel=True)
    jax.block_until_ready(op.deg)
    build_s = time.perf_counter() - t0
    bst = stream_stats()
    build = {"seconds": build_s, "bytes_read": bst.bytes_read,
             "bytes_decoded": bst.bytes_decoded, "bytes_h2d": bst.bytes_h2d,
             "bytes_h2d_saved": bst.bytes_h2d_saved, "panels": bst.panels,
             "peak_live_bytes": bst.peak_live_bytes}

    y = edge_projection(ctx, h, 0, k)
    reset_stream_stats()
    t0 = time.perf_counter()
    z, rep = solve(ctx, op, y, fixed_q=q)
    jax.block_until_ready(z)
    solve_s = time.perf_counter() - t0
    sst = stream_stats()
    op.release_scratch()
    roof = streamed_solve_roofline(
        bytes_read=sst.bytes_read, bytes_h2d=sst.bytes_h2d,
        flops=streamed_solve_flops(n, k, rep.iterations), seconds=solve_s,
        device_kind=jax.devices()[0].device_kind,
    )
    result = {
        "bench": "oochain_trajectory", "schema": 1,
        "config": {"n": n, "d": d, "q": q, "k_rp": k, "grid": g,
                   "codec": "bf16", "use_gemm_kernel": True},
        "build": build,
        "solve": {"seconds": solve_s, "iterations": rep.iterations,
                  "residual": rep.residual,
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
            k: v for k, v in _obs_registry().delta(m0).items()
            if not k.startswith("stream.")
        },
    }
    Path(out_path).write_text(json.dumps(result, indent=2))
    out(f"[bench_oochain] trajectory: build {build_s:.2f}s, solve "
        f"{solve_s:.2f}s/{rep.iterations} its, {sst.bytes_h2d / 1e6:.1f} MB "
        f"H2D ({sst.bytes_h2d_saved / 1e6:.1f} MB saved), roofline "
        f"{roof['roofline_frac']} ({roof.get('bound')}-bound); wrote {out_path}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--d", type=int, default=4)
    ap.add_argument("--q", type=int, default=4)
    ap.add_argument("--grid", type=int, default=None, help="store/scratch tiles per side")
    ap.add_argument("--budget-mb", type=float, default=1.0)
    ap.add_argument("--sweep", action="store_true",
                    help="prefetch-depth x codec x solver-batch sweep with "
                         "bytes-moved columns")
    ap.add_argument("--out", default=None, help="write results JSON here")
    ap.add_argument("--trajectory", default=None, metavar="PATH",
                    help="write the canonical fixed-config perf-trajectory "
                         "artifact (BENCH_oochain.json) and exit")
    args = ap.parse_args()
    if args.trajectory:
        trajectory(args.trajectory)
        return
    run(n=args.n, d=args.d, q=args.q, grid=args.grid, budget_mb=args.budget_mb,
        do_sweep=args.sweep, out_path=args.out)


if __name__ == "__main__":
    main()

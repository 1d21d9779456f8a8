"""CADDeLaG driver: the paper's anomaly-detection pipeline on a mesh.

Runs the sequence engine end-to-end on a synthetic GMM snapshot sequence
(paper section 4.2.1) or a climate-like sequence, with the matmul schedule,
chain length d, Richardson iterations q, eps_RP and the sequence length T all
selectable -- the knobs of the paper's accuracy study (Fig. 2) and scaling
study (Fig. 3).  Every snapshot's chain operator is built exactly once and
reused for both transitions it touches.

  python -m repro.launch.caddelag_run --n 256 --t-steps 4 --schedule cannon

Out-of-core mode: ``--store DIR`` writes the synthetic sequence into a tiled
on-disk snapshot store (resumable; skipped if already present) and scores it
end-to-end from disk -- adjacencies are streamed through the tile executor
one row panel at a time and are never fully device-resident.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core import CommuteConfig, SequenceDetector, make_context, reset_stream_stats, stream_stats
from repro.graphs import climate_snapshot_sequence, gmm_snapshot_sequence, store_snapshot_sequence
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh


def _default_grid(n: int, n_row_shards: int) -> int:
    """Finest store grid with panels of >= 32 rows that divide the row shards."""
    for g in (16, 8, 4, 2):
        if n % g == 0 and (n // g) % n_row_shards == 0 and n // g >= 32:
            return g
    return 1


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256, help="graph nodes")
    ap.add_argument("--t-steps", type=int, default=2, help="snapshots in the sequence")
    ap.add_argument("--dataset", default="gmm", choices=["gmm", "climate"])
    ap.add_argument("--drift-nodes", type=int, default=None,
                    help="gmm dataset only: slowly-drifting sequence where "
                         "only this many nodes move per step and no edges are "
                         "injected (near-low-rank dS per transition -- the "
                         "regime --incremental-chain targets)")
    ap.add_argument("--schedule", default="cannon", choices=["xla", "summa", "cannon"])
    ap.add_argument("--eps", type=float, default=1e-3)
    ap.add_argument("--d", type=int, default=6)
    ap.add_argument("--q", type=int, default=10)
    ap.add_argument("--top-k", type=int, default=20)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--use-kernel", action="store_true", help="Pallas tile bodies")
    ap.add_argument("--donate", action="store_true", help="free outgoing snapshots eagerly")
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="score out-of-core from a tiled snapshot store at DIR")
    ap.add_argument("--store-grid", type=int, default=None,
                    help="tiles per side when creating the store (default: auto)")
    ap.add_argument("--emb-store", default=None, metavar="DIR",
                    help="publish each snapshot's committed (Z, vol, deg) "
                         "embedding into an EmbeddingStore at DIR -- the "
                         "artifact caddelag-query serves top-k / neighbor "
                         "reads from without re-running the pipeline")
    ap.add_argument("--emb-codec", default="raw", choices=["raw", "bf16"],
                    help="embedding artifact codec (bf16 halves bytes; the "
                         "query kernel decodes it on-device)")
    ap.add_argument("--oocore-chain", action="store_true",
                    help="run the squaring chain out-of-core: S/T/P spill through a "
                         "TileStore scratch, device residency is panels, not n^2")
    ap.add_argument("--oocore-dir", default=None, metavar="DIR",
                    help="scratch dir for --oocore-chain working matrices "
                         "(default: host-RAM scratch)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="panel-pipeline staging depth: how many row panels the "
                         "background prefetch thread keeps decoded ahead of compute")
    ap.add_argument("--tile-codec", default="raw", choices=["raw", "bf16", "zstd"],
                    help="tile storage codec for --store and the --oocore-chain "
                         "scratch (bf16 halves bytes; zstd needs the optional "
                         "'zstandard' package and falls back to raw without it)")
    ap.add_argument("--use-gemm-kernel", action="store_true",
                    help="fused Pallas stream-GEMM path for the out-of-core "
                         "chain and solver: panels ship in stored form (bf16 "
                         "bit patterns decode on-device, halving H2D) and "
                         "each streamed solve iteration is one fused pass "
                         "over the P2 scratch; interpret mode on CPU, "
                         "no effect without --oocore-chain")
    ap.add_argument("--solver-batch", type=int, default=1,
                    help="solver iterations per scratch stream of P2: the "
                         "solver streams the store once per batch and replays "
                         "decoded panels from host RAM (identical scores, "
                         "~batch x fewer scratch reads)")
    ap.add_argument("--solver", default="richardson",
                    choices=["richardson", "chebyshev", "cg"],
                    help="iterative method for the chain solve (see "
                         "repro.core.solvers): chebyshev accelerates the "
                         "Richardson iteration to ~sqrt-fewer iterations using "
                         "the rho(S^{2^d}) estimate cached at chain build "
                         "(adapted upward in-solve when the measured "
                         "contraction misses the predicted rate); cg runs "
                         "conjugate gradients on the deflated SPD subspace "
                         "with degree-weighted inner products")
    ap.add_argument("--warm-start", action="store_true",
                    help="seed each transition's solve with the previous "
                         "snapshot's solution (sequence solves only; "
                         "transition 1 onward) -- slowly-drifting sequences "
                         "converge in far fewer iterations at the same "
                         "tolerance, with scores allclose to cold solves")
    ap.add_argument("--incremental-chain", action="store_true",
                    help="incremental delta-chain updates (repro.core."
                         "delta_chain): on slowly-drifting transitions the "
                         "O(n^3) chain rebuild is replaced by a rank-r "
                         "correction propagated with skinny O(n^2 r) panel "
                         "GEMMs against the retained base chain; a sketched "
                         "drift monitor falls back to a full rebuild when "
                         "||dS||/||S|| exceeds --delta-budget")
    ap.add_argument("--delta-rank", type=int, default=4,
                    help="rank of the incremental chain correction (higher = "
                         "more accurate corrected scores, more skinny-GEMM "
                         "work per transition)")
    ap.add_argument("--delta-budget", type=float, default=0.1,
                    help="drift gate for --incremental-chain: sketched "
                         "||dS||_F / ||S||_F (measured against the last full "
                         "rebuild) above which the transition triggers a full "
                         "rebuild")
    ap.add_argument("--solver-tol", type=float, default=None,
                    help="stop the solve when the relative preconditioned "
                         "residual drops below this (default: fixed q "
                         "iterations, the paper's worst-case bound)")
    ap.add_argument("--solver-max-iters", type=int, default=None,
                    help="hard cap on solver refinement steps (default: "
                         "derived from --delta when given; a 300-step safety "
                         "cap when only --solver-tol is set; else q-1)")
    ap.add_argument("--delta", type=float, default=None,
                    help="paper accuracy parameter: bounds iterations at "
                         "q = ceil(log 1/delta) when no explicit cap is given")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a Chrome trace-event JSON of the run "
                         "(Perfetto / chrome://tracing loadable); enables "
                         "span fencing, so phase spans measure honest device "
                         "walls at the cost of extra synchronization")
    ap.add_argument("--run-report", default=None, metavar="OUT.json",
                    help="write a structured RunReport JSON (schema-versioned; "
                         "see repro.obs.report): per-transition phase/bytes/"
                         "solver telemetry, cache hit rates, roofline fraction")
    ap.add_argument("--strict-convergence", action="store_true",
                    help="exit nonzero (code 2) if any transition's solve "
                         "finished NOT-CONVERGED")
    args = ap.parse_args(argv)

    from repro.obs import enable_tracing, tracer
    from repro.obs.report import build_run_report, save_run_report

    enable_compile_cache()
    if args.trace is not None:
        enable_tracing(fence=True)

    # Resolve the codec once up front: a backend-less zstd request degrades to
    # raw (with a warning) and everything downstream -- scratch stores, the
    # snapshot store, the summary lines -- must report what tiles really are.
    from repro.store import resolve_codec

    effective_codec = resolve_codec(args.tile_codec).name

    mesh = make_mesh(data=args.data, model=args.model)
    ctx = make_context(mesh)
    cfg = CommuteConfig(eps_rp=args.eps, d=args.d, q=args.q, schedule=args.schedule,
                        oocore=args.oocore_chain, oocore_dir=args.oocore_dir,
                        prefetch_depth=args.prefetch_depth,
                        tile_codec=args.tile_codec, solver_batch=args.solver_batch,
                        use_gemm_kernel=args.use_gemm_kernel,
                        solver=args.solver, solver_tol=args.solver_tol,
                        solver_max_iters=args.solver_max_iters, delta=args.delta,
                        warm_start=args.warm_start,
                        incremental_chain=args.incremental_chain,
                        delta_rank=args.delta_rank,
                        delta_budget=args.delta_budget)

    if args.dataset == "gmm":
        n_nodes = args.n
        if args.drift_nodes is not None:
            seq = gmm_snapshot_sequence(
                ctx, n_nodes, args.t_steps, seed=0, noise=0.02,
                inject_steps=set(), drift_nodes=args.drift_nodes,
            )
        else:
            seq = gmm_snapshot_sequence(ctx, n_nodes, args.t_steps, seed=0, inject_p=0.01)
    else:
        side = int(np.sqrt(args.n))
        n_nodes = side * (args.n // side)  # climate grid may round n down
        if n_nodes != args.n:
            print(f"[caddelag] climate grid {side}x{args.n // side}: using n={n_nodes}")
        seq = climate_snapshot_sequence(ctx, side, args.n // side, args.t_steps, sigma=1.0)

    emb_store = None
    if args.emb_store is not None:
        from repro.store import EmbeddingStore

        emb_store = EmbeddingStore.create(
            args.emb_store, n=n_nodes, k=cfg.k_rp(n_nodes),
            codec=args.emb_codec, seed=cfg.seed,
            meta={"dataset": args.dataset, "n": n_nodes, "seed": 0},
        )

    det = SequenceDetector(
        ctx, cfg, top_k=args.top_k, use_kernel=args.use_kernel, donate=args.donate,
        emb_store=emb_store,
    )
    if args.store is not None:
        from repro.store import TileStore

        grid = args.store_grid or _default_grid(n_nodes, ctx.n_row_shards)
        # meta fingerprints the generator so a reused directory with stale
        # content (different dataset/params) is rejected, not silently scored.
        meta = {"dataset": args.dataset, "n": n_nodes, "seed": 0}
        store = TileStore.create(
            args.store, n=n_nodes, grid=grid, codec=args.tile_codec, meta=meta
        )
        ids = store_snapshot_sequence(store, seq)
        reset_stream_stats()
        res = det.run(store.snapshot(sid) for sid in ids)
        st = stream_stats()
        # One StreamStats covers the run: with --oocore-chain the adjacency
        # panels and the chain-scratch panels share these counters, so label
        # the line accordingly rather than misattributing one to the other.
        what = "adjacency + chain scratch" if args.oocore_chain else "adjacency"
        print(
            f"[caddelag] store={args.store} grid={grid}x{grid} "
            f"codec={store.manifest.codec} prefetch={args.prefetch_depth}: "
            f"{args.t_steps} snapshots, {args.t_steps * store.snapshot_nbytes / 1e6:.1f} MB logical; "
            f"read {st.bytes_read / 1e6:.1f} MB from store, decoded "
            f"{st.bytes_decoded / 1e6:.1f} MB, streamed {st.bytes_h2d / 1e6:.1f} MB "
            f"H2D ({what}) in {st.panels} panels, peak device panel residency "
            f"{st.peak_live_bytes / 1e6:.2f} MB"
        )
    else:
        reset_stream_stats()
        res = det.run(seq.snapshots())
    if args.oocore_chain:
        st = stream_stats()
        extra = " (incl. adjacency streaming)" if args.store is not None else ""
        saved = (
            f" ({st.bytes_h2d_saved / 1e6:.1f} MB saved by on-device decode)"
            if st.bytes_h2d_saved else ""
        )
        print(
            f"[caddelag] oocore chain: working matrices spilled to "
            f"{args.oocore_dir or 'host RAM'} (codec={effective_codec}, "
            f"solver_batch={args.solver_batch}); {st.panels} panels{extra}, "
            f"{st.bytes_read / 1e6:.1f} MB scratch reads, {st.bytes_h2d / 1e6:.1f} MB "
            f"H2D{saved}, peak device panel residency "
            f"{st.peak_live_bytes / 1e6:.2f} MB (vs ~{5 * n_nodes * n_nodes * 4 / 1e6:.2f} MB "
            f"resident chain working set)"
        )

    if emb_store is not None:
        print(
            f"[caddelag] embedding artifacts -> {args.emb_store}: "
            f"{len(emb_store.embedding_ids)} committed (codec="
            f"{emb_store.manifest.codec}, panel_rows={emb_store.panel_rows}); "
            f"serve reads with: caddelag-query --store {args.emb_store} "
            f"--top-k {args.top_k}"
        )

    print(
        f"[caddelag] n={args.n} T={args.t_steps} schedule={args.schedule} "
        f"d={args.d} q={args.q} eps={args.eps}: "
        f"{res.chain_builds} chain builds for {len(res.transitions)} transitions"
    )
    if args.incremental_chain:
        from repro.obs.metrics import REGISTRY

        print(
            f"[caddelag] incremental chain: "
            f"{int(REGISTRY.value('chain.full_rebuilds'))} full rebuilds, "
            f"{int(REGISTRY.value('chain.incremental_updates'))} incremental "
            f"updates, {int(REGISTRY.value('chain.drift_fallbacks'))} drift "
            f"and {int(REGISTRY.value('chain.solve_fallbacks'))} solve "
            f"fallbacks (rank={args.delta_rank}, budget={args.delta_budget}, "
            f"last drift={REGISTRY.gauge('chain.drift_last'):.2e}); "
            f"delta GEMM {REGISTRY.value('chain.delta_gemm_flops') / 1e9:.3f} "
            f"GFLOP, {REGISTRY.value('chain.delta_gemm_bytes') / 1e6:.1f} MB "
            f"operand traffic"
        )
    for t, (r, dt) in enumerate(zip(res.transitions, res.transition_seconds)):
        found = np.asarray(r.top_idx).tolist()
        # truth is ranked strongest-first; score recall against its top-k slice
        truth = set(np.asarray(seq.truth[t])[: args.top_k].tolist())
        hits = len(truth & set(found)) if truth else "-"
        print(
            f"[caddelag]   transition {t}->{t + 1}: {dt:6.2f}s  "
            f"top-{args.top_k} truth overlap: {hits}/{len(truth) if truth else 0}"
        )
        # Per-transition solver telemetry: one SolveReport per endpoint
        # embedding (the left one was built by the previous push).
        reps = [rep for rep in r.solve_reports if rep is not None]
        if reps:
            its = "+".join(str(rep.iterations) for rep in reps)
            worst = max(reps, key=lambda rep: rep.residual)
            scratch = sum(rep.bytes_read for rep in reps)
            io = f", {scratch / 1e6:.1f} MB scratch" if any(
                rep.streamed for rep in reps) else ""
            conv = "" if all(rep.converged for rep in reps) else "  NOT-CONVERGED"
            warm = " warm" if any(rep.warm_start for rep in reps) else ""
            print(
                f"[caddelag]     solver[{worst.method}{warm}]: {its} its "
                f"(cap {worst.max_iters}), res {worst.residual:.1e}{io}{conv}"
            )
    total = sum(res.transition_seconds)
    print(f"[caddelag] total {total:.2f}s "
          f"({total / max(len(res.transitions), 1):.2f}s per transition, amortized)")
    g_idx = np.asarray(res.global_top_idx).tolist()
    g_step = np.asarray(res.global_top_step).tolist()
    print(f"[caddelag] sequence-wide top-{args.top_k}: "
          f"{[f'{i}@t{s}' for i, s in zip(g_idx, g_step)]}")

    # Convergence summary: count transitions where any endpoint solve ended
    # NOT-CONVERGED (the per-transition lines above flag which ones).
    bad = sum(
        1 for r in res.transitions
        if any(rep is not None and not rep.converged for rep in r.solve_reports)
    )
    if bad:
        print(
            f"[caddelag] WARNING: {bad}/{len(res.transitions)} transitions "
            f"had a NOT-CONVERGED solve"
        )

    if args.run_report is not None:
        doc = build_run_report(
            config={k.replace("-", "_"): v for k, v in vars(args).items()},
            result=res,
            n=n_nodes,
            k_rp=cfg.k_rp(n_nodes),
        )
        save_run_report(doc, args.run_report)
        print(f"[caddelag] run report -> {args.run_report}")
    if args.trace is not None:
        tracer().save(args.trace)
        print(f"[caddelag] trace -> {args.trace} "
              f"({len(tracer().events())} events; open in Perfetto)")

    if bad and args.strict_convergence:
        raise SystemExit(2)


if __name__ == "__main__":
    main()

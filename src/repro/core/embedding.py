"""Commute-time embedding (paper Algorithm 3, CommuteTimeEmbedding).

For j = 1..k_RP:  y_j = B^T W^{1/2} q_j  (edge-space Rademacher projection,
generated counter-based -- see :mod:`repro.core.rng`),  solve L z_j = y_j with
the precomputed chain operator.  Stack Z = [z_1 .. z_k]; then

    c(i, j) ~= V_G * || Z_i - Z_j ||^2.

The edge projection never materializes the m = n^2 edge space: each device
reduces sqrt(A) (.) Q over its own adjacency tile, regenerating Q from integer
hashes.  One pass over A per batch of k_RP columns, zero stored randomness.
"""

from __future__ import annotations

import math
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import rng as crng
from repro.core.chain import ChainOperator, chain_product, factored_chain, use_factored
from repro.core.distmatrix import F32_PRECISION, DistContext
from repro.core.solvers import SolveReport, SolverSpec, solve
from repro.core.solvers.power import DEFAULT_POWER_ITERS
from repro.core.tiles import is_streamable, tile_map, tile_stream
from repro.obs import REGISTRY, phase


@dataclass(frozen=True)
class CommuteConfig:
    """Accuracy knobs, named as in the paper (eps_RP, d, q)."""

    eps_rp: float = 1e-3
    d: int = 6  # inverse-chain length
    q: int = 10  # Richardson iterations
    seed: int = 0
    schedule: str = "cannon"
    dtype: object = jnp.float32
    deflate: bool = True
    fuse_l: bool = False
    k_override: int | None = None  # force embedding dim (tests/ablations)
    # Out-of-core chain: spill S/T/P/P1/P2 through a TileStore scratch so the
    # chain build (and the solver, via store-backed P1/P2) is panel-bounded.
    oocore: bool = False
    oocore_dir: str | None = None  # scratch dir; None = host-RAM scratch
    oocore_panel_rows: int | None = None  # override the streaming unit
    # Panel-I/O knobs (see repro.store.PanelPipeline): staging depth of the
    # background prefetch, scratch-tile storage codec (raw / bf16 / zstd),
    # and Richardson iteration batching (stream P2 once per `solver_batch`
    # iterations, replay from host RAM -- cuts solve-phase scratch reads).
    prefetch_depth: int = 2
    tile_codec: str = "raw"
    solver_batch: int = 1
    # Fused Pallas stream-GEMM path for the out-of-core hot loop: panels ship
    # at stored width (bf16 bit patterns decode in-kernel, halving H2D) and
    # streamed solve iterations fuse mat-vec + update + residual into one
    # pass over the panel stream.  Interpret-mode fallback off-TPU.
    use_gemm_kernel: bool = False
    # Solver subsystem (see repro.core.solvers): the iterative method, an
    # optional relative-residual target (None = fixed `q` iterations, the
    # historical behaviour), an optional hard step cap, and the paper's delta
    # (q = ceil(log 1/delta)) as an alternative way to bound iterations.
    solver: str = "richardson"  # "richardson" | "chebyshev" | "cg"
    solver_tol: float | None = None
    solver_max_iters: int | None = None
    delta: float | None = None
    # Warm-start sequence solves from the previous snapshot's solution: the
    # detector carries Embedding.z forward, so a slowly-drifting transition's
    # first residual starts ~|dA| instead of ~1 and tolerance-targeted solves
    # converge in far fewer iterations.  Scores stay allclose to cold solves
    # (same tolerance, same stopping metric); only the iteration count drops.
    warm_start: bool = False
    # Incremental delta-chain updates (repro.core.delta_chain): on a
    # slowly-drifting transition, skip the O(n^3) chain rebuild -- compress
    # the change in S to a rank-`delta_rank` factorisation, propagate it
    # through the squaring recurrence as skinny panel GEMMs against the
    # retained base chain (O(n^2 r) per level), and attach the result to the
    # operator as a low-rank correction every solve applies.  `delta_budget`
    # is the drift gate: the sketched relative drift ||dS|| / ||S|| (always
    # measured against the last *full-rebuild* base) above which the
    # detector falls back to a full rebuild and collapses the accumulated
    # correction into a fresh base.  Drift only slows the corrected solve,
    # whose fixed point is exact; a delta whose solve falls short of the
    # base's residual rebuilds too (sequence engine).
    incremental_chain: bool = False
    delta_rank: int = 4
    delta_budget: float = 0.1

    def k_rp(self, n: int) -> int:
        if self.k_override is not None:
            return int(self.k_override)
        return max(1, math.ceil(math.log(n / self.eps_rp)))

    def solver_spec(self) -> SolverSpec:
        """The :class:`~repro.core.solvers.SolverSpec` these knobs select."""
        return SolverSpec(
            method=self.solver,
            tolerance=self.solver_tol,
            max_iters=self.solver_max_iters,
            delta=self.delta,
        )


def _edge_projection_body(tile, blk, seed, ks):
    s = jnp.sqrt(jnp.maximum(blk.astype(jnp.float32), 0.0))
    q = crng.edge_rademacher(
        seed,
        tile.rows[:, None, None],
        tile.cols[None, :, None],
        ks[None, None, :],
    )
    # sum (not einsum): reduces each column over axis 1 in the same order
    # as the sequential per-column pass, keeping the output bit-identical.
    return jnp.sum(s[:, :, None] * q, axis=1)


def edge_projection(
    ctx: DistContext,
    a: jax.Array,
    seed: int,
    k: int,
    *,
    prefetch_depth: int | None = None,
) -> jax.Array:
    """Y = B^T W^{1/2} Q for k Rademacher columns, (n, k) row-sharded.

    Y[i, c] = sum_j sqrt(A[i, j]) * Q_c[i, j] with Q_c antisymmetric +/-1.
    Entries scaled 1/sqrt(k) (Johnson-Lindenstrauss normalization).

    All k Rademacher columns are generated in one vectorized (pr, pc, k) pass
    per tile -- same counter hash, same per-column reduction order (hence
    bitwise identical to the former sequential ``fori_loop``), but the VPU
    sees one fused multiply-reduce instead of k dependent passes (this is the
    layout the Pallas kernel in :mod:`repro.kernels.edge_projection` uses).
    ``a`` may be a store-backed snapshot handle; the projection then streams
    row panels (one pass over A either way).  The seed and the column counter
    enter as uint32 operands (same hash bits as the former literals), keeping
    the body a cache-stable module-level program.
    """
    seed_arr = jnp.asarray(np.uint32(int(seed) & 0xFFFFFFFF))
    ks = jnp.arange(k, dtype=jnp.uint32)
    kwargs = dict(
        in_specs=(ctx.matrix_spec, P(), P(None)),
        reduce="cols",
        out_spec=P(ctx.row_axes, None),
    )
    if is_streamable(a):
        y = tile_stream(
            ctx, _edge_projection_body, a, seed_arr, ks,
            prefetch_depth=prefetch_depth, **kwargs,
        )
    else:
        y = tile_map(ctx, _edge_projection_body, a, seed_arr, ks, **kwargs)
    return y * (1.0 / jnp.sqrt(jnp.float32(k)))


@dataclass
class Embedding:
    z: jax.Array  # (n, k) row-sharded
    vol: jax.Array  # scalar V_G
    op: ChainOperator | None = None  # kept for reuse across random batches
    report: SolveReport | None = None  # solver telemetry for this embedding's solve


def commute_time_embedding(
    ctx: DistContext,
    a: jax.Array,
    cfg: CommuteConfig,
    *,
    op: ChainOperator | None = None,
    use_kernel: bool = False,
    warm_from: jax.Array | None = None,
) -> Embedding:
    """Z (n, k_RP) commute-time embedding of ``a`` (Algorithm 3).

    ``a`` may be a resident sharded adjacency or a store-backed snapshot
    handle -- with a handle, the chain build and the edge projection stream
    row panels from the store and A is never fully device-resident.

    ``warm_from`` is a previous embedding's ``z`` (same n, same seed => same
    k): the solver starts from it instead of the cold ``y0 = chi`` start.
    Ignored (with a cold solve) when its shape does not match -- a sequence
    whose k_RP changed mid-stream should not crash the detector.

    Without ``op`` the operator is built here and serves this one solve, so
    its form follows :func:`repro.core.chain.use_factored`: the factored
    chain (d - 1 GEMMs; the solve applies the T levels and A as factors)
    where the solve's ``max_steps + 1`` applications (plus the power
    iterations Chebyshev needs) number fewer than ``n / PASS_COST_DIVISOR``
    (n / 128), the build is resident (``not cfg.oocore``, A on the device)
    and d + 4 n x n shards fit; else the materialized chain (2d - 1 GEMMs),
    as for a tolerance-driven solve's 300-step cap or an out-of-core graph.
    Counted in ``chain.factored_builds`` (0 for a materialized build) and
    named by the ``phase.chain`` span's ``form``.
    """
    n = a.shape[0]
    k = cfg.k_rp(n)
    if op is None:
        spec = cfg.solver_spec()
        passes = spec.max_steps(cfg.q) + 1
        if spec.method == "chebyshev":
            passes += DEFAULT_POWER_ITERS
        factored = not cfg.oocore and use_factored(ctx, a, cfg.d, passes)
        form = "factored" if factored else "materialized"
        with phase("chain", n=n, d=cfg.d, oocore=cfg.oocore, form=form) as sp:
            if factored:
                op = factored_chain(
                    ctx, a, cfg.d, schedule=cfg.schedule, dtype=cfg.dtype,
                    deflate=cfg.deflate, use_kernel=use_kernel,
                )
                sp.fence(op.t_levels[-1])
            else:
                op = chain_product(
                    ctx,
                    a,
                    cfg.d,
                    schedule=cfg.schedule,
                    dtype=cfg.dtype,
                    deflate=cfg.deflate,
                    fuse_l=cfg.fuse_l,
                    use_kernel=use_kernel,
                    oocore=cfg.oocore,
                    oocore_work=cfg.oocore_dir,
                    oocore_panel_rows=cfg.oocore_panel_rows,
                    tile_codec=cfg.tile_codec,
                    prefetch_depth=cfg.prefetch_depth,
                    use_gemm_kernel=cfg.use_gemm_kernel,
                )
                sp.fence(op.p2 if not is_streamable(op.p2) else op.vol)
        REGISTRY.add_named({"chain.factored_builds": 1.0 if factored else 0.0})
    with phase("ingest", n=n, k=k) as sp:
        y = edge_projection(
            ctx, a, cfg.seed, k, prefetch_depth=cfg.prefetch_depth
        )
        sp.fence(y)
    y0 = None
    if warm_from is not None:
        if tuple(warm_from.shape) == (int(n), int(k)):
            y0 = warm_from
        else:
            # A silent cold start here used to be invisible: the sequence kept
            # converging, just slowly.  Count it and warn so a mid-stream k_RP
            # (or n) change shows up in run reports and test output.
            REGISTRY.inc("solve.warm_skipped")
            warnings.warn(
                f"warm_from shape {tuple(warm_from.shape)} does not match the "
                f"expected ({int(n)}, {int(k)}); solving cold (counted in "
                "solve.warm_skipped)",
                RuntimeWarning,
                stacklevel=2,
            )
    with phase("solve", n=n, k=k, method=cfg.solver, warm=y0 is not None) as sp:
        z, report = solve(
            ctx,
            op,
            y,
            cfg.solver_spec(),
            fixed_q=cfg.q,
            deflate=cfg.deflate,
            solver_batch=cfg.solver_batch,
            prefetch_depth=cfg.prefetch_depth,
            y0=y0,
        )
        sp.fence(z)
    return Embedding(z=z, vol=op.vol, op=op, report=report)


def validate_node_indices(name: str, idx, n: int) -> None:
    """Raise ``IndexError`` naming the first bad index when any of ``idx``
    falls outside ``[0, n)``.

    jax's gather silently *clamps* out-of-range indices, so ``z[rows]`` with
    a bad row returns the edge row's distances -- a plausible-looking, wrong
    answer.  Validation only applies to concrete indices; traced indices
    (inside jit) cannot be checked at trace time and pass through.
    """
    try:
        arr = np.asarray(idx)
    except Exception:
        return  # traced: concrete values unavailable at trace time
    if arr.size == 0:
        return
    bad = (arr < 0) | (arr >= n)
    if bad.any():
        first = int(arr[bad][0] if arr.ndim else arr)
        raise IndexError(
            f"{name} index {first} is out of range for n={n} "
            "(valid node ids are 0..n-1; jax would silently clamp it)"
        )


def commute_distance_block(
    emb: Embedding, rows: jax.Array, cols: jax.Array
) -> jax.Array:
    """c(i, j) = V_G ||Z_i - Z_j||^2 for an index block (gathered Z rows)."""
    n = int(emb.z.shape[0])
    validate_node_indices("rows", rows, n)
    validate_node_indices("cols", cols, n)
    zi = emb.z[rows].astype(jnp.float32)
    zj = emb.z[cols].astype(jnp.float32)
    sq_i = jnp.sum(zi * zi, axis=-1)
    sq_j = jnp.sum(zj * zj, axis=-1)
    cross = jnp.dot(zi, zj.T, precision=F32_PRECISION)  # cancels below
    return emb.vol * (sq_i[:, None] + sq_j[None, :] - 2.0 * cross)


def exact_commute_distances(a) -> jax.Array:
    """O(n^3) eigendecomposition oracle (tests / paper Fig. 2 baseline)."""
    import numpy as np

    a = np.asarray(a, np.float64)
    n = a.shape[0]
    deg = a.sum(1)
    l_mat = np.diag(deg) - a
    pinv = np.linalg.pinv(l_mat, rcond=1e-12)
    di = np.diag(pinv)
    vol = deg.sum()
    return jnp.asarray(vol * (di[:, None] + di[None, :] - 2.0 * pinv))

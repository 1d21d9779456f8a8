"""Peng-Spielman inverse-chain product (paper Algorithm 2, ChainProduct).

P = (I + S)(I + S^2)(I + S^4) ... (I + S^{2^{d-1}})  ~=  (I - S)^{-1}
(the product telescopes: (I - S) P = I - S^{2^d}), giving the approximate
Laplacian pseudo-inverse  Z^ = D^{-1/2} P D^{-1/2}.

Erratum vs the paper: Alg. 2 line 8 writes P1 = D^{-1/2} P; the right
inverse needs the symmetric sandwich D^{-1/2} P D^{-1/2} (their EstimateSolution
only converges with the latter).  We implement the correct sandwich.

Two forms of the same operator:

* **materialized** (:func:`chain_product`): 2(d-1) + 1 dense n x n GEMMs
  (T <- T@T and P <- P@T + P per level, one more for P2 = Z^ @ L) -- the
  paper's hot spot, distributed with the schedule chosen in
  :mod:`repro.core.distmatrix`.  ``fuse_l=True`` instead forms
  P2 = Z^ D - Z^ A via a column scale plus one GEMM on the *original*
  adjacency, saving the materialization of L (a beyond-paper memory
  optimization).  Every solve step is then one skinny pass over P2.
* **factored** (:func:`factored_chain`): only the d - 1 squarings.  The
  operator keeps T_0 .. T_{d-1} and the snapshot's adjacency, and a solve
  applies ``P1 x = D^{-1/2} (I + T_{d-1}) ... (I + T_0) D^{-1/2} x``
  (d skinny passes; the T_j commute, all being powers of S~) and
  ``P2 y = P1 (deg y - A y)`` (one pass more).  The same operator, reordered
  from ``(P L) y`` to ``P (L y)``: d GEMMs fewer, d skinny passes more a
  solve step.

:func:`use_factored` picks the form from what it can observe: the factored
one where the operator serves one solve whose passes cost less than the
GEMMs they replace (``max_steps + 1 < n / PASS_COST_DIVISOR``), the build and
its adjacency are resident, and d + 4 n x n shards fit the device; the
materialized one otherwise (a tolerance-driven solve's 300-step cap, an
out-of-core or streamed graph, an operator built to serve many solves).
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.sharding import PartitionSpec as P

from repro.core import laplacian as lap
from repro.core.distmatrix import (
    F32_PRECISION,
    DistContext,
    add_scaled_identity,
    matmul,
    matmul_rowblock,
)
from repro.core.tiles import is_streamable, sharded_zeros, stream_stats, tile_map
from repro.obs.metrics import REGISTRY as _OBS_REGISTRY

# Build counting: chain_product is the O(n^3) hot spot, so the sequence engine
# (and its tests) track exactly how many times it runs.  The storage is the
# obs metrics registry (``chain.builds``, alongside ``chain.gemm_flops`` /
# ``chain.gemm_bytes`` and the incremental-update counters from
# :mod:`repro.core.delta_chain`) so rebuild-vs-incremental counts flow through
# RunReport and bench registry deltas like every other metric; these two
# functions are the legacy facade over it.
_BUILD_BASE = 0.0  # registry value at the last reset_chain_build_count()


def chain_build_count() -> int:
    """Number of chain operators built since process start (or last reset)."""
    return int(_OBS_REGISTRY.value("chain.builds") - _BUILD_BASE)


def reset_chain_build_count() -> None:
    global _BUILD_BASE
    _BUILD_BASE = _OBS_REGISTRY.value("chain.builds")


@jax.tree_util.register_pytree_node_class
@dataclass
class ChainOperator:
    """Precomputed pieces so every Richardson iteration is mat-vec only.

    ``p1`` / ``p2`` are resident sharded arrays, or store-backed snapshot
    handles when the operator was built out-of-core
    (:func:`repro.core.oochain.chain_product_oocore`) -- the solver streams
    handle-backed operators per panel.  ``prefetch_depth`` and ``rho`` ride
    along as static metadata: the staging depth every downstream consumer of
    a store-backed operator inherits, and the power-iteration estimate of
    ``rho(S~^{2^d})`` (the Richardson contraction / Chebyshev interval bound,
    see :mod:`repro.core.solvers.power`) computed once at chain build so the
    solve driver never re-measures it.
    """

    p1: jax.Array  # (n, n)  Z^ = D^{-1/2} P D^{-1/2}  (array or store handle)
    p2: jax.Array  # (n, n)  Z^ @ L                    (array or store handle)
    deg: jax.Array  # (n,)
    vol: jax.Array  # scalar V_G
    # Optional incremental correction (repro.core.delta_chain): the operator
    # then represents P1' = diag(p1_scale) P1 diag(p1_scale) + u1 v1^T around
    # the *base* p1 buffer, and applies P2' = P1' (diag(deg) - adj) from the
    # snapshot's own adjacency ``adj`` (its p2 is None), so the corrected
    # iteration's fixed point is the exact L' z = Y'.  None means an ordinary
    # (uncorrected) operator.
    p1_scale: jax.Array | None = None  # (n,)
    u1: jax.Array | None = None  # (n, r)
    v1: jax.Array | None = None  # (n, r)
    adj: jax.Array | None = None  # (n, n) the snapshot's adjacency (array or store handle)
    prefetch_depth: int = 2  # panel-pipeline staging depth for streamed consumers
    rho: float | None = None  # rho(S~^{2^d}) power-iteration estimate (build-time)
    # Streamed consumers route mat-vecs through the fused Pallas stream-GEMM
    # kernel path (stored-width panel shipping + in-kernel decode + fused
    # solve epilogue); set by the out-of-core build, inherited by solve().
    use_gemm_kernel: bool = False
    # True when p1/p2 belong to a live delta_chain.BaseChain shared with other
    # operators: release_scratch() is then a no-op -- BaseChain.release() is
    # the single owner of that scratch (prevents a corrected operator's
    # retirement from freeing panels the base or its siblings still stream).
    shared_base: bool = False
    # The factored form (factored_chain): T_0 .. T_{d-1}, resident, with p1 /
    # p2 None; the solve applies P1 and P2 = P1 (diag(deg) - adj) from them.
    t_levels: tuple | None = None

    @property
    def factored(self) -> bool:
        return self.t_levels is not None

    def tree_flatten(self):
        return (
            self.p1, self.p2, self.deg, self.vol,
            self.p1_scale, self.u1, self.v1, self.adj, self.t_levels,
        ), (self.prefetch_depth, self.rho, self.use_gemm_kernel, self.shared_base)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(
            *children[:-1], t_levels=children[-1],
            prefetch_depth=aux[0], rho=aux[1], use_gemm_kernel=aux[2],
            shared_base=aux[3],
        )

    def release_scratch(self) -> None:
        """Retire store-backed P1 / P2 from their scratch store, and free a
        factored operator's T levels (no-op for a resident materialized
        operator, which its last reference frees).  Call once the operator
        will not be used again; every consumer that builds operators
        internally (``detect_anomalies``, ``SequenceDetector``) does this
        itself.

        A failed removal (a wedged scratch dir, a concurrently-removed
        snapshot) is *warned*, never raised: scoring already succeeded and
        the scratch is disposable -- but a silently growing scratch dir must
        be diagnosable, so only the expected store errors are swallowed.

        Operators sharing a delta-chain base (``shared_base=True``) skip the
        removal entirely: their p1/p2 are the base's buffers, owned and
        eventually retired by ``BaseChain.release()``.
        """
        if self.shared_base:
            return
        levels, self.t_levels = self.t_levels or (), None
        for t in levels:
            t.delete()  # built for this operator alone
        for buf in (self.p1, self.p2):
            store = getattr(buf, "store", None)
            if store is not None and hasattr(buf, "snap_id"):
                try:
                    store.remove_snapshot(buf.snap_id)
                except (OSError, ValueError, KeyError) as e:
                    warnings.warn(
                        f"release_scratch: could not remove snapshot "
                        f"{buf.snap_id!r} from its scratch store ({e!r}); "
                        f"the scratch dir may be accumulating orphans",
                        RuntimeWarning,
                        stacklevel=2,
                    )


def _col_scale_body(tile, blk, v):
    return blk.astype(jnp.float32) * v[tile.cols][None, :]


def _matmul_panels_from_store(
    ctx: DistContext, m: jax.Array, h, out_dtype, prefetch_depth: int | None = None
) -> jax.Array:
    """M @ A with A streamed from the store: per-panel GEMM accumulation.

    M @ A = sum_K M[:, K] @ A[K, :] over row panels K of the stored adjacency
    -- each term is one resident (n, ph) x (ph, n) GEMM against a panel
    prefetched from host/disk by the panel pipeline, so A is never fully
    device-resident and the fetch/decode overlaps the GEMMs.  (Used by the
    ``fuse_l`` build; the panel-accumulation order makes this path
    close-but-not-bitwise vs the resident ``fuse_l`` GEMM.)
    """
    from repro.store import PanelPipeline  # deferred: core->store only on this path

    n = h.shape[0]
    ph = int(np.lcm(int(h.panel_rows), ctx.n_row_shards))
    sharding = ctx.sharding(ctx.matrix_spec)
    st = stream_stats()
    acc = sharded_zeros((n, n), jnp.float32, sharding)
    with PanelPipeline(
        [h], range(0, n, ph), ph, depth=prefetch_depth, sharding=sharding, stats=st
    ) as pipe:
        for r0, (panel,) in pipe:
            m_cols = lax.dynamic_slice(m, (0, r0), (n, ph))
            acc = acc + jnp.dot(
                m_cols.astype(jnp.float32), panel.astype(jnp.float32),
                precision=F32_PRECISION, preferred_element_type=jnp.float32,
            )
    return ctx.constrain(acc.astype(out_dtype), ctx.matrix_spec)


def _inv_sqrt(deg: jax.Array) -> jax.Array:
    """D^{-1/2}, zero where a node has no edges."""
    return jnp.where(deg > 0, jax.lax.rsqrt(jnp.maximum(deg, 1e-30)), 0.0)


def _chain_start(ctx: DistContext, a, deflate: bool, dtype, prefetch_depth):
    """``(deg, vol, T_0)``: the degrees, the volume and S~, both forms' start."""
    deg = lap.degrees(ctx, a, prefetch_depth=prefetch_depth)
    vol = lap.volume(ctx, deg)
    t0 = lap.normalized_adjacency(
        ctx, a, deg, deflate=deflate, dtype=dtype, prefetch_depth=prefetch_depth
    )
    return deg, vol, t0


def _resident_chain(
    ctx: DistContext,
    a,
    d_len: int,
    *,
    schedule: str,
    dtype,
    deflate: bool,
    fuse_l: bool,
    use_kernel: bool,
    prefetch_depth: int | None,
    level_sink: dict | None,
):
    """``(p1, p2, deg, vol)`` of the device-resident build: every GEMM of
    the chain, and nothing that syncs with the host (so it also traces as
    one program under ``jax.jit``)."""
    mm = partial(matmul, ctx, schedule=schedule, out_dtype=dtype, use_kernel=use_kernel)
    deg, vol, t = _chain_start(ctx, a, deflate, dtype, prefetch_depth)
    p = add_scaled_identity(ctx, t, 1.0)  # I + S
    # Only a level_sink keeps the T levels alive (the delta path applies
    # every P level as a product of (I + T) factors): a plain build holds
    # O(1) n x n matrices, a retaining one d more.
    keep = level_sink is not None
    t_levels = [t] if keep else []
    for _ in range(1, d_len):
        t = mm(t, t)  # S^{2^k}
        if keep:
            t_levels.append(t)
        p = jnp.add(mm(p, t), p)  # P (I + T) = P T + P, no identity materialized
    if keep:
        level_sink["t"] = t_levels

    inv_sqrt = _inv_sqrt(deg)
    p1 = tile_map(
        ctx,
        lap._sym_scale_body,
        p,
        inv_sqrt,
        in_specs=(ctx.matrix_spec, P(None)),
        out_dtype=dtype,
    )
    del t, p  # an eager build frees both n x n levels before the P2 GEMM
    if fuse_l:
        # P2 = Z^ (D - A) = (Z^ col-scaled by d) - Z^ @ A
        p1d = tile_map(
            ctx, _col_scale_body, p1, deg, in_specs=(ctx.matrix_spec, P(None)), out_dtype=dtype
        )
        if is_streamable(a):
            p2 = jnp.subtract(
                p1d, _matmul_panels_from_store(ctx, p1, a, dtype, prefetch_depth)
            )
        else:
            p2 = jnp.subtract(p1d, mm(p1, a.astype(dtype)))
    else:
        l_mat = lap.laplacian(ctx, a, deg, dtype=dtype, prefetch_depth=prefetch_depth)
        p2 = mm(p1, l_mat)
    return p1, p2, deg, vol


def chain_product(
    ctx: DistContext,
    a: jax.Array,
    d_len: int,
    *,
    schedule: str = "cannon",
    dtype=jnp.float32,
    deflate: bool = True,
    fuse_l: bool = False,
    use_kernel: bool = False,
    oocore: bool = False,
    oocore_work=None,
    oocore_panel_rows: int | None = None,
    tile_codec: str = "raw",
    prefetch_depth: int | None = None,
    use_gemm_kernel: bool = False,
    level_sink: dict | None = None,
) -> ChainOperator:
    """Build the materialized chain operator from ``a``: a resident sharded
    adjacency or a store-backed snapshot handle.  (:func:`factored_chain`
    builds the factored form, for a resident ``a`` and one solve.)

    ``level_sink`` (a caller-provided dict) opts into retaining the chain's
    squaring levels for incremental delta updates
    (:mod:`repro.core.delta_chain`): on return ``level_sink["t"]`` holds
    T_0 .. T_{d-1} (arrays resident, store handles out-of-core -- the oocore
    build then skips the usual removal of those intermediate snapshots; the
    caller owns their lifetime via ``BaseChain.release()``).  No P level is
    kept: P_{l} is the product of the (I + T_j), j <= l.

    With a handle, every consumer of A streams: the degree pass, the
    normalized-adjacency build (S, the first chain GEMM's operand, assembled
    per-tile from store panels) and the Laplacian build each make one pass
    over the stored tiles, so the raw n x n adjacency is never device-resident
    -- only the (already required) chain matrices are.  With the default
    ``fuse_l=False`` the streamed build is bitwise identical to the resident
    one (all A-consuming passes are elementwise or row-parallel); the opt-in
    ``fuse_l=True`` path instead accumulates Z^ @ A per panel, whose reduction
    order differs from the resident single GEMM -- allclose, not bitwise.

    ``oocore=True`` removes the remaining n^2 device term: the squaring chain
    itself runs against store-backed working matrices
    (:func:`repro.core.oochain.chain_product_oocore`), spilling S / T / P
    through ``oocore_work`` (a TileStore, a directory, or None for host-RAM
    scratch) so peak device residency is O(n * panel); the returned operator
    holds store-backed P1 / P2 that the solver streams.  Allclose, not
    bitwise, vs the resident build.  ``schedule`` / ``use_kernel`` / ``dtype``
    govern the resident GEMMs only and are ignored out-of-core (the scratch
    and operator are always fp32).

    ``tile_codec`` / ``prefetch_depth`` are the panel-I/O knobs and matter
    only where panels actually stream: the scratch store encoding and the
    panel-pipeline staging depth of the out-of-core build (and of the
    streamed ``fuse_l`` GEMM with a handle-backed ``a``).

    ``use_gemm_kernel`` (out-of-core only; ignored resident, where
    ``use_kernel`` already selects the Pallas tile bodies) runs the chain's
    GEMM steps through the fused streaming kernel with stored-width panel
    shipping, and marks the returned operator so streamed solves inherit the
    kernel path -- see :func:`repro.core.oochain.chain_product_oocore`.
    """
    if d_len < 1:
        raise ValueError("chain length d must be >= 1")
    # Logical GEMM cost of a full build -- 2(d-1)+1 dense n x n GEMMs at
    # 2 n^3 FLOPs / 3 n^2 fp32 operands each (the same convention the delta
    # path's skinny-pass ledger uses, so the registry ratio is meaningful).
    n_nodes = int(a.shape[0])
    n_gemms = 2 * (d_len - 1) + 1
    _OBS_REGISTRY.add_named({
        "chain.builds": 1.0,
        "chain.gemm_flops": n_gemms * 2.0 * float(n_nodes) ** 3,
        "chain.gemm_bytes": n_gemms * 3.0 * float(n_nodes) ** 2 * 4.0,
        # Scratch materialized: one fresh n^2 matrix per GEMM plus the S~
        # assembly (the matrices an out-of-core build spills to the store).
        "chain.scratch_bytes": (n_gemms + 1) * float(n_nodes) ** 2 * 4.0,
    })
    if oocore:
        from repro.core.oochain import chain_product_oocore

        return chain_product_oocore(
            ctx,
            a,
            d_len,
            dtype=dtype,
            deflate=deflate,
            fuse_l=fuse_l,
            work=oocore_work,
            panel_rows=oocore_panel_rows,
            tile_codec=tile_codec,
            prefetch_depth=prefetch_depth,
            use_gemm_kernel=use_gemm_kernel,
            level_sink=level_sink,
        )
    p1, p2, deg, vol = _resident_chain(
        ctx, a, d_len, schedule=schedule, dtype=dtype, deflate=deflate,
        fuse_l=fuse_l, use_kernel=use_kernel, prefetch_depth=prefetch_depth,
        level_sink=level_sink,
    )
    # Measure the Richardson contraction rho(S~^{2^d}) once, while P2 is hot:
    # a handful of eager skinny mat-vecs against the 2(d-1)+1 n^3 GEMMs above.
    # The solve driver reads it for Chebyshev intervals and telemetry.
    from repro.core.solvers.power import estimate_rho

    rho = estimate_rho(ctx, p2, prefetch_depth=prefetch_depth)
    return ChainOperator(p1=p1, p2=p2, deg=deg, vol=vol, rho=rho)


# ---------------------------------------------------------------------------
# the factored form: the squarings only, applied as factors by the solve
# ---------------------------------------------------------------------------

# One n x n float32 GEMM at HIGHEST costs about n / PASS_COST_DIVISOR skinny
# passes (one read of an n x n matrix against the solve's (n, k_RP) block).
# On one v5e at n=16384 a chain GEMM took 0.305 s and a solve pass 1.9 ms, a
# ratio of 160, n / 102 (ledger, the climate write cell: chain_s 3.359 s over
# 11 GEMMs, solve_s 0.0188 s over 10 passes); 128 leaves that margin.
PASS_COST_DIVISOR = 128

# n x n shards a factored build holds at its peak: T_0 .. T_{d-1} plus this
# many more (the snapshot's adjacency, the previous snapshot's that the
# sequence keeps for scoring, and a squaring's operand copies).
FACTORED_EXTRA_MATRICES = 4


def _device_bytes_limit(ctx: DistContext) -> int | None:
    """One device's memory in bytes, or None where the backend reports none."""
    stats = ctx.mesh.devices.flat[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return None if limit is None else int(limit)


def use_factored(ctx: DistContext, a, d_len: int, solve_passes: int) -> bool:
    """Whether a build that serves one solve of ``solve_passes`` operator
    applications (``max_steps + 1``, the power iterations added for
    Chebyshev) should take the factored form.

    It replaces d GEMMs by d skinny passes a application, so it pays where
    ``solve_passes < n / PASS_COST_DIVISOR``; it reads A on every step and
    holds every T level, so A must be resident and d + 4 n x n float32
    shards must fit one device (a backend that reports no memory limit, the
    CPU's, is taken to fit).
    """
    if is_streamable(a):
        return False
    n = int(a.shape[0])
    if solve_passes >= n / PASS_COST_DIVISOR:
        return False
    limit = _device_bytes_limit(ctx)
    shard = 4.0 * n * n / (ctx.n_row_shards * ctx.n_col_shards)
    return limit is None or (d_len + FACTORED_EXTRA_MATRICES) * shard <= limit


def factored_chain(
    ctx: DistContext,
    a: jax.Array,
    d_len: int,
    *,
    schedule: str = "cannon",
    dtype=jnp.float32,
    deflate: bool = True,
    use_kernel: bool = False,
) -> ChainOperator:
    """The factored chain operator of a resident ``a``: d - 1 squarings, no
    P, P1 or P2 (see the module docstring).  No rho is estimated: the solve
    measures one through the operator's own mat-vec where Chebyshev asks."""
    if d_len < 1:
        raise ValueError("chain length d must be >= 1")
    if is_streamable(a):
        raise ValueError("the factored chain reads A every solve step: A must be resident")
    n = float(a.shape[0])
    _OBS_REGISTRY.add_named({
        "chain.builds": 1.0,
        "chain.gemm_flops": (d_len - 1) * 2.0 * n**3,
        "chain.gemm_bytes": (d_len - 1) * 3.0 * n**2 * 4.0,
        "chain.scratch_bytes": d_len * n**2 * 4.0,  # S~ and the d - 1 squares
    })
    mm = partial(matmul, ctx, schedule=schedule, out_dtype=dtype, use_kernel=use_kernel)
    deg, vol, t = _chain_start(ctx, a, deflate, dtype, None)
    levels = [t]
    for _ in range(1, d_len):
        levels.append(mm(levels[-1], levels[-1]))  # S~^{2^k}
    return ChainOperator(p1=None, p2=None, deg=deg, vol=vol, adj=a, t_levels=tuple(levels))


def apply_levels(mm, t_levels, x=None, heads=None):
    """``(I + T_0) ... (I + T_{m-1}) x``: one skinny pass ``mm(T_j, block)``
    per level, the last level first.  The T_j commute, so that is the
    product in any order.

    ``heads[j]``, where given, joins the block as its first columns just
    before level j's pass, so each head comes out multiplied by the factors
    of levels 0 .. j alone -- all of them from one pass a level
    (the delta chain's ``P_{l-1} Ut_l``).
    """
    for j in reversed(range(len(t_levels))):
        if heads is not None:
            x = heads[j] if x is None else jnp.concatenate([heads[j], x], axis=1)
        x = x + mm(t_levels[j], x)
    return x


def factored_p1(ctx: DistContext, t_levels, deg: jax.Array, x: jax.Array) -> jax.Array:
    """``P1 x = D^{-1/2} (I + T_{d-1}) ... (I + T_0) D^{-1/2} x`` for an
    (n, k) block, in float32: d skinny passes."""
    s = _inv_sqrt(deg.astype(jnp.float32)).reshape(-1, 1)
    px = apply_levels(
        lambda t, y: matmul_rowblock(ctx, t, y), t_levels, s * x.astype(jnp.float32)
    )
    return s * px

"""Incremental delta-chain updates: skip the O(n^3) rebuild on small drift.

A slowly-drifting transition changes the chain operator by a *small-norm*
perturbation: Online Anomaly Detection Systems Using Incremental Commute Time
(arXiv:1107.3894) shows commute-time quantities admit incremental updates
under such perturbations, and the Rademacher-sketch machinery already used by
``edge_projection`` (Khoa & Chawla, arXiv:1111.4541) gives the low-rank
compression primitive.  This module implements that path for the squaring
chain:

1. **Sketch** ``dS = S~' - S~`` against a counter-generated Rademacher test
   matrix W (never materializing dS): a randomized range-finder compresses
   it to a rank-r factorization ``U0 V0^T`` from W's first r + 2 columns.
   The same two passes, over all ``DRIFT_SKETCH_COLS`` columns of W, yield
   the *drift monitor* ``||dS W||_F / ||S~ W||_F``.
2. **Propagate** the correction through the squaring recurrence.  With
   ``T_l = T_{l-1}^2`` and ``P_l = P_{l-1}(I + T_l)`` (all T_l symmetric,
   powers of S commute):

       dT_l = [T U, U] [V, T V + V (U^T V)]^T               (rank 2r)
       dP_l = [E, P Ut + E (F^T Ut)] [F + T_l F, Vt]^T      (rank 2r)

   where (U, V) = dT_{l-1}, (E, F) = dP_{l-1}, (Ut, Vt) = dT_l, T = T_{l-1}
   and P = P_{l-1}.  Each level recompresses 2r -> r via an exact QR +
   small-SVD factor truncation.  The base keeps only T_0 .. T_{d-1}: since
   ``P_{l-1} = (I + T_0)(I + T_1) ... (I + T_{l-1})``, every ``P_{l-1} Ut``
   comes from one pass per T level over a block that gathers the Ut's (the
   dT chain runs first), as many passes as against stored P levels and d-2
   fewer n x n matrices held.  Every product against the base is a skinny
   n x w panel GEMM through :func:`repro.core.distmatrix.matmul_rowblock`
   (streams store-backed levels through the panel pipeline; resident levels
   use one eager dot), so a level costs O(n^2 r) instead of the rebuild's
   O(n^3).
3. **Correct the operator.**  ``P1' = diag(s) P1 diag(s) + E~ F~^T``
   (s = sqrt(deg) * 1/sqrt(deg'), E~ = D'^{-1/2} E) is the preconditioner,
   exact up to the rank-r truncation of dP.  The corrected
   :class:`~repro.core.chain.ChainOperator` carries ``(p1_scale, u1, v1)``
   and the snapshot's adjacency, and every solver method applies
   ``P2' = P1' (D' - A')`` from them, two skinny products a step: the
   iteration ``z <- z - P2' z + P1' Y'`` has the exact ``L' z = Y'`` as its
   fixed point, whatever the truncation left in P1'.

All factor algebra stays on the device in float32 (``matmul_rowblock``
for the n^2 passes, a jitted QR + small-SVD truncation per shape): the only
host read is the drift the gate compares, so no pass waits on a copy back
to the host.  The factors shape only the preconditioner, never the fixed
point.  The delta path adds ZERO tile-program traces; the only new compiled
tile program is the corrected resident solve loop, keyed once per
correction rank.  Over resident levels the propagation is one jitted
program (:func:`_propagate_program`, compiled once per shape).

Spans (while tracing is on): ``delta.update`` around the whole update, with
``delta.sketch``, ``delta.propagate`` and ``delta.correct`` inside it, and
the ``delta.update.seconds`` / ``.calls`` counters (:func:`repro.obs.timed`).
"""

from __future__ import annotations

import math
import warnings
from dataclasses import dataclass, field
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import laplacian as lap
from repro.core import rng as crng
from repro.core.chain import ChainOperator, apply_levels, chain_product
from repro.core.distmatrix import F32_PRECISION, DistContext, matmul_rowblock
from repro.core.tiles import is_streamable
from repro.obs import timed
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY as _OBS_REGISTRY

# Range-finder oversampling: the sketch width is delta_rank + DELTA_OVERSAMPLE
# columns; the extra columns absorb the tail so the leading r directions are
# captured accurately (Halko/Martinsson/Tropp's standard few-column margin).
DELTA_OVERSAMPLE = 2

# Columns of the drift monitor's sketch.  The ratio estimates
# ||dS||_F / ||S~||_F; from r + 2 columns it is off by tens of percent, so
# the rebuild a budget asks for landed anywhere from 36 to 66 transitions
# into a base on the climate-drift traffic (the exact ratio: 51 to 55, on a
# 64x64 grid).  128 columns hold it to a few percent and add no pass: the
# same two passes read their n x n operands once, whatever the width.
# Below n = 16 * 128 the width is n / 16 (at least r + 2), so the two passes
# stay skinny at any n.
DRIFT_SKETCH_COLS = 128

# A delta transition's solve must end within this factor of the residual the
# base's own (full-rebuild) solve reached, or the sequence engine rebuilds:
# the corrected iteration has the exact fixed point, but only a converged
# iterate reaches it.  On the drifting 128x128 climate graph (n=16384, one
# v5e) a delta's residual reads 0.65-1.09 of the base's up to a drift of
# 0.17; two leaves that spread room and no more.
SOLVE_RESIDUAL_SLACK = 2.0


# ---------------------------------------------------------------------------
# logical GEMM accounting (the counters the >= 3x acceptance bar reads)
# ---------------------------------------------------------------------------


class _GemmLedger:
    """Logical FLOP/byte counts for chain-phase GEMM passes.

    One convention everywhere (fp32, counted at dispatch, not measured -- the
    point is a stable apples-to-apples ratio between the rebuild and the
    delta path):

    * ``flops``: a full (n, n) x (n, n) GEMM is ``2 n^3``; a skinny
      (n, n) x (n, w) pass is ``2 n^2 w``.
    * ``bytes``: operand + result traffic -- ``3 n^2 * 4`` for the full GEMM,
      ``(n^2 + 2 n w) * 4`` for a skinny pass.  Note a skinny pass still
      *reads* its n^2 operand once, so this metric shrinks only ~linearly
      with pass count, not with width.
    * ``scratch``: bytes of chain scratch *materialized* -- the full build
      writes a fresh n^2 matrix per GEMM (the T/P levels, P1, P2, all of
      which the out-of-core build spills to the scratch store), ``n^2 * 4``
      each; a skinny pass writes only its (n, w) result block, ``n w * 4``.
      This is the residency/spill axis the incremental path collapses.
    """

    def __init__(self) -> None:
        self.flops = 0.0
        self.bytes = 0.0
        self.scratch = 0.0

    def skinny(self, n: int, w: int) -> None:
        self.flops += 2.0 * n * n * w
        self.bytes += (n * n + 2.0 * n * w) * 4.0
        self.scratch += n * w * 4.0

    def add(self, other: "_GemmLedger") -> None:
        self.flops += other.flops
        self.bytes += other.bytes
        self.scratch += other.scratch


def full_build_gemm_cost(n: int, d_len: int) -> tuple[float, float, float]:
    """(flops, bytes, scratch) of one full chain build.

    ``2 (d-1) + 1`` dense n x n GEMMs (d-1 squarings, d-1 P updates, one
    P1 @ L); scratch additionally counts the S~ assembly, so ``2 d`` fresh
    n^2 matrices are materialized overall.
    """
    gemms = 2 * (d_len - 1) + 1
    return (
        gemms * 2.0 * n**3,
        gemms * 3.0 * n * n * 4.0,
        (gemms + 1) * n * n * 4.0,
    )


# ---------------------------------------------------------------------------
# base-chain retention
# ---------------------------------------------------------------------------


@dataclass
class BaseChain:
    """A full chain build plus the retained levels deltas multiply against.

    ``t_levels`` holds T_0 .. T_{d-1} (T_0 = S~), arrays or store-backed
    handles, matching the build.  ``op`` is the base operator with
    ``shared_base=True`` stamped on it, so the sequence engine's
    per-snapshot ``release_scratch()`` cannot retire scratch that the base
    still owns; :meth:`release` is the one place the base scratch actually
    dies.
    """

    op: ChainOperator
    t_levels: list = field(default_factory=list)
    d_len: int = 1
    deflate: bool = True
    released: bool = False
    residual: float = math.nan  # final residual of the base's own solve

    def solved(self, residual: float) -> None:
        """Record the final residual of the base's own solve (the bar a
        delta's solve must meet) and free the base's P2: no delta reads it,
        since a corrected operator applies ``P1' (D' - A')`` from its own
        snapshot."""
        self.residual = float(residual)
        p2, self.op.p2 = self.op.p2, None
        _remove_handle(p2, "BaseChain.solved: could not remove the base's P2")

    def release(self) -> None:
        """Retire the base: operator scratch plus every retained level.

        Idempotent -- a second release is a no-op, never a double-free (the
        regression the shared-base lifecycle audit guards).  Drops the
        base's references too, so a resident base's n x n buffers are freed
        before whatever allocates next (the fallback's rebuild).
        """
        if self.released:
            return
        self.released = True
        self.op.shared_base = False
        self.op.release_scratch()
        for buf in self.t_levels:
            _remove_handle(buf, "BaseChain.release: could not remove retained level")
        self.t_levels = []
        self.op = None


def _remove_handle(buf, what: str) -> None:
    """Remove a store-backed level from its scratch store (a resident array
    is freed with its last reference); a failed removal is warned."""
    store = getattr(buf, "store", None)
    if store is None or not hasattr(buf, "snap_id"):
        return
    try:
        store.remove_snapshot(buf.snap_id)
    except (OSError, ValueError, KeyError) as e:
        warnings.warn(f"{what} {buf.snap_id!r} ({e!r})", RuntimeWarning, stacklevel=3)


def build_base_chain(
    ctx: DistContext, a, cfg, *, use_kernel: bool = False
) -> BaseChain:
    """Full chain build that also retains the levels delta updates multiply
    against.  Counts one ``chain.full_rebuilds`` (the drift monitor's
    fallback lands here too, so rebuild-vs-incremental is one registry pair).
    """
    sink: dict = {}
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
        level_sink=sink,
    )
    op.shared_base = True
    _OBS_REGISTRY.add_named({"chain.full_rebuilds": 1.0})
    return BaseChain(
        op=op, t_levels=list(sink.get("t", ())), d_len=cfg.d, deflate=cfg.deflate
    )


# ---------------------------------------------------------------------------
# small host-side factor algebra
# ---------------------------------------------------------------------------


def _mm(a, b):
    return jnp.matmul(a, b, precision=F32_PRECISION)


@partial(jax.jit, static_argnames="r")
def truncate_factors(u, v, r: int):
    """Best rank-r recompression of ``u @ v.T`` (exact, O(n r^2)), on the
    device in float32.

    QR both factors, SVD the small core: ``u v^T = qu (ru rv^T) qv^T``;
    keeping the top r singular triplets of the core is the optimal rank-r
    approximation of the product itself.
    """
    qu, ru = jnp.linalg.qr(u.astype(jnp.float32))
    qv, rv = jnp.linalg.qr(v.astype(jnp.float32))
    w, s, zt = jnp.linalg.svd(_mm(ru, rv.T))
    rr = min(int(r), s.shape[0])
    return _mm(qu, w[:, :rr] * s[:rr]), _mm(qv, zt[:rr].T)


def _rademacher_omega(n: int, m: int, seed: int) -> jax.Array:
    """(n, m) +/-1 test matrix from the counter-based hash (zero stored
    randomness, deterministic across hosts -- same contract as the edge
    projection's Rademacher field)."""
    rows = jnp.arange(n, dtype=jnp.uint32)[:, None]
    cols = jnp.arange(m, dtype=jnp.uint32)[None, :]
    h = crng.hash_u32(np.uint32(int(seed) & 0xFFFFFFFF), rows, cols)
    return 1.0 - 2.0 * (h >> 31).astype(jnp.float32)


# ---------------------------------------------------------------------------
# the incremental update
# ---------------------------------------------------------------------------


class _Passes:
    """Skinny-GEMM passes against big operands, with ledger accounting."""

    def __init__(self, ctx: DistContext, depth, ledger: _GemmLedger):
        self.ctx = ctx
        self.depth = depth
        self.ledger = ledger

    def mm(self, mat, x: jax.Array) -> jax.Array:
        """mat @ x for an (n, w) block; mat is resident or a handle."""
        n, w = int(mat.shape[0]), int(x.shape[1])
        self.ledger.skinny(n, w)
        x = self.ctx.constrain(x.astype(jnp.float32), self.ctx.rowblock_spec)
        return matmul_rowblock(self.ctx, mat, x, prefetch_depth=self.depth)


def try_delta_update(
    ctx: DistContext, base: BaseChain, a, cfg
) -> ChainOperator | None:
    """Corrected operator for snapshot ``a`` against ``base``, or ``None``.

    ``None`` means the sketched drift ``||dS W||_F / ||S~ W||_F`` exceeded
    ``cfg.delta_budget`` and the caller must rebuild.  Deltas are always
    measured against the *last full rebuild* (never chained delta-on-delta),
    so the same budget bounds both per-transition drift and accumulated
    drift, and incremental error cannot compound across transitions.
    """
    with timed("delta.update", n=int(a.shape[0]), rank=int(cfg.delta_rank)) as sp:
        op = _delta_update(ctx, base, a, cfg)
        if op is not None:
            sp.fence((op.p1_scale, op.u1, op.v1))
        sp.annotate(fallback=op is None)
    return op


def _delta_update(ctx: DistContext, base: BaseChain, a, cfg) -> ChainOperator | None:
    n = int(a.shape[0])
    r = int(cfg.delta_rank)
    m = r + DELTA_OVERSAMPLE
    depth = cfg.prefetch_depth
    ledger = _GemmLedger()
    ps = _Passes(ctx, depth, ledger)

    t_lv = base.t_levels
    if len(t_lv) != base.d_len:
        raise ValueError(
            f"base chain retained {len(t_lv)} T levels for d={base.d_len}; "
            f"was it built with build_base_chain()?"
        )

    with obs_trace.span("delta.sketch", n=n, width=m) as sp:
        # -- current snapshot's degree data (the corrected op needs it anyway)
        deg_new = lap.degrees(ctx, a, prefetch_depth=depth)
        vol_new = lap.volume(ctx, deg_new)
        inv_sqrt_n = jnp.where(
            deg_new > 0, jax.lax.rsqrt(jnp.maximum(deg_new, 1e-30)), 0.0
        )[:, None]
        u_new = jnp.sqrt(jnp.maximum(deg_new, 0.0) / jnp.maximum(vol_new, 1e-30))[:, None]
        sqrt_b = jnp.sqrt(jnp.maximum(base.op.deg, 0.0))[:, None]

        def s_new(x: jax.Array) -> jax.Array:
            """S~' x from the raw snapshot: D'^{-1/2} A' D'^{-1/2} x (- u' u'^T x)."""
            y = inv_sqrt_n * ps.mm(a, inv_sqrt_n * x)
            if base.deflate:
                y = y - u_new * _mm(u_new.T, x)
            return y

        omega = _rademacher_omega(n, max(m, min(DRIFT_SKETCH_COLS, n // 16)), cfg.seed + 0x5EED)
        s_base_w = ps.mm(t_lv[0], omega)  # S~ W (base, retained T_0)
        dy = s_new(omega) - s_base_w  # dS W, S~' W implicit from the snapshot
        drift = float(
            jnp.linalg.norm(dy) / jnp.maximum(jnp.linalg.norm(s_base_w), 1e-30)
        )
        _OBS_REGISTRY.append("chain.drift", drift)
        _OBS_REGISTRY.set_gauge("chain.drift_last", drift)
        sp.annotate(drift=drift)
        if drift > float(cfg.delta_budget):
            _OBS_REGISTRY.add_named({"chain.drift_fallbacks": 1.0})
            return None

        # Range-finder: dS ~= Q (dS Q)^T (dS symmetric).  Zero drift (an
        # identical snapshot) gives rank-0 factors, which the truncation
        # below handles fine.
        q, _ = jnp.linalg.qr(dy[:, :m])
        w0 = s_new(q) - ps.mm(t_lv[0], q)  # dS Q
        dt0 = truncate_factors(q, w0, r)  # dT_0 = dS ~= u v^T

    with obs_trace.span("delta.propagate", levels=base.d_len - 1):
        e_f, f_f = _propagate(ps, t_lv, dt0, r)

    with obs_trace.span("delta.correct") as sp:
        # P1' = diag(s) P1 diag(s) + E~ F~^T; the solve applies P2' = P1' L'
        rb = ctx.sharding(ctx.rowblock_spec)
        op = ChainOperator(
            p1=base.op.p1,
            p2=None,
            deg=deg_new,
            vol=vol_new,
            prefetch_depth=base.op.prefetch_depth,
            # Keep the base interval bound: corrected spectra move by
            # O(||dS||) and both Chebyshev (Manteuffel adaptation) and CG are
            # robust to a slightly stale rho; re-measuring would cost power
            # iterations per transition, defeating the delta path's point.
            rho=base.op.rho,
            use_gemm_kernel=base.op.use_gemm_kernel,
            p1_scale=jax.device_put(
                (sqrt_b * inv_sqrt_n)[:, 0],
                ctx.sharding(jax.sharding.PartitionSpec(None)),
            ),
            u1=jax.device_put(inv_sqrt_n * e_f, rb),
            v1=jax.device_put(inv_sqrt_n * f_f, rb),
            adj=a,
            shared_base=True,
        )
        sp.fence((op.p1_scale, op.u1, op.v1))

    _OBS_REGISTRY.add_named({
        "chain.incremental_updates": 1.0,
        "chain.gemm_flops": ledger.flops,
        "chain.gemm_bytes": ledger.bytes,
        "chain.scratch_bytes": ledger.scratch,
        "chain.delta_gemm_flops": ledger.flops,
        "chain.delta_gemm_bytes": ledger.bytes,
    })
    return op


def _propagate(ps: _Passes, t_lv: list, dt0, r: int):
    """dP_{d-1} as rank-r factors (E, F), from dT_0 and the T levels.

    Resident levels run as one compiled program (:func:`_propagate_program`),
    one dispatch in place of one per operation; store-backed levels stream
    pass by pass (:func:`_propagate_passes`).
    """
    if any(is_streamable(t) for t in t_lv):
        return _propagate_passes(ps, t_lv, dt0, r)
    fn, ledgers = _propagate_program(ps.ctx, r)
    e_f, f_f = fn(tuple(t_lv), *dt0)
    ps.ledger.add(ledgers[(tuple(t.shape for t in t_lv), dt0[0].shape, dt0[1].shape)])
    return e_f, f_f


@lru_cache(maxsize=None)
def _propagate_program(ctx: DistContext, r: int):
    """The jitted :func:`_propagate_passes` over resident levels, and the
    ledger of its passes by operand shapes (recorded when it is traced)."""
    ledgers: dict = {}

    def run(t_lv, u0, v0):
        ledger = _GemmLedger()
        out = _propagate_passes(_Passes(ctx, None, ledger), list(t_lv), (u0, v0), r)
        ledgers[(tuple(t.shape for t in t_lv), u0.shape, v0.shape)] = ledger
        return out

    return jax.jit(run), ledgers


def _propagate_passes(ps: _Passes, t_lv: list, dt0, r: int):
    """:func:`_propagate` pass by pass: eagerly, or traced once per shape
    by :func:`_propagate_program`.

    The dT chain runs first (one pass over T_{l-1} per level); then one pass
    per T level, from T_{d-2} down to T_0, applies ``(I + T_j)`` to a block
    that gathers each Ut_l as its first factor T_{l-1} comes up, which
    leaves ``P_{l-1} Ut_l`` for every level (the T_j commute); last, the dP
    recurrence (one pass over T_l per level for ``T_l F``).
    """
    d_len = len(t_lv)
    u_t, v_t = dt0
    dts = []  # (Ut_l, Vt_l) for l = 1 .. d-1
    for lvl in range(1, d_len):
        uv = ps.mm(t_lv[lvl - 1], jnp.concatenate([u_t, v_t], axis=1))
        tu, tv = uv[:, : u_t.shape[1]], uv[:, u_t.shape[1] :]
        u2r = jnp.concatenate([tu, u_t], axis=1)
        v2r = jnp.concatenate([v_t, tv + _mm(v_t, _mm(u_t.T, v_t))], axis=1)
        u_t, v_t = truncate_factors(u2r, v2r, r)
        dts.append((u_t, v_t))

    block = apply_levels(ps.mm, t_lv[: d_len - 1], heads=[ut for ut, _ in dts])
    widths = np.cumsum([0] + [ut.shape[1] for ut, _ in dts])

    e_f, f_f = dt0  # dP_0 = dS (P_0 = I + T_0)
    for lvl in range(1, d_len):
        ut, vt = dts[lvl - 1]
        pu = block[:, widths[lvl - 1] : widths[lvl]]  # P_{lvl-1} Ut
        tf = ps.mm(t_lv[lvl], f_f)  # T_lvl F
        e2r = jnp.concatenate([e_f, pu + _mm(e_f, _mm(f_f.T, ut))], axis=1)
        f2r = jnp.concatenate([f_f + tf, vt], axis=1)
        e_f, f_f = truncate_factors(e2r, f2r, r)
    return e_f, f_f

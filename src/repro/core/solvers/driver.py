"""Unified solve driver: one place that owns resident-vs-streamed branching.

Every consumer of the chain operator (the commute-time embedding, the legacy
``estimate_solution`` shim, benchmarks) solves through :func:`solve`:

* **resident** operators run a single cached ``jax.jit(lax.while_loop)``
  program per (method, mesh, geometry): the tolerance, the step cap, the
  Chebyshev interval bound and the warm-start iterate all enter as
  *operands*, so a steady-state ``SequenceDetector.push`` -- or a tolerance
  change between solves, or switching between cold and warm starts -- adds
  zero traces and zero program-cache misses;
* **streamed** operators (store-backed P1/P2 from an out-of-core chain) run a
  host Python loop -- a traced loop body cannot fetch panels -- reusing the
  :class:`repro.store.CachingHandle` iteration batching (stream the scratch
  once per ``solver_batch`` iterations, replay from host RAM) and the panel
  pipeline's ``prefetch_depth`` staging.

Both paths stop on the same metric: the relative preconditioned residual
``||Z^(b - L y)||_F / ||Z^ b||_F``, which is free to measure (for Richardson
it *is* the step just taken) and bounds the true error by ``1/(1 - rho)``.
The denominator is always ``||Z^ b||`` -- in particular it does NOT become
``||Z^(b - L y0)||`` under a warm start, so a tolerance keeps exactly the
same meaning whether the solve starts cold (``y0 = chi``) or from a previous
snapshot's solution.  Adding a method means adding one iteration rule here;
the registry below is the whole surface.

Methods:

* ``richardson`` -- the paper's Algorithm 2 iteration ``y <- y + Z^(b - L y)``,
  now with residual-targeted stopping instead of always paying the worst-case
  ``q = ceil(log 1/delta)``.
* ``chebyshev`` -- classical Chebyshev semi-iterative acceleration (Golub &
  Varga; Hageman & Young form) of the same stationary iteration.  Using the
  power-iteration bound ``spec(G) in [0, rho]`` cached on the operator
  (:mod:`repro.core.solvers.power`), the three-term recurrence

      y_{k+1} = p_{k+1} [ gamma (G y_k + chi) + (1 - gamma) y_k ]
                + (1 - p_{k+1}) y_{k-1}

  with ``gamma = 2/(2 - rho)``, ``sigma = rho/(2 - rho)``, ``p_1 = 1``,
  ``p_2 = (1 - sigma^2/2)^{-1}``, ``p_{k+1} = (1 - sigma^2 p_k / 4)^{-1}``
  reaches a given residual in ~sqrt-fewer iterations than Richardson (error
  ~``2 r^k`` with ``r = sigma / (1 + sqrt(1 - sigma^2)) < rho``) -- and
  out-of-core, iterations are streamed passes over the P2 scratch, so the
  same factor comes off ``stream_stats().bytes_read``.  With ``rho -> 0`` the
  recurrence degenerates exactly to Richardson.  The interval adapts
  Manteuffel-style during the solve (see ``_rho_from_rate``): when the
  measured contraction misses the asymptotic rate the current interval
  predicts, the bound was an underestimate (power iteration converges to rho
  from below) -- the interval grows and the recurrence restarts from the
  current iterate.  This retires the old static ``RHO_GAP_SAFETY`` margin.
* ``cg`` -- conjugate gradients on the deflated SPD subspace, after Khoa &
  Chawla's solve-to-epsilon framing (arXiv:1111.4541).  The preconditioned
  operator is ``P2 = Z^ L = I - D^{-1/2} S~^{2^d} D^{1/2}``, so
  ``D^{1/2} P2 D^{-1/2} = I - S~^{2^d}`` is symmetric with spectrum in
  ``[1 - rho, 1]`` on the deflated subspace: CG with *degree-weighted* inner
  products ``<u, v>_D = u^T D v`` (the operator's ``deg`` vector) is exact
  CG on that SPD form.  One P2 mat-vec per iteration -- streamed, one pass
  over the P2 scratch, batched through ``CachingHandle`` and routed through
  the fused stream-GEMM kernel exactly like the stationary methods.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.sharding import PartitionSpec as P

from repro.core.chain import factored_p1
from repro.core.distmatrix import F32_PRECISION, DistContext, matmul_rowblock
from repro.core.solvers.base import SolveReport, SolverSpec
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY as _OBS_REGISTRY
from repro.core.tiles import (
    cached_program,
    is_streamable,
    program_cache_stats,
    shard_map,
    stream_stats,
)

RHO_MAX = 0.999

# Manteuffel-style interval adaptation (chebyshev).  The Chebyshev
# pseudo-residual is NOT monotone -- it oscillates with a short period even
# when the interval is correct -- so the observed contraction is measured as
# the *geometric mean* since the last (re)start, c = (res/res_anchor)^(1/kr),
# never step-to-step, and only after RHO_ADAPT_MIN_STEPS steps (enough to
# span an oscillation cycle).  When that smoothed rate misses the predicted
# asymptotic rate by more than RHO_ADAPT_SLACK, an eigenvalue of G sticks out
# of [0, rho]: grow the interval and restart the recurrence from the current
# iterate.  The growth is the SMALLER of the rate-implied bound (exact
# inverse of the predicted-rate formula, the right answer for a mild miss)
# and a gap-halving step (bounds the jump when the iteration has fully
# stalled and the measured ratio ~1 would otherwise slam the interval
# straight to RHO_MAX).
RHO_ADAPT_SLACK = 1.2
RHO_ADAPT_MIN_STEPS = 4
# No adaptation once the relative residual approaches the float32 noise
# floor: a roundoff-dominated stall there reads as c ~ 1 -- indistinguishable
# from a missed rate -- and growing the interval on it wrecks an
# already-converged iteration.  Conservative (two decades above f32 eps):
# a genuine interval underestimate shows up while residuals are still large.
RHO_ADAPT_RES_FLOOR = 1e-5

# Fixed-size residual-history buffer carried through the resident while_loop
# (a traced loop cannot append to a Python list).  Comfortably above
# TOLERANCE_ITER_CAP (300), so in practice the full per-iteration residual
# series survives; a longer run wraps the ring -- the driver un-rotates it so
# SolveReport.residuals is always the chronological tail.
RES_HIST_CAP = 512


def deflate_constant(ctx: DistContext, y: jax.Array) -> jax.Array:
    """Remove the all-ones (Laplacian nullspace) component from each column.

    Solutions of L z = y are defined up to a constant shift, which cancels in
    commute distances; removing it keeps bf16/fp32 iterates from drifting.
    The result is constrained to the row-sharded layout so the mean-subtract
    (an all-reduce over rows) can't silently regather the operand.
    """
    mean = jnp.mean(y.astype(jnp.float32), axis=0, keepdims=True)
    out = (y.astype(jnp.float32) - mean).astype(y.dtype)
    return ctx.constrain(out, ctx.rowblock_spec)


def _frob(x: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))


def _cheb_weight(k, p_prev, sigma2):
    """p_{k+1} of the Chebyshev three-term recurrence (k is the 0-based step
    counter since the last restart: step 0 uses p_1 = 1, step 1 uses p_2,
    then the general rule)."""
    return jnp.where(
        k == 0,
        jnp.float32(1.0),
        jnp.where(
            k == 1,
            1.0 / (1.0 - 0.5 * sigma2),
            1.0 / (1.0 - 0.25 * sigma2 * p_prev),
        ),
    ).astype(jnp.float32)


def _cheb_rate(sigma2):
    """Predicted asymptotic per-step contraction of the Chebyshev recurrence
    on [0, rho]: r = sigma / (1 + sqrt(1 - sigma^2))."""
    return jnp.sqrt(sigma2) / (1.0 + jnp.sqrt(jnp.maximum(1.0 - sigma2, 0.0)))


def _rho_from_rate(c):
    """Invert the rate formula: the interval bound whose predicted asymptotic
    contraction equals the measured per-step ratio ``c``.  Inverse pair:
    c = sigma/(1+sqrt(1-sigma^2)) <=> sigma = 2c/(1+c^2), and
    sigma = rho/(2-rho) <=> rho = 2 sigma/(1+sigma)."""
    sigma = 2.0 * c / (1.0 + c * c)
    return 2.0 * sigma / (1.0 + sigma)


def _unrotate_hist(hist: np.ndarray, iters: int) -> list[float]:
    """Chronological residual series from the while_loop's ring buffer.

    The loop writes step k at index ``k mod RES_HIST_CAP``; once
    ``iters > RES_HIST_CAP`` the buffer has wrapped and the oldest surviving
    entry sits at ``iters mod RES_HIST_CAP`` -- rotate so the returned series
    is the last ``RES_HIST_CAP`` residuals in order.
    """
    cap = hist.shape[0]
    if iters <= cap:
        out = hist[:iters]
    else:
        s = iters % cap
        out = np.concatenate([hist[s:], hist[:s]])
    return [float(r) for r in out]


def _corrected_matvec(ctx: DistContext, ops, y: jax.Array) -> jax.Array:
    """``P2' y = P1' L' y`` of a delta-corrected operator, from its parts
    ``ops = (p1, p1_scale, u1, v1, adj, deg)``: ``L' y = deg y - A' y``, then
    ``P1' x = s (P1 (s x)) + u1 (v1^T x)``.  ``p1`` and ``adj`` are resident
    arrays or store-backed handles (``matmul_rowblock`` streams those)."""
    p1, scale, u1, v1, adj, deg = ops
    y32 = y.astype(jnp.float32)
    s_col = scale.astype(jnp.float32).reshape(-1, 1)
    ly = deg.astype(jnp.float32).reshape(-1, 1) * y32 - matmul_rowblock(ctx, adj, y32)
    low = jnp.dot(
        u1, jnp.dot(v1.T, ly, precision=F32_PRECISION),
        precision=F32_PRECISION, preferred_element_type=jnp.float32,
    )
    out = s_col * matmul_rowblock(ctx, p1, s_col * ly) + low
    return ctx.constrain(out.astype(y.dtype), ctx.rowblock_spec)


def _factored_matvec(ctx: DistContext, ops, y: jax.Array) -> jax.Array:
    """``P2 y = P1 (deg y - A y)`` of a factored operator, from its parts
    ``ops = (t_levels, deg, adj)`` (:func:`repro.core.chain.factored_p1`):
    d + 1 skinny passes, the adjacency's and one per T level."""
    t_levels, deg, adj = ops
    y32 = y.astype(jnp.float32)
    ly = deg.astype(jnp.float32).reshape(-1, 1) * y32 - matmul_rowblock(ctx, adj, y32)
    out = factored_p1(ctx, t_levels, deg, ly)
    return ctx.constrain(out.astype(y.dtype), ctx.rowblock_spec)


# ---------------------------------------------------------------------------
# resident path: one cached while_loop program per (method, ctx, geometry)
# ---------------------------------------------------------------------------


def _resident_program(ctx: DistContext, method: str, deflate: bool, chi,
                      corr_rank: int | None = None, n_levels: int | None = None):
    """The jitted adaptive loop.  Stopping operands (tol, max_steps, rho) and
    the warm-start iterate y0 are traced, so one compiled program serves
    every tolerance/cap/rho and both cold (y0 = chi) and warm starts.

    ``corr_rank`` selects the delta-corrected variant: ``ops`` is then
    ``(p1, p1_scale, u1, v1, adj, deg)`` and every mat-vec is
    ``P2' y = P1' (deg y - A' y)`` (:func:`_corrected_matvec`), operands of
    the same while_loop program, so a steady-state incremental sequence
    compiles the corrected program once per correction rank and every later
    corrected push is a cache hit.  ``n_levels`` selects the factored
    variant the same way: ``ops`` is ``(t_levels, deg, adj)`` and every
    mat-vec is ``P1 (deg y - A y)`` (:func:`_factored_matvec`), compiled once
    per chain length.  Materialized solves pass ``ops = (p2,)`` and keep the
    historical program (and its bitwise behaviour) untouched.
    """

    def build():
        def matvec(ops, y):
            if corr_rank is not None:
                return _corrected_matvec(ctx, ops, y)
            if n_levels is not None:
                return _factored_matvec(ctx, ops, y)
            # identical op sequence to matmul_rowblock's resident branch
            out = jnp.dot(
                ops[0], y.astype(jnp.float32),
                precision=F32_PRECISION, preferred_element_type=jnp.float32,
            )
            return ctx.constrain(out.astype(y.dtype), ctx.rowblock_spec)

        def metric_deflate(delta):
            # Measure the residual on the solve's invariant subspace: the
            # iterate is deflated every step, so a nullspace (constant)
            # component of chi - P2 y is noise that never decays -- it
            # must not keep an otherwise-converged solve running.
            if deflate:
                delta = delta - jnp.mean(
                    delta.astype(jnp.float32), axis=0, keepdims=True
                )
            return delta

        def run(ops, chi, y0, tol, max_steps, rho):
            den = jnp.maximum(_frob(chi), 1e-30)

            def cond(carry):
                _, _, k, _, _, _, _, _, res = carry
                return jnp.logical_and(k < max_steps, res > tol)

            def body(carry):
                y, y_prev, k, kr, res_anchor, p_prev, rho_c, hist, _ = carry
                gamma = 2.0 / (2.0 - rho_c)
                sigma2 = (rho_c / (2.0 - rho_c)) ** 2
                gy = y - matvec(ops, y) + chi  # G y + chi; gy - y is the residual
                if method == "richardson":
                    y_new, p_new = gy, p_prev
                else:
                    p_new = _cheb_weight(kr, p_prev, sigma2)
                    y_new = p_new * (gamma * gy + (1.0 - gamma) * y) + (1.0 - p_new) * y_prev
                    y_new = ctx.constrain(y_new.astype(chi.dtype), ctx.rowblock_spec)
                if deflate:
                    y_new = deflate_constant(ctx, y_new)
                res = _frob(metric_deflate(gy - y)) / den
                hist = lax.dynamic_update_index_in_dim(
                    hist, res, jnp.mod(k, RES_HIST_CAP), 0
                )
                # the contraction anchor: the residual at the last (re)start
                res_anchor = jnp.where(kr == 0, res, res_anchor)
                kr_new = kr + jnp.int32(1)
                if method == "chebyshev":
                    # Manteuffel-style adaptation on the geometric-mean
                    # contraction since the last restart (the pseudo-residual
                    # oscillates; per-step ratios false-trigger).
                    c_avg = jnp.power(
                        res / jnp.maximum(res_anchor, jnp.float32(1e-30)),
                        1.0 / jnp.maximum(kr.astype(jnp.float32), 1.0),
                    )
                    pred = _cheb_rate(sigma2)
                    miss = jnp.logical_and(
                        kr >= RHO_ADAPT_MIN_STEPS,
                        jnp.logical_and(
                            c_avg > jnp.minimum(pred * RHO_ADAPT_SLACK, 0.999),
                            res > jnp.float32(RHO_ADAPT_RES_FLOOR),
                        ),
                    )
                    implied = _rho_from_rate(jnp.minimum(c_avg, 0.9995))
                    gap_half = 1.0 - 0.5 * (1.0 - rho_c)
                    rho_new = jnp.minimum(
                        jnp.minimum(implied, gap_half), jnp.float32(RHO_MAX)
                    )
                    grow = jnp.logical_and(miss, rho_new > rho_c)
                    rho_c = jnp.where(grow, rho_new, rho_c).astype(jnp.float32)
                    # restart: kr = 0 makes the next step use p_1 = 1, which
                    # zeroes the y_prev term -- a fresh start from y_new.
                    kr_new = jnp.where(grow, jnp.int32(0), kr_new)
                return (
                    y_new, y, k + jnp.int32(1), kr_new, res_anchor, p_new,
                    rho_c, hist, res,
                )

            init = (
                y0, y0, jnp.int32(0), jnp.int32(0), jnp.float32(jnp.inf),
                jnp.float32(1.0), rho,
                jnp.zeros((RES_HIST_CAP,), jnp.float32), jnp.float32(jnp.inf),
            )
            y, _, k, _, _, _, rho_c, hist, res = lax.while_loop(cond, body, init)
            return y, k, res, hist, rho_c

        def run_cg(ops, chi, y0, w, tol, max_steps):
            den = jnp.maximum(_frob(chi), 1e-30)
            wcol = jnp.maximum(w.astype(jnp.float32), 0.0).reshape(-1, 1)
            wsum = jnp.maximum(jnp.sum(wcol), 1e-30)

            def wdot(u, v):
                return jnp.sum(wcol * u * v, axis=0, keepdims=True)

            def dproj(x):
                # project onto range(P2) = {u : 1^T D u = 0}: remove the
                # deg-weighted mean (the D-geometry's nullspace direction)
                return x - jnp.sum(wcol * x, axis=0, keepdims=True) / wsum

            r0 = chi.astype(jnp.float32) - matvec(
                ops, y0.astype(jnp.float32)
            ).astype(jnp.float32)
            if deflate:
                r0 = dproj(r0)
            r0 = ctx.constrain(r0, ctx.rowblock_spec)

            def cond(carry):
                _, _, _, _, k, res, _ = carry
                return jnp.logical_and(k < max_steps, res > tol)

            def body(carry):
                y, r, p, rz, k, _, hist = carry
                q = matvec(ops, p)
                if deflate:
                    q = ctx.constrain(dproj(q), ctx.rowblock_spec)
                pq = wdot(p, q)
                alpha = jnp.where(pq > 0, rz / jnp.maximum(pq, 1e-30), 0.0)
                y_new = (y.astype(jnp.float32) + alpha * p).astype(chi.dtype)
                if deflate:
                    y_new = deflate_constant(ctx, y_new)
                y_new = ctx.constrain(y_new, ctx.rowblock_spec)
                r_new = r - alpha * q
                if deflate:
                    r_new = dproj(r_new)
                r_new = ctx.constrain(r_new, ctx.rowblock_spec)
                rz_new = wdot(r_new, r_new)
                beta = jnp.where(rz > 0, rz_new / jnp.maximum(rz, 1e-30), 0.0)
                p_new = ctx.constrain(r_new + beta * p, ctx.rowblock_spec)
                res = _frob(metric_deflate(r_new)) / den
                hist = lax.dynamic_update_index_in_dim(
                    hist, res, jnp.mod(k, RES_HIST_CAP), 0
                )
                return (y_new, r_new, p_new, rz_new, k + jnp.int32(1), res, hist)

            init = (
                y0, r0, r0, wdot(r0, r0), jnp.int32(0), jnp.float32(jnp.inf),
                jnp.zeros((RES_HIST_CAP,), jnp.float32),
            )
            y, _, _, _, k, res, hist = lax.while_loop(cond, body, init)
            return y, k, res, hist

        return jax.jit(run_cg if method == "cg" else run)

    key = (
        "solve_driver", method, ctx, deflate, tuple(chi.shape),
        np.dtype(chi.dtype).name, RES_HIST_CAP, corr_rank, n_levels,
    )
    return cached_program(key, build)


# ---------------------------------------------------------------------------
# streamed path: host loop (a traced body cannot fetch panels)
# ---------------------------------------------------------------------------


def _kernel_panel_program(ctx, ph: int, n: int, k: int, panel_dtype: str,
                          fused: bool):
    """Cached shard_map program for one streamed panel of the kernel path.

    The panel arrives matrix-sharded in its *stored* form (uint16 bf16 bit
    patterns, or fp32 for raw scratch); ``y`` (and ``chi``, fused) ride
    replicated so every device can slice both its column window (the GEMM
    operand) and the panel's global row window (the epilogue operands --
    panel row-sharding does not coincide with the solver's rowblock
    sharding, so a sliced-from-replicated read is the only layout-safe way
    in).  ``fused=True`` is one solve iteration over the panel: mat-vec +
    ``gy = chi + y - P2 y`` + deflated-residual moments, single kernel pass
    where the mesh has one column shard, kernel mat-vec + psum + jnp
    epilogue otherwise.  ``fused=False`` is the plain mat-vec (the chi
    build and the CG direction product).  The row origin is traced, so one
    program serves every panel.
    """

    def build():
        from repro.kernels.ops import fused_panel_matvec, stream_gemm

        R, C = ctx.n_row_shards, ctx.n_col_shards
        pr, pc = ph // R, n // C

        def local(r0, p_blk, y_rep, *rest):
            program_cache_stats().note_trace()
            row0 = r0 + lax.axis_index(ctx.row_axes) * pr
            if C == 1:
                y_cols = y_rep
            else:
                c = lax.axis_index(ctx.col_axes)
                y_cols = lax.dynamic_slice(y_rep, (c * pc, jnp.int32(0)), (pc, k))
            # The psums over a size-1 axis (C == 1, R == 1) move no data; they
            # make the outputs invariant over that axis, as out_specs require.
            if not fused:
                return lax.psum(stream_gemm(p_blk, y_cols), ctx.col_axes)
            (chi_rep,) = rest
            y_rows = lax.dynamic_slice(y_rep, (row0, jnp.int32(0)), (pr, k))
            chi_rows = lax.dynamic_slice(chi_rep, (row0, jnp.int32(0)), (pr, k))
            if C == 1:
                gy, cs, ss = lax.psum(
                    fused_panel_matvec(p_blk, y_cols, chi_rows, y_rows), ctx.col_axes
                )
            else:
                mv = lax.psum(stream_gemm(p_blk, y_cols), ctx.col_axes)
                gy = chi_rows + y_rows - mv
                delta = chi_rows - mv
                cs = jnp.sum(delta, axis=0, keepdims=True)
                ss = jnp.sum(delta * delta).reshape(1, 1)
            return gy, *lax.psum((cs, ss), ctx.row_axes)

        out_specs = P(ctx.row_axes, None)
        if fused:
            out_specs = (out_specs, P(None, None), P(None, None))
        in_specs = (P(), ctx.matrix_spec, P(None, None))
        if fused:
            in_specs = in_specs + (P(None, None),)
        return jax.jit(
            shard_map(
                local, mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs
            )
        )

    key = ("kernel_panel_matvec", ctx, ph, n, k, panel_dtype, fused)
    return cached_program(key, build)


def _kernel_stream_pass(ctx, handle, y, chi, *, depth, fused):
    """One pass over a store-backed operator through the Pallas kernel path.

    Panels stream in stored form (``encoded=True`` pipeline: bf16 scratch
    ships uint16 bit patterns, half the H2D bytes, decoded in VMEM by the
    kernel).  ``fused=True`` returns ``(gy, colsum, sumsq)`` for one whole
    solve iteration -- ``gy = chi + y - P2 y`` row-sharded plus the residual
    moments of ``delta = chi - P2 y`` reduced over all n rows -- so the
    iteration costs exactly this one pass over the stream.  ``fused=False``
    returns the plain mat-vec (the chi build / CG direction product).
    Per-panel outputs are concatenated in the solver's rowblock sharding.
    """
    from repro.store import PanelPipeline  # deferred: optional path

    n = int(handle.shape[0])
    k = int(y.shape[1])
    ph = int(np.lcm(int(handle.panel_rows), ctx.n_row_shards))
    if n % ph:
        raise ValueError(f"panel height {ph} does not tile n={n}")
    st = stream_stats()
    st.add(calls=1)
    sharding = ctx.sharding(ctx.matrix_spec)
    y_rep = ctx.constrain(y.astype(jnp.float32), P(None, None))
    chi_rep = (
        ctx.constrain(chi.astype(jnp.float32), P(None, None)) if fused else None
    )
    parts = []
    cs_total, ss_total = None, 0.0
    prog = None
    with PanelPipeline(
        [handle], range(0, n, ph), ph, depth=depth, sharding=sharding,
        stats=st, encoded=True,
    ) as pipe:
        for r0, (panel,) in pipe:
            if prog is None:
                prog = _kernel_panel_program(
                    ctx, ph, n, k, str(panel.dtype), fused
                )
            if fused:
                gy_p, cs, ss = prog(jnp.int32(r0), panel, y_rep, chi_rep)
                cs_np = np.asarray(cs, np.float64)[0]
                cs_total = cs_np if cs_total is None else cs_total + cs_np
                ss_total += float(np.asarray(ss)[0, 0])
            else:
                gy_p = prog(jnp.int32(r0), panel, y_rep)
            st._note_live(pipe.device_live_bytes + gy_p.nbytes)
            parts.append(gy_p)
    out = ctx.constrain(jnp.concatenate(parts, axis=0), ctx.rowblock_spec)
    if fused:
        return out, cs_total, ss_total
    return out


def _solve_streamed(
    ctx, p2_handle, chi, y0, method, deflate, tol, max_steps, rho,
    solver_batch, prefetch_depth, use_kernel=False, w=None, corr=None,
):
    p2, cached = p2_handle, None
    if solver_batch > 1 and is_streamable(p2_handle):
        from repro.store import CachingHandle  # deferred: optional path

        p2 = cached = CachingHandle(p2_handle)
    den = max(float(_frob(chi)), 1e-30)
    n_rows = int(chi.shape[0])
    passes = 0

    def stream_matvec(x):
        """One P2 @ x pass over the stream (kernel path when enabled); a
        corrected operator's P1' L' x streams its P1 and adjacency."""
        nonlocal passes
        if corr is not None:
            return _corrected_matvec(ctx, corr, x).astype(jnp.float32)
        if cached is not None and passes and passes % solver_batch == 0:
            cached.refresh()  # batch boundary: next pass re-streams the store
        passes += 1
        if use_kernel:
            mv = _kernel_stream_pass(ctx, p2, x, None, depth=prefetch_depth,
                                     fused=False)
            mv = mv.astype(jnp.float32)
        else:
            mv = matmul_rowblock(
                ctx, p2, x, prefetch_depth=prefetch_depth
            ).astype(jnp.float32)
        return ctx.constrain(mv, ctx.rowblock_spec)

    def metric(delta):
        if deflate:
            delta = delta - jnp.mean(
                delta.astype(jnp.float32), axis=0, keepdims=True
            )
        return float(_frob(delta)) / den

    res_hist: list[float] = []

    if method == "cg":
        wcol = jnp.maximum(
            jnp.asarray(w, jnp.float32).reshape(-1, 1), 0.0
        )
        wsum = max(float(jnp.sum(wcol)), 1e-30)

        def wdot(u, v):
            return jnp.sum(wcol * u * v, axis=0, keepdims=True)

        def dproj(x):
            m = jnp.sum(wcol * x, axis=0, keepdims=True) / wsum
            return ctx.constrain(x - m, ctx.rowblock_spec)

        y = y0
        r = chi.astype(jnp.float32) - stream_matvec(y0.astype(jnp.float32))
        if deflate:
            r = dproj(r)
        p_dir = r
        rz = wdot(r, r)
        k, res = 0, math.inf
        while k < max_steps and res > tol:
            q = stream_matvec(p_dir)
            if deflate:
                q = dproj(q)
            pq = wdot(p_dir, q)
            alpha = jnp.where(pq > 0, rz / jnp.maximum(pq, 1e-30), 0.0)
            y = (y.astype(jnp.float32) + alpha * p_dir).astype(chi.dtype)
            if deflate:
                y = deflate_constant(ctx, y)
            y = ctx.constrain(y, ctx.rowblock_spec)
            r = r - alpha * q
            if deflate:
                r = dproj(r)
            rz_new = wdot(r, r)
            beta = jnp.where(rz > 0, rz_new / jnp.maximum(rz, 1e-30), 0.0)
            p_dir = ctx.constrain(r + beta * p_dir, ctx.rowblock_spec)
            rz = rz_new
            res = metric(r)
            k += 1
            res_hist.append(float(res))
        return y, k, res, res_hist, None

    rho_c = float(rho)
    gamma = 2.0 / (2.0 - rho_c)
    sigma2 = (rho_c / (2.0 - rho_c)) ** 2

    y, y_prev, p_prev = y0, y0, 1.0
    k, kr, res, res_anchor = 0, 0, math.inf, math.inf
    while k < max_steps and res > tol:
        if use_kernel:
            # One fused pass over the P2 stream: gy AND the residual moments
            # of delta = chi - P2 y come out of the same kernel traversal, so
            # each iteration reads the scratch exactly once.
            if cached is not None and passes and passes % solver_batch == 0:
                cached.refresh()
            passes += 1
            gy, cs, ss = _kernel_stream_pass(
                ctx, p2, y, chi, depth=prefetch_depth, fused=True
            )
            gy = ctx.constrain(gy.astype(chi.dtype), ctx.rowblock_spec)
            num2 = ss - float(np.sum(cs * cs)) / n_rows if deflate else ss
            res = math.sqrt(max(num2, 0.0)) / den
        else:
            gy = y - stream_matvec(y).astype(chi.dtype) + chi
        if method == "richardson":
            y_new = gy
        else:
            # same weight rule as the traced path; host scalars here
            p_new = float(_cheb_weight(kr, p_prev, sigma2))
            y_new = p_new * (gamma * gy + (1.0 - gamma) * y) + (1.0 - p_new) * y_prev
            y_new = ctx.constrain(y_new.astype(chi.dtype), ctx.rowblock_spec)
            p_prev = p_new
        if deflate:
            y_new = deflate_constant(ctx, y_new)
        if not use_kernel:
            res = metric(gy - y)  # residual, minus its never-decaying nullspace part
        if kr == 0:
            res_anchor = res  # contraction anchor: residual at the (re)start
        kr += 1
        if (
            method == "chebyshev"
            and kr - 1 >= RHO_ADAPT_MIN_STEPS
            and res > RHO_ADAPT_RES_FLOOR
        ):
            # geometric-mean contraction since the restart (see the constants
            # block: per-step ratios false-trigger on the oscillation)
            pred = float(_cheb_rate(jnp.float32(sigma2)))
            c_avg = (res / max(res_anchor, 1e-30)) ** (1.0 / max(kr - 1, 1))
            if c_avg > min(pred * RHO_ADAPT_SLACK, 0.999):
                implied = _rho_from_rate(min(c_avg, 0.9995))
                rho_new = min(implied, 1.0 - 0.5 * (1.0 - rho_c), RHO_MAX)
                if rho_new > rho_c:
                    rho_c = rho_new
                    gamma = 2.0 / (2.0 - rho_c)
                    sigma2 = (rho_c / (2.0 - rho_c)) ** 2
                    kr = 0  # restart: next step uses p_1 = 1 from y_new
        y_prev, y = y, y_new
        k += 1
        res_hist.append(float(res))
    return y, k, res, res_hist, rho_c


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------


def solve(
    ctx: DistContext,
    op,
    b: jax.Array,
    spec: SolverSpec | None = None,
    *,
    fixed_q: int | None = None,
    deflate: bool = True,
    solver_batch: int = 1,
    prefetch_depth: int | None = None,
    use_gemm_kernel: bool | None = None,
    y0: jax.Array | None = None,
) -> tuple[jax.Array, SolveReport]:
    """x* ~= L^+ b for each column of the row-sharded (n, k) ``b``.

    ``op`` is any chain operator (duck-typed: ``p1``/``p2`` arrays or
    store-backed handles, optional ``prefetch_depth``/``rho``/``deg``
    metadata; a factored operator's ``t_levels``, ``deg`` and ``adj`` in
    place of ``p1``/``p2``).  ``fixed_q`` feeds the legacy fixed-iteration
    default: with no tolerance, cap or delta on the spec, the driver runs exactly
    ``fixed_q - 1`` refinement steps -- bit-compatible with the historical
    Richardson loop.  ``solver_batch``/``prefetch_depth`` are the streamed
    path's I/O knobs (ignored resident -- nothing streams); see
    :func:`repro.core.solver.estimate_solution` for their semantics.

    ``y0`` warm-starts the iteration: the previous snapshot's solution (same
    shape as ``b``'s solution) replaces the cold ``y0 = chi`` start, so a
    slowly-drifting sequence's first residual starts at ~|dA| instead of
    ~1.  The iterate is deflated on entry (a stale nullspace component must
    not survive into the new solve) and the stopping denominator stays
    ``||Z^ b||`` -- tolerances mean the same thing warm or cold.

    ``use_gemm_kernel`` routes the streamed iterations (and the chi build,
    where P1 is also a handle) through the fused Pallas stream-GEMM path:
    panels ship in stored form and each iteration is a single fused pass
    over the P2 stream (mat-vec + update + residual moments).  ``None``
    (default) inherits the flag the out-of-core chain build stamped on the
    operator; resident solves ignore it.

    Returns ``(solution, SolveReport)``; the report carries iterations, the
    final relative preconditioned residual, and the scratch-store traffic of
    this solve.  A run that never measured a residual (``max_iters=0``)
    reports ``residual=nan, converged=False``.
    """
    spec = spec or SolverSpec()
    if solver_batch < 1:
        raise ValueError("solver_batch must be >= 1")
    depth = prefetch_depth if prefetch_depth is not None else getattr(
        op, "prefetch_depth", None
    )
    max_steps = spec.max_steps(fixed_q)
    tol = 0.0 if spec.tolerance is None else float(spec.tolerance)

    # Incremental-chain correction (None on a plain base operator):
    # p1_scale/u1/v1 turn the chi build into the corrected
    # P1' b = s * (P1 (s * b)) + u1 (v1^T b), and every mat-vec of the
    # iteration applies P2' = P1' (D' - A') from the snapshot's adjacency.
    # A factored operator's T levels apply P1 and P2 = P1 (D - A) instead.
    p1_scale = getattr(op, "p1_scale", None)
    u1 = getattr(op, "u1", None)
    v1 = getattr(op, "v1", None)
    adj = getattr(op, "adj", None)
    t_levels = getattr(op, "t_levels", None)
    factored = None if t_levels is None else (tuple(t_levels), op.deg, adj)
    corr = None if adj is None or factored is not None else (
        op.p1, p1_scale, u1, v1, adj, op.deg
    )
    corr_rank = None if corr is None else int(u1.shape[1])

    rho = None
    if spec.method == "chebyshev":
        rho_raw = getattr(op, "rho", None)
        if rho_raw is None:
            from repro.core.solvers.power import estimate_rho

            if factored is not None:
                rho_raw = estimate_rho(
                    ctx, lambda y: _factored_matvec(ctx, factored, y), n=int(b.shape[0])
                )
            else:
                rho_raw = estimate_rho(ctx, op.p2, prefetch_depth=depth)
            if hasattr(op, "rho"):
                op.rho = rho_raw  # cache: later solves on this operator reuse it
        # Start from the raw power-iteration estimate (it converges to rho
        # from below); Manteuffel-style adaptation during the solve grows the
        # interval if the estimate's lag shows up as a missed contraction.
        rho = min(RHO_MAX, max(0.0, float(rho_raw)))

    w = None
    if spec.method == "cg":
        w = getattr(op, "deg", None)
        if w is None:
            # No degree metadata on the operator: fall back to the Euclidean
            # inner product (exact only for uniform degrees).
            w = jnp.ones((int(b.shape[0]),), jnp.float32)

    streamed = any(is_streamable(m) for m in (op.p1, op.p2, adj))
    use_k = bool(
        use_gemm_kernel
        if use_gemm_kernel is not None
        else getattr(op, "use_gemm_kernel", False)
    )
    st = stream_stats()
    read0, panels0, h2d0 = st.bytes_read, st.panels, st.bytes_h2d
    warm = y0 is not None

    with obs_trace.span(
        "solver.solve", method=spec.method, streamed=streamed, warm=warm
    ) as sp:
        b = ctx.constrain(b, ctx.rowblock_spec)
        b_in = b
        if p1_scale is not None:
            scale_col = p1_scale.astype(jnp.float32).reshape(-1, 1)
            b_in = ctx.constrain(
                (b.astype(jnp.float32) * scale_col).astype(b.dtype),
                ctx.rowblock_spec,
            )
        if factored is not None:
            chi = ctx.constrain(
                factored_p1(ctx, factored[0], op.deg, b_in).astype(b.dtype),
                ctx.rowblock_spec,
            )
        elif streamed and use_k and is_streamable(op.p1):
            chi = _kernel_stream_pass(
                ctx, op.p1, b_in, None, depth=depth, fused=False
            )
            chi = ctx.constrain(chi.astype(b.dtype), ctx.rowblock_spec)
        else:
            chi = matmul_rowblock(ctx, op.p1, b_in, prefetch_depth=depth)
        if p1_scale is not None:
            chi = (
                chi.astype(jnp.float32) * scale_col
                + jnp.dot(
                    u1, jnp.dot(v1.T, b.astype(jnp.float32), precision=F32_PRECISION),
                    precision=F32_PRECISION, preferred_element_type=jnp.float32,
                )
            ).astype(b.dtype)
            chi = ctx.constrain(chi, ctx.rowblock_spec)
        if deflate:
            chi = deflate_constant(ctx, chi)

        if warm:
            if tuple(y0.shape) != tuple(chi.shape):
                raise ValueError(
                    f"warm start y0 shape {tuple(y0.shape)} does not match "
                    f"the solution shape {tuple(chi.shape)}"
                )
            y_start = ctx.constrain(y0.astype(chi.dtype), ctx.rowblock_spec)
            if deflate:
                y_start = deflate_constant(ctx, y_start)
        else:
            y_start = chi  # historical cold start: y0 = chi = Z^ b

        rho_final = rho
        if streamed:
            y, iters, res, res_hist, rho_final = _solve_streamed(
                ctx, op.p2, chi, y_start, spec.method, deflate, tol, max_steps,
                rho or 0.0, solver_batch, depth,
                use_kernel=use_k and is_streamable(op.p2), w=w, corr=corr,
            )
            if spec.method != "chebyshev":
                rho_final = rho
        else:
            prog = _resident_program(
                ctx, spec.method, deflate, chi, corr_rank,
                None if factored is None else len(factored[0]),
            )
            ops = factored or corr or (op.p2,)
            if spec.method == "cg":
                y, k_arr, res_arr, hist_arr = prog(
                    ops, chi, y_start, jnp.asarray(w),
                    jnp.float32(tol), jnp.int32(max_steps),
                )
            else:
                y, k_arr, res_arr, hist_arr, rho_arr = prog(
                    ops, chi, y_start, jnp.float32(tol),
                    jnp.int32(max_steps), jnp.float32(rho or 0.0),
                )
                if spec.method == "chebyshev":
                    rho_final = float(rho_arr)
            iters, res = int(k_arr), float(res_arr)
            res_hist = _unrotate_hist(np.asarray(hist_arr), iters)
        if iters == 0:
            # The loop never ran (max_iters=0): no residual was ever
            # measured -- report that honestly rather than inf/converged.
            res = float("nan")
        sp.annotate(iterations=iters, residual=res)
        sp.fence(y)

    st = stream_stats()
    report = SolveReport(
        method=spec.method,
        iterations=iters,
        residual=res,
        converged=(not math.isnan(res))
        and ((spec.tolerance is None) or res <= spec.tolerance),
        tolerance=spec.tolerance,
        max_iters=max_steps,
        streamed=streamed,
        rho=rho,
        bytes_read=st.bytes_read - read0,
        bytes_h2d=st.bytes_h2d - h2d0,
        panels=st.panels - panels0,
        residuals=tuple(res_hist),
        rho_final=rho_final,
        warm_start=warm,
    )
    _OBS_REGISTRY.add_named({
        "solver.solves": 1.0,
        "solver.iterations": float(iters),
        "solver.not_converged": 0.0 if report.converged else 1.0,
        "solver.warm_starts": 1.0 if warm else 0.0,
    })
    _OBS_REGISTRY.extend("solver.residuals", res_hist)
    return y, report

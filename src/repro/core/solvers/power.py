"""Power-iteration estimate of rho(S~^{2^d}) -- the Richardson contraction.

The preconditioned Richardson iteration contracts with the iteration matrix
``G = I - Z^ L``, which (by the telescoping identity ``(I - S~) P = I -
S~^{2^d}``) is ``D^{-1/2} S~^{2^d} D^{1/2}`` -- similar to the symmetric
``S~^{2^d}``, so its spectrum is real, and for d >= 1 the exponent ``2^d`` is
even, so it is also nonnegative: ``spec(G) in [0, rho]`` on the 1-orthogonal
subspace with ``rho = rho(S~^{2^d}) = lambda_2^{2^d} < 1``.

``rho`` is exactly what the Chebyshev accelerator needs (the eigenvalue
interval ``[0, rho]`` of the underlying stationary iteration) and what turns
the paper's worst-case ``q = ceil(log 1/delta)`` into a measured bound --
von Luxburg et al. (arXiv:1003.1266) show the spectral regime, not the
iteration count, is what governs commute-time estimate quality.  Estimating
it costs a handful of ``G v`` mat-vecs against the already-built P2, so the
materialized chain build computes it once and caches it on the operator
(:class:`repro.core.chain.ChainOperator.rho`).  A factored operator forms no
P2: the solve driver measures its rho through the operator's own mat-vec,
and only for Chebyshev, the one method that reads it.

All ops here are eager (no tile-program bodies, no jitted closures), so the
estimate adds zero entries to the program cache and zero body retraces.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.distmatrix import DistContext, matmul_rowblock
from repro.core.tiles import is_streamable

DEFAULT_POWER_ITERS = 16


def estimate_rho(
    ctx: DistContext,
    p2,
    *,
    iters: int = DEFAULT_POWER_ITERS,
    seed: int = 0,
    prefetch_depth: int | None = None,
    n: int | None = None,
) -> float:
    """Spectral-radius estimate of ``G = I - P2`` on the 1-orthogonal subspace.

    Plain power iteration with per-step mean deflation (the Laplacian
    nullspace direction is projected out, exactly as the solver's
    ``deflate_constant`` does), normalized every step; the returned value is
    the final norm ratio ``||G v|| / ||v||``, clamped to ``[0, 0.999]``.

    ``p2`` may be a store-backed handle (an out-of-core chain's P2): the
    mat-vecs then stream, and a :class:`repro.store.CachingHandle` wrap makes
    the whole estimate cost ONE real scratch pass -- the remaining iterations
    replay decoded panels from host RAM.  It may also be a function
    ``v -> P2 v`` (a factored operator's, which forms no P2); ``n`` then
    gives the order.
    """
    if iters < 1:
        raise ValueError(f"power iters must be >= 1, got {iters}")
    if callable(p2):
        matvec = p2
    else:
        n = int(p2.shape[0])
        handle = p2
        if is_streamable(p2):
            from repro.store import CachingHandle  # deferred: optional oocore path

            handle = CachingHandle(p2)

        def matvec(x):
            return matmul_rowblock(ctx, handle, x, prefetch_depth=prefetch_depth)
    rng = np.random.default_rng(seed)
    v0 = rng.normal(size=(n, 1)).astype(np.float32)
    v0 -= v0.mean(axis=0, keepdims=True)
    v0 /= max(float(np.linalg.norm(v0)), 1e-30)
    v = ctx.put_rowblock(v0)

    # All iterations stay on device (the norm is a device scalar); the single
    # host sync is the final float() below, so the estimate costs mat-vec
    # dispatches, not per-step round-trips.
    nrm = None
    for _ in range(iters):
        gv = v - matvec(v)
        gv = gv - jnp.mean(gv.astype(jnp.float32), axis=0, keepdims=True)
        nrm = jnp.sqrt(jnp.sum(gv.astype(jnp.float32) ** 2))
        v = ctx.constrain(
            (gv / jnp.maximum(nrm, 1e-30)).astype(jnp.float32), ctx.rowblock_spec
        )
    rho = float(nrm)  # ||G v|| with ||v|| == 1
    if not np.isfinite(rho) or rho < 1e-12:
        # G annihilated the iterate along the way (e.g. a long chain on a
        # well-separated graph): the contraction is effectively zero.
        return 0.0
    return float(min(rho, 0.999))

"""RWKV6 WKV recurrence Pallas kernel (chunked linear attention).

One kernel instance owns one (batch, head) pair and walks the sequence in
chunks, carrying the (dk, dv) state in VMEM across grid steps (the TPU grid
executes the chunk axis sequentially, so the scratch state persists):

    y_t = r_t . (S + u (.) k_t v_t^T)          (bonus on the current token)
    S  <- diag(w_t) S + k_t v_t^T              (per-channel decay)

Within a chunk the pairwise decay ratios turn the recurrence into two
masked MXU matmuls (same math as models/rwkv6.wkv_chunked); across chunks
only the state flows -- O(S*C) work, O(dk*dv) carried bytes.

Layout: r/k (BH, S, dk), v (BH, S, dv), lw (BH, S, dk) log-decay <= 0.
dk = dv = 64 for all assigned configs (rwkv6-3b) -- one MXU tile.

Numerical range: the factorized intra-chunk form computes exp(cum_{t-1}) *
exp(-cum_i); pick ``chunk`` so the cumulative per-chunk log-decay stays
above ~-30 (|cum| <= 30) or precision degrades -- trained RWKV decays
(w ~ exp(-1e-2..1e-3)) allow chunks of 128-512; adversarially strong decay
needs smaller chunks (see tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import pallas_call


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)  # (C, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, dk)

    cum = jnp.cumsum(lw, axis=0)  # inclusive log decay
    cum_tm1 = cum - lw  # exclusive
    r_dec = r * jnp.exp(cum_tm1)
    k_dec = k * jnp.exp(jnp.minimum(-cum, 40.0))
    scores = jnp.dot(r_dec, k_dec.T, preferred_element_type=jnp.float32)
    c = r.shape[0]
    mask = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
            > jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))  # strictly lower
    scores = jnp.where(mask, scores, 0.0)
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)  # (C, 1)
    y = jnp.dot(scores, v, preferred_element_type=jnp.float32) + bonus * v

    # inter-chunk: y += (r_t (x) W_{t-1}) . S_prev
    y = y + jnp.dot(r_dec, s_ref[...], preferred_element_type=jnp.float32)

    # state update: S <- diag(W_C) S + sum_i diag(W_C / W_i) k_i (x) v_i
    tail = jnp.exp(cum[-1:] - cum)  # (C, dk)
    s_ref[...] = s_ref[...] * jnp.exp(cum[-1:]).T + jnp.dot(
        (tail * k).T, v, preferred_element_type=jnp.float32
    )
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv(r, k, v, lw, u, *, chunk: int = 128):
    """(BH, S, dk) x ... -> (BH, S, dv); u (BH, dk) bonus."""
    bh, s, dk = r.shape
    dv = v.shape[-1]
    from repro.kernels.tiling import fit

    c = fit(s, chunk)
    grid = (bh, s // c)
    return pallas_call(
        functools.partial(_wkv_kernel, chunk=c),
        r, k, v, lw, u[:, None, :],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, dk), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, c, dk), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, c, dv), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, c, dk), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, dk), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, dv), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dv), r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )

"""Flash-attention (online-softmax) Pallas kernel for the LM substrate.

Causal multi-head attention without materializing the (S, S) score matrix:
the grid walks (batch*heads, q_blocks, kv_blocks); each step rescales the
running (max, sum, accumulator) triple by the new block max -- the standard
online softmax -- entirely in VMEM.  KV blocks beyond the causal frontier of
a q block are skipped via ``pl.when`` (no HBM read is wasted on them because
the index map still walks them, but the FLOPs are gated; on real TPU the
comparison is cheap relative to the dots).

Layout: q, k, v are (B*H, S, D) -- heads flattened into the leading grid dim
so one kernel instance handles one (head, q-tile) strip.  D is the head dim
(128-aligned for the MXU).  fp32 softmax statistics regardless of input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import pallas_call

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, bq, bk, scale, causal, kv_steps):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            q_pos = qi * bq + jnp.arange(bq)
            k_pos = ki * bk + jnp.arange(bk)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_ref[...], l_ref[...] = m_new, l_new

    if causal:
        # Skip fully-masked KV blocks (block start beyond the q block's end).
        pl.when(ki * bk <= qi * bq + bq - 1)(attend)
    else:
        attend()

    @pl.when(ki == kv_steps - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bq: int = 256,
    bk: int = 256,
) -> jax.Array:
    """(BH, S, D) x (BH, T, D) x (BH, T, D) -> (BH, S, D) flash attention."""
    bh, s, d = q.shape
    _, t, _ = k.shape
    from repro.kernels.tiling import fit

    bq, bk = fit(s, bq), fit(t, bk)
    scale = 1.0 / (d**0.5)
    grid = (bh, s // bq, t // bk)
    return pallas_call(
        functools.partial(
            _flash_kernel, bq=bq, bk=bk, scale=scale, causal=causal, kv_steps=grid[2]
        ),
        q, k, v,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )

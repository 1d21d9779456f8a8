"""Tiled MXU matmul Pallas kernel -- the per-block GEMM of the paper.

The paper's per-block product (``numpy`` GEMM on a Spark executor, their
``O(p^{2+zeta})`` term) becomes a Pallas kernel on the TPU MXU: the grid walks
(m/bm, n/bn, k/bk) tiles, streams A(bm,bk) / B(bk,bn) HBM->VMEM via BlockSpec,
and accumulates the (bm,bn) product in an fp32 VMEM scratch across the k-steps
(the innermost, sequential grid dimension), writing the output tile once on the
last step.  MXU alignment: all tile dims are multiples of 128 by default;
fp32 accumulation regardless of storage dtype (bf16 in the chain product).

VMEM budget (defaults bm=bk=bn=256, bf16 in / fp32 acc):
    A tile 128 KiB + B tile 128 KiB + acc 256 KiB + out 128 KiB < 1 MiB,
well inside the ~16 MiB/core VMEM of v5e, leaving room for double buffering
(Pallas pipelines the next HBM->VMEM copy under the current dot).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import pallas_call


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # float32 operands at full precision (the chain GEMMs); bf16 ones are
    # exact in the MXU's one pass
    full = jnp.float32 in (a_ref.dtype, b_ref.dtype)
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], precision=lax.Precision.HIGHEST if full else None,
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "out_dtype"),
)
def block_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 256,
    bk: int = 256,
    bn: int = 256,
    out_dtype=None,
) -> jax.Array:
    """C = A @ B, (m,k)x(k,n), tiled for the MXU with fp32 accumulation."""
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dims mismatch: {a.shape} @ {b.shape}")
    out_dtype = out_dtype or a.dtype
    from repro.kernels.tiling import fit

    bm, bk, bn = fit(m, bm), fit(k, bk), fit(n, bn)
    grid = (m // bm, n // bn, k // bk)
    return pallas_call(
        functools.partial(_matmul_kernel, k_steps=grid[2]),
        a, b,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )

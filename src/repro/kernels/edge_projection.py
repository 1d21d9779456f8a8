"""Fused edge-space random projection Pallas kernel.

Computes Y[i, c] = sum_j sqrt(A[i, j]) * Q_c[i, j] -- i.e. Y = B^T W^{1/2} Q
for ``k`` Rademacher columns -- WITHOUT materializing the m = n^2 edge space.
The antisymmetric Rademacher field Q is regenerated inside the kernel from the
same splitmix32 counter hash as :mod:`repro.core.rng` (bit-identical: the hash
is plain jnp uint32 ops and runs on the VPU), so the kernel reads only the
adjacency tile and writes only the (bm, k) output tile: arithmetic intensity
k ops/byte of A, zero bytes of stored randomness.

Grid: (rows/bm, cols/bn) with the column walk innermost and sequential; the
output row-tile is accumulated across the column steps in-place (output
revisiting), matching the TPU grid execution order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core import rng as crng
from repro.kernels.dispatch import pallas_call


def _edge_proj_kernel(a_ref, o_ref, *, seed: int, k: int, bm: int, bn: int, col_steps: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # 2-D int32 index tiles: Mosaic lowers 2-D iotas (not 1-D -> 3-D
    # reshapes) and signed min/max (not unsigned); the hash widens to uint32.
    rows = i * bm + lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = j * bn + lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    s = jnp.sqrt(jnp.maximum(a_ref[...].astype(jnp.float32), 0.0))
    # One (bm, bn) Rademacher tile per projection column, regenerated --
    # identical hash to core.rng -- and row-reduced on the VPU.
    for c in range(k):
        q = crng.edge_rademacher(seed, rows, cols, c)
        o_ref[:, c : c + 1] += jnp.sum(s * q, axis=1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("seed", "k", "bm", "bn")
)
def edge_projection(
    a: jax.Array,
    *,
    seed: int,
    k: int,
    bm: int = 256,
    bn: int = 256,
) -> jax.Array:
    """Y (n, k) = B^T W^{1/2} Q with JL 1/sqrt(k) normalization."""
    m, n = a.shape
    from repro.kernels.tiling import fit

    bm, bn = fit(m, bm), fit(n, bn)
    grid = (m // bm, n // bn)
    y = pallas_call(
        functools.partial(
            _edge_proj_kernel, seed=seed, k=k, bm=bm, bn=bn, col_steps=grid[1]
        ),
        a,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
    )
    return y * (1.0 / jnp.sqrt(jnp.float32(k)))

"""Fused CAD anomaly-score Pallas kernel (paper Algorithm 4 lines 3-6).

F_i = sum_j |A1[i,j] - A2[i,j]| * |c1(i,j) - c2(i,j)|,
c_t(i,j) = V_t * (||Z_t[i]||^2 + ||Z_t[j]||^2 - 2 Z_t[i].Z_t[j]).

The n x n commute-distance matrices D_1, D_2 of the paper are NEVER
materialized: each (bm, bn) grid cell reconstructs both distance tiles from
the embedding rows (two skinny (bm,k)x(k,bn) MXU dots), applies the |dA| gate,
and row-reduces into the (bm, 1) output, accumulated across the column walk.
HBM traffic: 2 adjacency tiles + 4 skinny Z tiles in, bm floats out --
vs 2 extra n^2 matrices for the unfused path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.dispatch import pallas_call


def _dist_tile(zi, zj, vol):
    zi = zi.astype(jnp.float32)
    zj = zj.astype(jnp.float32)
    sq_i = jnp.sum(zi * zi, axis=-1)
    sq_j = jnp.sum(zj * zj, axis=-1)
    # full float32: the expansion below cancels for near pairs
    cross = jnp.dot(
        zi, zj.T, precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    )
    return vol * (sq_i[:, None] + sq_j[None, :] - 2.0 * cross)


def _cad_kernel(a1_ref, a2_ref, z1i_ref, z1j_ref, z2i_ref, z2j_ref, v_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    v1, v2 = v_ref[0, 0], v_ref[0, 1]
    d1 = _dist_tile(z1i_ref[...], z1j_ref[...], v1)
    d2 = _dist_tile(z2i_ref[...], z2j_ref[...], v2)
    de = jnp.abs(a1_ref[...].astype(jnp.float32) - a2_ref[...].astype(jnp.float32)) * jnp.abs(
        d1 - d2
    )
    o_ref[...] += jnp.sum(de, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def cad_scores_tile(
    a1: jax.Array,
    a2: jax.Array,
    z1i: jax.Array,
    z1j: jax.Array,
    z2i: jax.Array,
    z2j: jax.Array,
    vol1: jax.Array,
    vol2: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
) -> jax.Array:
    """Partial row scores (m,) for one rectangular (m, n) adjacency tile.

    ``z*i`` are the embedding rows for the tile's global rows, ``z*j`` for its
    global columns -- so a shard_map tile program can run the fused kernel on
    its local block and psum the partial sums across the column axis.
    """
    m, n = a1.shape
    k = z1i.shape[1]
    from repro.kernels.tiling import fit

    bm, bn = fit(m, bm), fit(n, bn)
    vols = jnp.stack([vol1, vol2]).astype(jnp.float32).reshape(1, 2)
    grid = (m // bm, n // bn)
    out = pallas_call(
        _cad_kernel,
        a1, a2, z1i, z1j, z2i, z2j, vols,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
    )
    return out[:, 0]


def cad_scores(
    a1: jax.Array,
    a2: jax.Array,
    z1: jax.Array,
    z2: jax.Array,
    vol1: jax.Array,
    vol2: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
) -> jax.Array:
    """Node anomaly scores F (n,) from two embeddings, fused (square case)."""
    return cad_scores_tile(
        a1, a2, z1, z1, z2, z2, vol1, vol2, bm=bm, bn=bn
    )

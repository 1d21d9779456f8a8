"""CAD anomaly scoring over a graph transition (paper Algorithm 4).

    dE      = |A_1 - A_2| (.) |D_1 - D_2|     (Hadamard)
    F_i     = sum_j dE[i, j]                  (node anomaly scores)

The commute-distance matrices D_t are *never materialized*: each device fuses
the distance evaluation ||Z_i - Z_j||^2 (two skinny GEMMs on the MXU), the
|dA| gate, and the row reduction inside its own adjacency tile.  Pairs with
dA = 0 contribute nothing -- the paper's "only compute d for changed pairs"
optimization becomes a fused multiply on dense hardware, which beats
gather/scatter on the MXU for dense graphs.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.distmatrix import F32_PRECISION, DistContext
from repro.core.embedding import CommuteConfig, Embedding, commute_time_embedding
from repro.core.tiles import is_streamable, tile_map, tile_stream
from repro.obs import phase


def _cad_scores_body(tile, b1, b2, z1, z2, v1, v2):
    def dist(z, vol):
        zi = z[tile.rows].astype(jnp.float32)
        zj = z[tile.cols].astype(jnp.float32)
        sq_i = jnp.sum(zi * zi, -1)
        sq_j = jnp.sum(zj * zj, -1)
        # The expansion cancels: its k_RP-wide contraction runs at full
        # float32 precision (a bf16 pass would swamp near distances).
        cross = jnp.dot(zi, zj.T, precision=F32_PRECISION)
        return vol * (sq_i[:, None] + sq_j[None, :] - 2.0 * cross)

    de = jnp.abs(b1.astype(jnp.float32) - b2.astype(jnp.float32)) * jnp.abs(
        dist(z1, v1) - dist(z2, v2)
    )
    return de.sum(axis=1)


def _cad_scores_kernel_body(tile, b1, b2, z1, z2, v1, v2):
    from repro.kernels import ops as kops

    return kops.cad_scores_tile(
        b1, b2, z1[tile.rows], z1[tile.cols], z2[tile.rows], z2[tile.cols], v1, v2
    )


def node_anomaly_scores(
    ctx: DistContext,
    a1: jax.Array,
    a2: jax.Array,
    e1: Embedding,
    e2: Embedding,
    *,
    use_kernel: bool = False,
    prefetch_depth: int | None = None,
) -> jax.Array:
    """F (n,) row-sharded; fused blockwise Alg. 4 lines 3-6.

    ``use_kernel=True`` swaps the tile body for the fused Pallas scorer
    (:func:`repro.kernels.cad_score.cad_scores_tile`) -- the tile program owns
    distribution, the kernel owns the on-chip schedule.

    Either adjacency may be a store-backed snapshot handle: the scorer then
    streams matching row panels of both endpoints (``prefetch_depth`` panels
    staged ahead by the panel pipeline) and the same tile body runs off-core,
    bitwise identical to the resident run.  Only the (n, k_RP) embeddings
    stay device-resident.
    """
    # Z is (n, k_RP) -- small; replicate it for tile-local access to rows+cols.
    z1 = ctx.constrain(e1.z, P(None, None))
    z2 = ctx.constrain(e2.z, P(None, None))
    streamed = is_streamable(a1) or is_streamable(a2)
    kwargs = {"prefetch_depth": prefetch_depth} if streamed else {}
    runner = tile_stream if streamed else tile_map
    with phase("score", streamed=streamed, kernel=use_kernel) as sp:
        scores = runner(
            ctx,
            _cad_scores_kernel_body if use_kernel else _cad_scores_body,
            a1,
            a2,
            z1,
            z2,
            e1.vol,
            e2.vol,
            in_specs=(
                ctx.matrix_spec,
                ctx.matrix_spec,
                P(None, None),
                P(None, None),
                P(),
                P(),
            ),
            reduce="cols",
            **kwargs,
        )
        sp.fence(scores)
    return scores


def top_anomalies(scores: jax.Array, k: int):
    vals, idx = lax.top_k(scores, k)
    return idx, vals


@dataclass
class CADResult:
    scores: jax.Array  # (n,) node anomaly scores
    top_idx: jax.Array  # (k,)
    top_val: jax.Array  # (k,)
    # Solver telemetry of the two endpoint embeddings (left, right); None
    # entries when an embedding was built before reports existed / externally.
    solve_reports: tuple = ()


def _solved(ctx, a, cfg, use_kernel) -> Embedding:
    """``a``'s embedding, its operator retired once solved: scoring reads only
    ``z`` and ``vol``, so the first snapshot's operator (P1 / P2, out-of-core
    scratch or T levels) is gone before the second's chain is built."""
    emb = commute_time_embedding(ctx, a, cfg, use_kernel=use_kernel)
    emb.op.release_scratch()
    return replace(emb, op=None)


def detect_anomalies(
    ctx: DistContext,
    a1: jax.Array,
    a2: jax.Array,
    cfg: CommuteConfig | None = None,
    *,
    top_k: int = 10,
    use_kernel: bool = False,
) -> CADResult:
    """End-to-end CADDeLaG (Algorithm 4) for one graph transition."""
    cfg = cfg or CommuteConfig()
    e1, e2 = (_solved(ctx, a, cfg, use_kernel) for a in (a1, a2))
    scores = node_anomaly_scores(
        ctx, a1, a2, e1, e2, use_kernel=use_kernel, prefetch_depth=cfg.prefetch_depth
    )
    idx, vals = top_anomalies(scores, top_k)
    return CADResult(
        scores=scores, top_idx=idx, top_val=vals,
        solve_reports=(e1.report, e2.report),
    )

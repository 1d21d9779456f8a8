"""Fused tiled distance/top-k Pallas kernel: the query-scale read path.

One kernel pass over a streamed ``Z`` row panel answers "which of this
panel's nodes are among my queries' best k so far":

* **Fused distance evaluation**: for the (q, k_RP) query block and a
  (panel_rows, k_RP) panel of the persisted sketch, the squared distances
  ``||z_q - z_j||^2`` are two skinny MXU GEMM-shaped reductions plus a
  rank-1 broadcast -- the n x n commute matrix is never materialized, and
  neither is an n-wide score row (scores live per block column chunk).
* **On-device bf16 decode**: panels may arrive as raw bf16 bit patterns
  (``uint16``, the embedding store's stored form), widened to fp32 in VMEM
  exactly like :mod:`repro.kernels.stream_gemm` -- the pipeline ships half
  the decoded bytes.
* **von Luxburg correction epilogue** (``corrected=True``): large dense
  graphs degenerate raw commute times to ``vol * (1/deg_i + 1/deg_j)``
  (arXiv 1003.1266), so the corrected scorer rescales to ``C / vol`` and
  subtracts the degree term -- applied per score block before selection, so
  raw and corrected queries are the same single pass.
* **Running per-query top-k merge**: the kernel carries the best-(k) values
  AND global node ids in VMEM scratch across the grid walk, merging each
  block's candidates by an unrolled masked-extremum selection (top-k is
  static and small; ``argmax``-free, so the body lowers on TPU Pallas and
  interpret mode alike).  The running state is threaded *through* the kernel
  as operands, so a whole-store query is: seed state, one kernel call per
  streamed panel, read back (q, topk) -- device residency stays two panels +
  the O(q k) state, and every panel uses one compiled program.

Interpret mode runs the same body off-TPU, as everywhere in
:mod:`repro.kernels`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import pallas_call
from repro.kernels.stream_gemm import _dec


def _select_topk(vals, idx, *, topk: int, largest: bool):
    """(q, topk) best values/ids of a (q, m) candidate block, order preserved.

    Unrolled masked-extremum selection (topk is static and small): each round
    takes the per-row best remaining candidate, breaking ties toward the
    lower *position* -- so earlier candidates (the running state, then lower
    node ids) win ties, matching ``lax.top_k``'s stability.  Built from
    max/min/where/iota only: no argmax, no gather, TPU-Pallas lowerable.
    """
    q, m = vals.shape
    work = vals if largest else -vals
    pos = lax.broadcasted_iota(jnp.int32, (q, m), 1)
    out_v, out_i = [], []
    for _ in range(topk):
        best = jnp.max(work, axis=-1, keepdims=True)
        first = jnp.min(
            jnp.where(work == best, pos, jnp.int32(m)), axis=-1, keepdims=True
        )
        sel = pos == first
        out_v.append(jnp.sum(jnp.where(sel, vals, 0.0), axis=-1))
        out_i.append(jnp.sum(jnp.where(sel, idx, 0), axis=-1))
        work = jnp.where(sel, -jnp.inf, work)
    return jnp.stack(out_v, axis=-1), jnp.stack(out_i, axis=-1)


def _panel_topk_kernel(
    zq_ref, zp_ref, idq_ref, idp_ref, vol_ref, row0_ref, ex_ref,
    rv_ref, ri_ref, ov_ref, oi_ref, accv_ref, acci_ref,
    *, k_steps, bj, topk, enc, corrected, largest,
):
    kk = pl.program_id(0)

    @pl.when(kk == 0)
    def _seed():
        # The running state enters as operands: a whole-store query threads
        # (vals, ids) through one kernel call per panel.
        accv_ref[...] = rv_ref[...]
        acci_ref[...] = ri_ref[...]

    zq = zq_ref[...].astype(jnp.float32)
    zb = _dec(zp_ref[...], enc)
    sq_q = jnp.sum(zq * zq, axis=-1, keepdims=True)
    sq_j = jnp.sum(zb * zb, axis=-1)[None, :]
    # Full float32 contraction: the expansion cancels for near neighbours,
    # where one bf16 pass leaves errors of ~1e-2 of the distance.
    dist2 = sq_q + sq_j - 2.0 * jnp.dot(
        zq, zb.T, precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    )
    dist2 = jnp.maximum(dist2, 0.0)  # clamp the rank-1 cancellation noise
    if corrected:
        # C_amp = C/vol - 1/deg_i - 1/deg_j (and C/vol is exactly dist2):
        # the degenerate dense-graph limit subtracts out, structure remains.
        scores = dist2 - idq_ref[...] - idp_ref[...]
    else:
        scores = vol_ref[0, 0] * dist2
    q = scores.shape[0]
    cidx = (
        row0_ref[0, 0]
        + kk * bj
        + lax.broadcasted_iota(jnp.int32, (q, bj), 1)
    )
    worst = jnp.float32(-jnp.inf if largest else jnp.inf)
    scores = jnp.where(cidx == ex_ref[...], worst, scores)  # self-exclusion
    vals = jnp.concatenate([accv_ref[...], scores], axis=1)
    idx = jnp.concatenate([acci_ref[...], cidx], axis=1)
    mv, mi = _select_topk(vals, idx, topk=topk, largest=largest)
    accv_ref[...] = mv
    acci_ref[...] = mi

    @pl.when(kk == k_steps - 1)
    def _flush():
        ov_ref[...] = accv_ref[...]
        oi_ref[...] = acci_ref[...]


def topk_init(nq: int, topk: int, *, largest: bool) -> tuple[jax.Array, jax.Array]:
    """The seed running state: worst-possible values, id -1 (empty slots)."""
    worst = -jnp.inf if largest else jnp.inf
    return (
        jnp.full((nq, topk), worst, jnp.float32),
        jnp.full((nq, topk), -1, jnp.int32),
    )


@functools.partial(
    jax.jit, static_argnames=("topk", "corrected", "largest")
)
def panel_topk_update(
    run_vals: jax.Array,
    run_idx: jax.Array,
    zq: jax.Array,
    z_panel: jax.Array,
    inv_deg_q: jax.Array,
    inv_deg_panel: jax.Array,
    vol: jax.Array,
    row0,
    exclude: jax.Array,
    *,
    topk: int,
    corrected: bool = False,
    largest: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Merge one Z row panel into the running per-query top-k.

    ``run_vals`` / ``run_idx`` (q, topk) are the state from
    :func:`topk_init` or a previous call; ``zq`` (q, k) the resident query
    block; ``z_panel`` (ph, k) the streamed panel -- fp32 values or raw bf16
    bit patterns (``uint16``, decoded on-device); ``inv_deg_q`` (q, 1) /
    ``inv_deg_panel`` (1, ph) the correction terms (ignored unless
    ``corrected``); ``vol`` the scalar graph volume (ignored when
    ``corrected`` -- the amplified score is volume-free); ``row0`` the
    panel's global row origin (an *operand*, so every panel reuses one
    compiled program); ``exclude`` (q, 1) int32 global ids masked to the
    worst score per query (-1 for none) -- nearest-neighbor queries drop
    their own node in-kernel.

    Returns the merged (vals, ids); ids are global node indices, -1 in slots
    not yet filled (topk > rows seen so far).
    """
    q, kdim = zq.shape
    ph, k2 = z_panel.shape
    if kdim != k2:
        raise ValueError(f"query dim mismatch: {zq.shape} vs panel {z_panel.shape}")
    if run_vals.shape != (q, topk) or run_idx.shape != (q, topk):
        raise ValueError(
            f"running state must be {(q, topk)}, got "
            f"{run_vals.shape}/{run_idx.shape}"
        )
    if inv_deg_q.shape != (q, 1) or inv_deg_panel.shape != (1, ph):
        raise ValueError(
            f"inv_deg blocks must be {(q, 1)}/{(1, ph)}, got "
            f"{inv_deg_q.shape}/{inv_deg_panel.shape}"
        )
    if exclude.shape != (q, 1):
        raise ValueError(f"exclude must be {(q, 1)}, got {exclude.shape}")
    from repro.kernels.tiling import fit

    bj = fit(ph, 256)
    grid = (ph // bj,)
    vol2 = jnp.asarray(vol, jnp.float32).reshape(1, 1)
    row02 = jnp.asarray(row0, jnp.int32).reshape(1, 1)
    kernel = functools.partial(
        _panel_topk_kernel,
        k_steps=grid[0], bj=bj, topk=topk,
        enc=z_panel.dtype == jnp.uint16, corrected=corrected, largest=largest,
    )
    return pallas_call(
        kernel,
        zq, z_panel, inv_deg_q, inv_deg_panel, vol2, row02, exclude,
        run_vals, run_idx,
        grid=grid,
        in_specs=[
            pl.BlockSpec((q, kdim), lambda kk: (0, 0)),
            pl.BlockSpec((bj, kdim), lambda kk: (kk, 0)),
            pl.BlockSpec((q, 1), lambda kk: (0, 0)),
            pl.BlockSpec((1, bj), lambda kk: (0, kk)),
            pl.BlockSpec((1, 1), lambda kk: (0, 0)),
            pl.BlockSpec((1, 1), lambda kk: (0, 0)),
            pl.BlockSpec((q, 1), lambda kk: (0, 0)),
            pl.BlockSpec((q, topk), lambda kk: (0, 0)),
            pl.BlockSpec((q, topk), lambda kk: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((q, topk), lambda kk: (0, 0)),
            pl.BlockSpec((q, topk), lambda kk: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((q, topk), jnp.float32),
            jax.ShapeDtypeStruct((q, topk), jnp.int32),
        ),
        scratch_shapes=[
            pltpu.VMEM((q, topk), jnp.float32),
            pltpu.VMEM((q, topk), jnp.int32),
        ],
    )

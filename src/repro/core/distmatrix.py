"""Distributed dense block matrices on a TPU mesh.

This is the JAX/TPU re-think of the paper's Spark RDD block matrix
(``((row_id, col_id), M)``): an ``n x n`` matrix is one ``jax.Array`` whose
NamedSharding tiles it into a beta x beta grid over the device mesh -- rows
over ``row_axes`` ("data", and "pod" when multi-pod), columns over
``col_axes`` ("model").

Three matmul *schedules* mirror the paper's design space:

- ``xla``     -- leave the collective schedule to XLA SPMD.  This is the
                 analogue of Spark's built-in ``BlockMatrix.multiply``: simple,
                 but it replicates a full operand panel per device
                 (all-gather), the moral equivalent of the shuffle.
- ``summa``   -- explicit one-panel-per-device SUMMA under shard_map:
                 all-gather A along the column axis (row panel) and B along the
                 row axis (column panel), one local GEMM.  Predictable, but
                 O(n^2/R + n^2/C) resident bytes per chip.
- ``cannon``  -- systolic Cannon rings under shard_map: pre-skew with
                 collective_permute, then R steps of (local GEMM + neighbor
                 shift).  O(n^2/P) resident bytes per chip and only
                 nearest-neighbor ICI traffic -- this is the TPU-native
                 "shuffle-free" streaming the paper builds on Lustre.  The
                 next-step permute is issued *before* the local GEMM so XLA's
                 latency-hiding scheduler overlaps communication with compute
                 (double buffering).

All schedules accumulate in fp32 (MXU-faithful) regardless of storage dtype.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.tiles import (
    cached_program,
    is_streamable,
    pcast_varying,
    program_cache_stats,
    shard_map,
    tile_map,
    tile_stream,
)

SCHEDULES = ("xla", "summa", "cannon")


def _axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) if axes else 1


@dataclass(frozen=True)
class DistContext:
    """Mesh + axis-naming context for distributed block matrices."""

    mesh: Mesh
    row_axes: tuple[str, ...] = ("data",)
    col_axes: tuple[str, ...] = ("model",)

    @property
    def n_row_shards(self) -> int:
        return _axes_size(self.mesh, self.row_axes)

    @property
    def n_col_shards(self) -> int:
        return _axes_size(self.mesh, self.col_axes)

    @property
    def matrix_spec(self) -> P:
        return P(self.row_axes, self.col_axes)

    @property
    def rowblock_spec(self) -> P:
        """(n, k) tall-skinny operands: rows sharded, columns replicated."""
        return P(self.row_axes, None)

    @property
    def vector_spec(self) -> P:
        return P(self.row_axes)

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def constrain(self, x: jax.Array, spec: P) -> jax.Array:
        sharding = self.sharding(spec)
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            # Eagerly, a constraint is a dispatched copy; an array already
            # laid out so is returned as it is.
            if x.sharding.is_equivalent_to(sharding, x.ndim):
                return x
        return lax.with_sharding_constraint(x, sharding)

    def put_matrix(self, x) -> jax.Array:
        return jax.device_put(jnp.asarray(x), self.sharding(self.matrix_spec))

    def put_rowblock(self, x) -> jax.Array:
        return jax.device_put(jnp.asarray(x), self.sharding(self.rowblock_spec))


def make_context(
    mesh: Mesh,
    row_axes: Sequence[str] = ("data",),
    col_axes: Sequence[str] = ("model",),
) -> DistContext:
    return DistContext(mesh=mesh, row_axes=tuple(row_axes), col_axes=tuple(col_axes))


def trivial_context() -> DistContext:
    """Single-device 1x1 mesh context (tests / laptop runs)."""
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return DistContext(mesh=Mesh(dev, ("data", "model")))


# ---------------------------------------------------------------------------
# matmul schedules
# ---------------------------------------------------------------------------


# Float32 matmuls of the chain, the solve and the distance expansions run
# at full float32 precision; XLA's TPU default makes one bf16 pass over
# float32 operands.  On a v5e (n=2048 climate, d=6) that put the scores
# 7.6e-2 of the top score off the CPU's; full precision on the chain GEMMs
# alone left 3.0e-2, and on the solve mat-vecs as well 8.6e-4.  The
# mat-vecs are HBM-bound: only the chain GEMMs pay for the extra MXU passes.
F32_PRECISION = lax.Precision.HIGHEST


def _local_dot(a: jax.Array, b: jax.Array, use_kernel: bool) -> jax.Array:
    if use_kernel:
        from repro.kernels import ops as kops

        return kops.block_matmul(a, b, out_dtype=jnp.float32)
    return jnp.dot(a, b, precision=F32_PRECISION, preferred_element_type=jnp.float32)


def _matmul_xla(ctx: DistContext, a, b, out_dtype):
    out = jnp.dot(a, b, precision=F32_PRECISION, preferred_element_type=jnp.float32)
    return ctx.constrain(out.astype(out_dtype), ctx.matrix_spec)


def _matmul_summa(ctx: DistContext, a, b, out_dtype, use_kernel=False):
    def build():
        row_ax, col_ax = ctx.row_axes, ctx.col_axes

        def local(a_blk, b_blk):
            program_cache_stats().note_trace()
            # Row panel of A (gather along column axis), column panel of B.
            a_panel = lax.all_gather(a_blk, col_ax, axis=1, tiled=True)
            b_panel = lax.all_gather(b_blk, row_ax, axis=0, tiled=True)
            return _local_dot(a_panel, b_panel, use_kernel).astype(out_dtype)

        return jax.jit(
            shard_map(
                local,
                mesh=ctx.mesh,
                in_specs=(ctx.matrix_spec, ctx.matrix_spec),
                out_specs=ctx.matrix_spec,
            )
        )

    key = ("summa", ctx, np.dtype(out_dtype).name, use_kernel)
    return cached_program(key, build)(a, b)


def _cannon_perms(R: int, C: int):
    """Static permutation tables over the flattened (rows..., cols...) axes."""
    skew_a = [(r * C + c, r * C + ((c - r) % C)) for r in range(R) for c in range(C)]
    skew_b = [(r * C + c, ((r - c) % R) * C + c) for r in range(R) for c in range(C)]
    shift_a = [(r * C + c, r * C + ((c - 1) % C)) for r in range(R) for c in range(C)]
    shift_b = [(r * C + c, ((r - 1) % R) * C + c) for r in range(R) for c in range(C)]
    return skew_a, skew_b, shift_a, shift_b


def _matmul_cannon(ctx: DistContext, a, b, out_dtype, use_kernel=False):
    R, C = ctx.n_row_shards, ctx.n_col_shards
    if R != C:
        raise ValueError(
            f"cannon schedule needs a square device grid, got {R}x{C}; "
            "use schedule='summa' (or make the pod axis an outer sequence axis)"
        )
    def build():
        axes = ctx.row_axes + ctx.col_axes
        skew_a, skew_b, shift_a, shift_b = _cannon_perms(R, C)

        def local(a_blk, b_blk):
            program_cache_stats().note_trace()
            a_blk = lax.ppermute(a_blk, axes, skew_a)
            b_blk = lax.ppermute(b_blk, axes, skew_b)
            # pcast-to-varying: the accumulator must carry the same
            # (data, model)-varying type as the per-step GEMM output.
            acc0 = pcast_varying(jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32), axes)

            def body(_, carry):
                acc, a_cur, b_cur = carry
                # Issue next-step permutes first: independent of the GEMM below, so
                # the latency-hiding scheduler overlaps ICI transfer with the MXU.
                a_nxt = lax.ppermute(a_cur, axes, shift_a)
                b_nxt = lax.ppermute(b_cur, axes, shift_b)
                acc = acc + _local_dot(a_cur, b_cur, use_kernel)
                return acc, a_nxt, b_nxt

            acc, _, _ = lax.fori_loop(0, R, body, (acc0, a_blk, b_blk))
            return acc.astype(out_dtype)

        return jax.jit(
            shard_map(
                local,
                mesh=ctx.mesh,
                in_specs=(ctx.matrix_spec, ctx.matrix_spec),
                out_specs=ctx.matrix_spec,
            )
        )

    key = ("cannon", ctx, np.dtype(out_dtype).name, use_kernel)
    return cached_program(key, build)(a, b)


def matmul(
    ctx: DistContext,
    a: jax.Array,
    b: jax.Array,
    *,
    schedule: str = "xla",
    out_dtype=None,
    use_kernel: bool = False,
) -> jax.Array:
    """C = A @ B over the mesh with the chosen collective schedule."""
    out_dtype = out_dtype or a.dtype
    if schedule == "xla":
        return _matmul_xla(ctx, a, b, out_dtype)
    if schedule == "summa":
        return _matmul_summa(ctx, a, b, out_dtype, use_kernel)
    if schedule == "cannon":
        return _matmul_cannon(ctx, a, b, out_dtype, use_kernel)
    raise ValueError(f"unknown schedule {schedule!r}; want one of {SCHEDULES}")


def _rowblock_body(tile, blk, x):
    return jnp.dot(
        blk.astype(jnp.float32),
        x[tile.cols].astype(jnp.float32),
        precision=F32_PRECISION,
        preferred_element_type=jnp.float32,
    )


def matmul_rowblock(
    ctx: DistContext,
    m: jax.Array,
    x: jax.Array,
    *,
    prefetch_depth: int | None = None,
) -> jax.Array:
    """(n x n) @ (n x k) with k << n: the Richardson mat-vec workhorse.

    m is matrix-sharded; x is row-sharded and tiny, so XLA's reduce-scatter /
    all-gather pair on the k-columns is cheap.  Always accumulates fp32.

    ``m`` may also be a store-backed snapshot handle (an out-of-core chain's
    P1 / P2): the mat-vec then streams row panels of m against the small
    replicated x (``prefetch_depth`` panels staged ahead by the panel
    pipeline), so the operator matrix is never device-resident -- the solver
    inherits the panel residency bound of the chain build.
    """
    if is_streamable(m):
        xr = ctx.constrain(x, P(None, None))
        out = tile_stream(
            ctx,
            _rowblock_body,
            m,
            xr,
            in_specs=(ctx.matrix_spec, P(None, None)),
            reduce="cols",
            out_spec=ctx.rowblock_spec,
            prefetch_depth=prefetch_depth,
        )
        return ctx.constrain(out.astype(x.dtype), ctx.rowblock_spec)
    out = jnp.dot(
        m, x.astype(jnp.float32), precision=F32_PRECISION, preferred_element_type=jnp.float32
    )
    return ctx.constrain(out.astype(x.dtype), ctx.rowblock_spec)


# ---------------------------------------------------------------------------
# blockwise constructors -- the "never load the graph" builders
# ---------------------------------------------------------------------------


def build_from_nodes(
    ctx: DistContext,
    feats: jax.Array,
    kernel_fn: Callable[[jax.Array, jax.Array], jax.Array],
    *,
    dtype=jnp.float32,
    zero_diagonal: bool = True,
) -> jax.Array:
    """Materialize A[i, j] = kernel_fn(feats[i], feats[j]) directly *sharded*.

    Each device computes only its local (n/R, n/C) tile from the (small)
    replicated node-feature table -- the n x n graph never exists centrally.
    This is how the climate graph (259200 nodes, 6.7e10 edges) is built.
    """
    n = feats.shape[0]
    R, C = ctx.n_row_shards, ctx.n_col_shards
    if n % R or n % C:
        raise ValueError(f"n={n} must divide the {R}x{C} shard grid")

    def tile_fn(tile, f):
        blk = kernel_fn(f[tile.rows], f[tile.cols]).astype(dtype)
        if zero_diagonal:
            blk = jnp.where(tile.diag_mask(), jnp.zeros((), dtype), blk)
        return blk

    return tile_map(ctx, tile_fn, feats, grid=(n, n), in_specs=(P(None, None),))


def blockwise_unary(
    ctx: DistContext,
    fn: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
    x: jax.Array,
    *,
    out_dtype=None,
    prefetch_depth: int | None = None,
) -> jax.Array:
    """Apply ``fn(block, global_rows, global_cols) -> block`` tile-locally.

    ``x`` may be a store-backed snapshot handle (see :mod:`repro.store`): the
    transform then *streams* -- each row panel is fetched from host/disk
    (``prefetch_depth`` panels staged ahead), transformed, and written into
    the sharded output, so the raw input is never device-resident (this is
    how the chain build materializes S and L without ever loading A).
    """
    out_dtype = out_dtype or x.dtype
    body = lambda tile, blk: fn(blk, tile.rows, tile.cols)
    if is_streamable(x):
        return tile_stream(ctx, body, x, out_dtype=out_dtype, prefetch_depth=prefetch_depth)
    return tile_map(ctx, body, x, out_dtype=out_dtype)


def _add_scaled_identity_body(tile, blk, s):
    return blk + s * tile.diag_mask().astype(blk.dtype)


def add_scaled_identity(ctx: DistContext, x: jax.Array, scale=1.0) -> jax.Array:
    """x + scale * I without materializing I (used for P <- P @ T + P etc.).

    The scale rides along as a scalar operand (not a closure constant) so the
    tile program is compiled once per mesh/geometry, not once per call.
    Resident operands only: every caller applies this to an already-resident
    chain matrix (the out-of-core chain has its own panel program).
    """
    s = jnp.asarray(scale, x.dtype)
    return tile_map(ctx, _add_scaled_identity_body, x, s, in_specs=(ctx.matrix_spec, P()))

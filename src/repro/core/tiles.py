"""Unified tile-program layer: every blockwise computation in the core.

The paper's Spark job graph is a pile of near-identical map stages: "for the
(r, c) block of the n x n matrix, recover the global row/column ids, compute
something tile-local, optionally reduce across the block row".  The JAX port
accumulated five hand-rolled copies of that shard_map pattern; this module
owns it once.

``tile_map(ctx, fn, *operands)`` runs ``fn(tile, *local_blocks)`` on every
device with a :class:`Tile` describing the device's (rows, cols) window of the
global grid, and stitches the local outputs back into one sharded array.
An optional ``reduce="cols"`` psums the per-tile result across the column
axis (the Map+ReduceByKey of the paper).  Tile bodies are ordinary traced JAX,
so they can drop into a Pallas kernel for the inner loop (see
``node_anomaly_scores``) -- the tile program handles distribution, the kernel
handles the single-chip schedule.

``tile_stream(ctx, fn, *operands)`` is the out-of-core twin: operands may be
store-backed snapshot handles (see :mod:`repro.store`) instead of resident
arrays, and the same tile bodies run over row panels fetched from host/disk
with double-buffered host->device prefetch.  Device residency is bounded by
two panels per streamed operand, not by n^2 -- the row-parallel tile programs
(degrees, edge projection, CAD scoring, blockwise builds) are bitwise
identical to their resident runs because each output row sees exactly the
same per-device reduction extents either way.

This module also owns the thin ``shard_map`` / ``pcast_varying`` helpers
the rest of the core builds its manual-sharding programs with.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY as _OBS_REGISTRY
from repro.obs.metrics import MetricsRegistry

# ---------------------------------------------------------------------------
# manual-sharding API
# ---------------------------------------------------------------------------


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None, check=True):
    """``jax.shard_map`` with varying-type checking on by default.

    ``axis_names`` restricts manual sharding to those mesh axes;
    ``check=False`` turns varying-type checking off.
    """
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check, **kw
    )


def pcast_varying(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """Mark ``x`` as device-varying over ``axes`` (loop-carry seeding)."""
    return lax.pcast(x, tuple(axes), to="varying")


# ---------------------------------------------------------------------------
# tile-program compile cache
# ---------------------------------------------------------------------------
#
# jax.jit keys its C++ dispatch cache on the *callable's identity*, and both
# executors historically wrapped a fresh closure per invocation -- so a
# T-snapshot sequence run retraced (and recompiled) the same ~5 tile programs
# T times.  The cache below keys the jitted program on everything the closure
# actually depends on: the body function object, the mesh/axes context, the
# static panel geometry, the partition specs, the reduction and the output
# dtype.  Bodies that want cache hits must therefore be *module-level
# functions taking all data as operands* (a per-call lambda, or a closure over
# arrays, gets a fresh identity and safely misses).


class ProgramCacheStats:
    """Process-wide compile-cache accounting (see :func:`program_cache_stats`).

    ``traces`` counts Python executions of tile-program bodies -- a body runs
    in Python only while jax traces it, so a steady-state snapshot push that
    adds zero traces provably reused every compiled tile program.

    A live view over ``program_cache.*`` counters in a
    :class:`repro.obs.metrics.MetricsRegistry` (the process registry by
    default, so run reports read the same numbers).  Reads are properties,
    mutation goes through the atomic ``note_*`` methods, and
    :func:`reset_program_cache_stats` zeroes the counters *in place* -- held
    references stay live across resets.
    """

    __slots__ = ("_reg",)
    _PREFIX = "program_cache."

    def __init__(self, registry: MetricsRegistry | None = None):
        self._reg = registry if registry is not None else MetricsRegistry()

    @property
    def hits(self) -> int:  # cache hits: program reused, no retrace
        return int(self._reg.value("program_cache.hits"))

    @property
    def misses(self) -> int:  # cache misses: a new program was built (and traced)
        return int(self._reg.value("program_cache.misses"))

    @property
    def traces(self) -> int:  # Python trace executions of tile-program bodies
        return int(self._reg.value("program_cache.traces"))

    def note_hit(self) -> None:
        self._reg.inc("program_cache.hits")

    def note_miss(self) -> None:
        self._reg.inc("program_cache.misses")

    def note_trace(self) -> None:
        self._reg.inc("program_cache.traces")

    def __repr__(self) -> str:
        return (
            f"ProgramCacheStats(hits={self.hits}, misses={self.misses}, "
            f"traces={self.traces})"
        )


_PROGRAM_STATS = ProgramCacheStats(registry=_OBS_REGISTRY)
_PROGRAM_CACHE: OrderedDict = OrderedDict()
_PROGRAM_CACHE_MAX = 512  # per-call lambdas miss forever; bound their footprint


def program_cache_stats() -> ProgramCacheStats:
    """Counters since process start / last :func:`reset_program_cache_stats`."""
    return _PROGRAM_STATS


def reset_program_cache_stats() -> ProgramCacheStats:
    """Zero the counters in place (held references observe the reset)."""
    _PROGRAM_STATS._reg.reset(ProgramCacheStats._PREFIX)
    return _PROGRAM_STATS


def clear_program_cache() -> None:
    _PROGRAM_CACHE.clear()


def cached_program(key: tuple, build: Callable[[], Callable]) -> Callable:
    """The jitted program for ``key``, building (and tracing) it on first use.

    The caller owns the key contract: it must cover every value the built
    closure captures.  Keys holding per-call function objects pin them in the
    cache; eviction is LRU once the cache exceeds its bound, so a long run's
    churn of never-hit per-call lambdas (e.g. ``build_from_nodes`` closures,
    one per generated snapshot) can't evict the hot, constantly-hitting
    chain/scorer programs.
    """
    prog = _PROGRAM_CACHE.get(key)
    if prog is None:
        while len(_PROGRAM_CACHE) >= _PROGRAM_CACHE_MAX:
            _PROGRAM_CACHE.popitem(last=False)  # least recently used
        _PROGRAM_STATS.note_miss()
        prog = build()
        _PROGRAM_CACHE[key] = prog
    else:
        _PROGRAM_STATS.note_hit()
        _PROGRAM_CACHE.move_to_end(key)
    return prog


def _dtype_key(dt) -> str | None:
    return None if dt is None else np.dtype(dt).name


def sharded_zeros(shape: tuple[int, ...], dtype, sharding) -> jax.Array:
    """A zero buffer born with ``sharding`` (jitted with out_shardings).

    Eager ``jnp.zeros`` materializes the whole array on the default device
    before any reshard -- at out-of-core scale that single-device allocation
    OOMs exactly the buffers (streaming assembly targets, GEMM accumulators)
    whose residency the executors are bounding.  The jitted program allocates
    each shard on its own device; programs are cached per (shape, dtype,
    sharding).
    """
    return cached_program(
        ("zeros", tuple(shape), _dtype_key(dtype), sharding),
        lambda: jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding),
    )()


# ---------------------------------------------------------------------------
# the tile-program primitive
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Tile:
    """One device's window of the global block grid, visible to tile bodies."""

    rows: jax.Array  # (pr,) global row ids of this tile
    cols: jax.Array  # (pc,) global col ids
    row_index: jax.Array  # scalar shard index along the row axes
    col_index: jax.Array  # scalar shard index along the col axes
    block_shape: tuple[int, int]  # static (pr, pc)
    mesh_axes: tuple[str, ...]  # all manual axes, for loop-carry casts

    def varying(self, x: jax.Array) -> jax.Array:
        """Seed a loop carry with the tile-varying type."""
        return pcast_varying(x, self.mesh_axes)

    def diag_mask(self) -> jax.Array:
        """(pr, pc) bool mask of global-diagonal entries in this tile."""
        return self.rows[:, None] == self.cols[None, :]


def _tile_local(
    ctx,
    fn: Callable[..., jax.Array],
    pr: int,
    pc: int,
    reduce_axes,
    out_dtype,
    *,
    with_origin: bool = False,
):
    """Shared per-device body for :func:`tile_map` and :func:`tile_stream`.

    With ``with_origin=True`` the wrapped function takes a leading (traced)
    global row offset, so one compiled program serves every streamed panel.
    """
    mesh_axes = tuple(ctx.row_axes) + tuple(ctx.col_axes)

    def local(*args):
        _PROGRAM_STATS.note_trace()  # body runs in Python only while tracing
        if with_origin:
            origin, *blocks = args
        else:
            origin, blocks = jnp.int32(0), args
        # flattened shard index over the row / col axes, row-major
        r = lax.axis_index(ctx.row_axes)
        c = lax.axis_index(ctx.col_axes)
        tile = Tile(
            rows=origin + r * pr + jnp.arange(pr),
            cols=c * pc + jnp.arange(pc),
            row_index=origin // pr + r,
            col_index=c,
            block_shape=(pr, pc),
            mesh_axes=mesh_axes,
        )
        out = fn(tile, *blocks)
        if reduce_axes is not None:
            out = lax.psum(out, reduce_axes)
        if out_dtype is not None:
            out = out.astype(out_dtype)
        return out

    return local


def tile_map(
    ctx,
    fn: Callable[..., jax.Array],
    *operands: jax.Array,
    grid: tuple[int, int] | None = None,
    in_specs: Sequence[P] | None = None,
    out_spec: P | None = None,
    reduce: str | None = None,
    out_dtype=None,
) -> jax.Array:
    """Run ``fn(tile, *local_blocks)`` over the ctx mesh, one tile per device.

    Args:
      ctx: ``DistContext`` (mesh + row/col axis names).
      fn: tile body; receives a :class:`Tile` plus each operand's local block
        (operands with a replicated spec arrive whole).  Returns the local
        output block.  The body is ordinary traced JAX and may call Pallas
        kernels on its block.
      operands: global arrays.
      grid: global (n_rows, n_cols) of the logical block grid.  Defaults to
        the shape of the first matrix-sharded operand.
      in_specs: one PartitionSpec per operand.  Defaults to
        ``ctx.matrix_spec`` for every operand (pass ``P(None, None)`` / ``P()``
        explicitly for replicated tables and scalars).
      out_spec: sharding of the stitched output.  Defaults to
        ``ctx.matrix_spec``; with ``reduce="cols"`` defaults to
        ``ctx.vector_spec`` (pass ``P(row_axes, None)`` for (pr, k) tiles).
      reduce: ``None`` or ``"cols"``/``"rows"`` -- psum the tile output over
        that mesh axis before stitching (the blockwise Map+ReduceByKey).
      out_dtype: optional cast of the tile output.
    """
    if in_specs is None:
        in_specs = tuple(ctx.matrix_spec for _ in operands)
    in_specs = tuple(in_specs)
    if len(in_specs) != len(operands):
        raise ValueError(f"{len(operands)} operands but {len(in_specs)} in_specs")

    if grid is None:
        for op, spec in zip(operands, in_specs):
            if spec == ctx.matrix_spec:
                grid = (op.shape[0], op.shape[1])
                break
        if grid is None:
            raise ValueError("grid= is required when no operand is matrix-sharded")
    n0, n1 = grid
    R, C = ctx.n_row_shards, ctx.n_col_shards
    if n0 % R or n1 % C:
        raise ValueError(f"grid {grid} must divide the {R}x{C} shard grid")
    pr, pc = n0 // R, n1 // C

    if reduce not in (None, "cols", "rows"):
        raise ValueError(f"reduce must be None, 'cols' or 'rows', got {reduce!r}")
    reduce_axes = {"cols": ctx.col_axes, "rows": ctx.row_axes, None: None}[reduce]

    if out_spec is None:
        if reduce == "cols":
            out_spec = ctx.vector_spec
        elif reduce == "rows":
            out_spec = P(ctx.col_axes)
        else:
            out_spec = ctx.matrix_spec

    # jit for numeric parity with tile_stream: both executors compile their
    # tile program through the same pipeline, so a streamed run is bitwise
    # identical to the resident run (XLA fuses jit and eager-dispatch
    # programs slightly differently).  The program is cached on everything the
    # closure depends on, so repeated calls with the same body reuse one
    # compiled program instead of retracing per call.
    key = ("tile_map", fn, ctx, pr, pc, in_specs, out_spec, reduce, _dtype_key(out_dtype))
    mapped = cached_program(
        key,
        lambda: jax.jit(
            shard_map(
                _tile_local(ctx, fn, pr, pc, reduce_axes, out_dtype),
                mesh=ctx.mesh,
                in_specs=in_specs,
                out_specs=out_spec,
            )
        ),
    )
    return mapped(*operands)


# ---------------------------------------------------------------------------
# the streaming tile executor (out-of-core operands)
# ---------------------------------------------------------------------------


def is_streamable(x) -> bool:
    """True for store-backed snapshot handles (duck-typed, no store import).

    The protocol: ``shape`` (n0, n1), ``dtype``, ``panel_rows`` (preferred
    streaming height) and ``read_panel(row0, height) -> host array``.
    :class:`repro.store.SnapshotHandle` satisfies it; so can any user object.
    """
    return (
        not isinstance(x, (jax.Array, np.ndarray))
        and hasattr(x, "read_panel")
        and hasattr(x, "panel_rows")
        and hasattr(x, "shape")
    )


class StreamStats:
    """Process-wide accounting of the streaming executors (see stream_stats()).

    ``bytes_read`` counts what the backing tier (disk / store RAM) actually
    served, *before* codec decode -- the number that tracks real disk traffic
    across PRs.  ``bytes_decoded`` is the post-codec host bytes the prefetch
    thread produced from them; with ``codec='raw'`` the two move together
    (modulo .npy headers), with ``bf16``/``zstd`` the gap is the bandwidth
    the codec saved.  Host-RAM replays (solver iteration batching) add
    ``panels``/``bytes_h2d`` but zero ``bytes_read`` and zero
    ``bytes_decoded`` -- nothing was served or decoded for them.

    ``bytes_h2d_saved`` is the stored-width vs decoded-width transfer gap of
    the kernel path: panels shipped in their *stored* form (bf16 bit patterns
    decoded on-device by the stream-GEMM kernel) add the difference between
    what a host-decoded fp32 transfer would have cost and what actually
    crossed H2D.  Zero on the host-decode path -- the counter is exactly the
    bandwidth the on-device decode won.

    A live view over ``stream.*`` counters in a
    :class:`repro.obs.metrics.MetricsRegistry`.  The process-wide instance
    behind :func:`stream_stats` is backed by the process registry (so run
    reports read the very same counters); a bare ``StreamStats()`` gets its
    own private registry for isolated accounting (tests pass one straight to
    a :class:`~repro.store.PanelPipeline`).  All mutation goes through the
    atomic :meth:`add`, and :func:`reset_stream_stats` zeroes the counters
    *in place* -- a prefetch thread mid-``add`` can no longer race a reset
    into lost updates, and references held across a reset stay live.
    """

    __slots__ = ("_reg",)
    _PREFIX = "stream."
    FIELDS = (
        "panels",  # row panels fetched host -> device
        "bytes_h2d",  # bytes device_put by the executor
        "bytes_h2d_saved",  # decoded-width minus stored-width H2D (kernel path)
        "bytes_read",  # pre-decode bytes served by the backing store
        "bytes_decoded",  # post-decode host bytes produced by prefetch
        "calls",  # tile_stream invocations
    )

    def __init__(self, registry: MetricsRegistry | None = None):
        self._reg = registry if registry is not None else MetricsRegistry()

    def add(self, **fields: int) -> None:
        """Atomically increment counters: ``st.add(panels=1, bytes_h2d=nb)``."""
        for name in fields:
            if name not in StreamStats.FIELDS:
                raise AttributeError(f"unknown stream counter {name!r}")
        self._reg.add_named(
            {f"stream.{name}": v for name, v in fields.items()}
        )

    def _note_live(self, live: int) -> None:
        self._reg.max_gauge("stream.peak_live_bytes", live)

    @property
    def panels(self) -> int:
        return int(self._reg.value("stream.panels"))

    @property
    def bytes_h2d(self) -> int:
        return int(self._reg.value("stream.bytes_h2d"))

    @property
    def bytes_h2d_saved(self) -> int:
        return int(self._reg.value("stream.bytes_h2d_saved"))

    @property
    def bytes_read(self) -> int:
        return int(self._reg.value("stream.bytes_read"))

    @property
    def bytes_decoded(self) -> int:
        return int(self._reg.value("stream.bytes_decoded"))

    @property
    def calls(self) -> int:
        return int(self._reg.value("stream.calls"))

    @property
    def peak_live_bytes(self) -> int:
        return int(self._reg.gauge("stream.peak_live_bytes"))

    def snapshot(self) -> dict[str, int]:
        """One atomic dict of every counter (plus the peak gauge)."""
        snap = self._reg.snapshot()
        out = {f: int(snap.counter(f"stream.{f}")) for f in StreamStats.FIELDS}
        out["peak_live_bytes"] = int(snap.gauges.get("stream.peak_live_bytes", 0))
        return out

    def __repr__(self) -> str:
        fields = ", ".join(f"{k}={v}" for k, v in self.snapshot().items())
        return f"StreamStats({fields})"


_STREAM_STATS = StreamStats(registry=_OBS_REGISTRY)


def stream_stats() -> StreamStats:
    """Counters since process start / last :func:`reset_stream_stats`."""
    return _STREAM_STATS


def reset_stream_stats() -> StreamStats:
    """Zero the counters in place, atomically.

    The returned object is the same live instance every caller (and every
    in-flight :class:`~repro.store.PanelPipeline`) already holds -- the reset
    cannot strand a pipeline on a stale counter object, and a concurrent
    ``add`` from the prefetch thread lands entirely before or entirely after
    the reset, never interleaved with it.
    """
    _STREAM_STATS._reg.reset(StreamStats._PREFIX)
    return _STREAM_STATS


class _PanelSource:
    """Operand classification for the streaming executor: ``streamed``
    operands are prefetched by the :class:`repro.store.PanelPipeline`
    background thread, resident ones are sliced on device at consume time."""

    def __init__(self, x, streamed: bool):
        self.x = x
        self.streamed = streamed


def _infer_panel_rows(handles, n0: int, n_row_shards: int) -> int:
    """Smallest height that is tile-aligned for every handle and shardable."""
    quanta = [int(h.panel_rows) for h in handles] + [n_row_shards]
    rows = int(np.lcm.reduce(np.asarray(quanta, np.int64)))
    if n0 % rows:
        raise ValueError(
            f"no common panel height: operand tile rows {quanta} don't tile n0={n0}"
        )
    return rows


def tile_stream(
    ctx,
    fn: Callable[..., jax.Array],
    *operands,
    grid: tuple[int, int] | None = None,
    in_specs: Sequence[P] | None = None,
    out_spec: P | None = None,
    reduce: str | None = None,
    out_dtype=None,
    panel_rows: int | None = None,
    prefetch_depth: int | None = None,
) -> jax.Array:
    """Run a :func:`tile_map` body over *streamed* row panels of the operands.

    The out-of-core execution path: operands that satisfy the snapshot-handle
    protocol (:func:`is_streamable`) are fetched from host/disk one full-width
    row panel at a time and fed to ``fn`` under the same :class:`Tile`
    contract as ``tile_map`` -- existing tile bodies (degrees, edge
    projection, blockwise builds, the Pallas CAD scorer) run unchanged, with
    ``tile.rows`` carrying the true global ids of the current panel.

    Prefetch is owned by :class:`repro.store.PanelPipeline`: a background
    thread fetches (and codec-decodes) up to ``prefetch_depth`` panels per
    streamed operand ahead of the consumer (default 2), and the
    ``jax.device_put`` of panel t+1 is issued before the compute on panel t
    is dispatched, so host reads, decode and the host->device copy all
    overlap the tile program.  Device residency for each streamed operand
    stays at most two panels regardless of the host-side depth.

    Bitwise contract: every supported body is row-parallel (output rows
    [r0:r1] depend only on operand rows [r0:r1]), and a panel run splits the
    mesh reduction extents exactly as the resident run does, so results are
    bitwise identical to ``tile_map`` on the same mesh.

    Args mirror :func:`tile_map`; additionally ``panel_rows`` overrides the
    streaming unit (default: the finest tile-aligned height that divides the
    row-shard grid) and ``prefetch_depth`` the host-side staging depth.
    ``reduce`` may be ``None`` (the (n0, n1) output is assembled
    panel-by-panel into a sharded buffer, donated between updates) or
    ``"cols"`` (per-panel row reductions are concatenated).
    """
    if reduce not in (None, "cols"):
        raise ValueError(f"tile_stream supports reduce=None or 'cols', got {reduce!r}")
    if in_specs is None:
        in_specs = tuple(ctx.matrix_spec for _ in operands)
    in_specs = tuple(in_specs)
    if len(in_specs) != len(operands):
        raise ValueError(f"{len(operands)} operands but {len(in_specs)} in_specs")

    handles = [op for op in operands if is_streamable(op)]
    if grid is None:
        if not handles:
            raise ValueError("grid= is required when no operand is streamable")
        grid = tuple(handles[0].shape)
    n0, n1 = grid
    for h in handles:
        if tuple(h.shape) != (n0, n1):
            raise ValueError(f"streamed operand is {h.shape}, grid is {grid}")

    R, C = ctx.n_row_shards, ctx.n_col_shards
    if panel_rows is None:
        panel_rows = _infer_panel_rows(handles, n0, R) if handles else n0
    if n0 % panel_rows or panel_rows % R or n1 % C:
        raise ValueError(
            f"panel_rows={panel_rows} must divide n0={n0} and the {R}x{C} shard grid"
        )
    pr, pc = panel_rows // R, n1 // C

    # Streamed operands: anything satisfying the handle protocol, plus
    # resident matrix-sharded arrays of the full grid shape (mixed
    # resident/store transitions slice their panels on device).
    sources: list[_PanelSource | None] = []
    for op, spec in zip(operands, in_specs):
        if is_streamable(op):
            sources.append(_PanelSource(op, streamed=True))
        elif spec == ctx.matrix_spec and getattr(op, "shape", None) == (n0, n1):
            sources.append(_PanelSource(op, streamed=False))
        else:
            sources.append(None)  # per-call constant (replicated table, scalar)

    reduce_axes = ctx.col_axes if reduce == "cols" else None

    panel_in_specs = []
    for spec, src in zip(in_specs, sources):
        panel_in_specs.append(ctx.matrix_spec if src is not None else spec)
    panel_in_specs = tuple(panel_in_specs)
    if out_spec is None:
        out_spec = ctx.vector_spec if reduce == "cols" else ctx.matrix_spec
    panel_out_spec = out_spec

    # jit so panels after the first hit the compile cache (eager shard_map
    # retraces per call; one compiled program serves the whole panel walk
    # because the row origin is a traced operand, not a constant), and cache
    # the program itself so later tile_stream calls with the same body don't
    # retrace either.
    key = (
        "tile_stream", fn, ctx, pr, pc, panel_in_specs, panel_out_spec, reduce,
        _dtype_key(out_dtype),
    )
    mapped = cached_program(
        key,
        lambda: jax.jit(
            shard_map(
                _tile_local(ctx, fn, pr, pc, reduce_axes, out_dtype, with_origin=True),
                mesh=ctx.mesh,
                in_specs=(P(), *panel_in_specs),
                out_specs=panel_out_spec,
            )
        ),
    )

    stats = _STREAM_STATS
    stats.add(calls=1)
    consts = [op for op, src in zip(operands, sources) if src is None]
    panel_sharding = ctx.sharding(ctx.matrix_spec)

    def run_panel(row0: int, panels):
        args = []
        it = iter(panels)
        jt = iter(consts)
        for src in sources:
            args.append(next(it) if src is not None else next(jt))
        return mapped(jnp.int32(row0), *args)

    # reduce="cols" panel outputs are small row reductions -- collect and
    # concatenate.  reduce=None assembles the (n0, n1) output *incrementally*
    # (buffer donated between updates), so at most one output buffer plus the
    # in-flight panels are ever live -- never all panels at once.
    out_sharding = ctx.sharding(out_spec)
    donate = (0,) if jax.default_backend() != "cpu" else ()
    update = cached_program(
        ("stream_update", out_sharding, donate),
        lambda: jax.jit(
            lambda buf, blk, r0: lax.dynamic_update_slice(buf, blk, (r0, jnp.int32(0))),
            donate_argnums=donate,
            out_shardings=out_sharding,
        ),
    )
    reduced_outs: list[jax.Array] = []
    buf = None

    def consume(row0: int, panels):
        nonlocal buf
        out = run_panel(row0, panels)
        if reduce == "cols":
            reduced_outs.append(out)
        else:
            if buf is None:
                buf = sharded_zeros((n0, n1), out.dtype, out_sharding)
            buf = update(buf, out, jnp.int32(row0))

    # All host staging -- background fetch + codec decode + device_put one
    # origin ahead -- is owned by the panel pipeline; the executor only runs
    # the compiled panel program and stitches outputs.
    from repro.store.pipeline import PanelPipeline  # deferred: store is optional

    origins = list(range(0, n0, panel_rows))
    with obs_trace.span(
        "tiles.stream",
        body=getattr(fn, "__name__", repr(fn)),
        n0=n0,
        n1=n1,
        panels=len(origins),
    ):
        with PanelPipeline(
            [src.x for src in sources if src is not None],
            origins,
            panel_rows,
            depth=prefetch_depth,
            sharding=panel_sharding,
            stats=stats,
        ) as pipe:
            for r0, panels in pipe:
                consume(r0, panels)

    if reduce == "cols":
        return ctx.constrain(jnp.concatenate(reduced_outs, axis=0), out_spec)
    return buf

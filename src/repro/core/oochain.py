"""Out-of-core Peng-Spielman chain product: the squaring chain against
store-backed working matrices.

The resident :func:`repro.core.chain.chain_product` keeps S, T, P, P1, P2 as
n x n device-resident arrays -- five n^2 buffers, the HBM bound on n once the
raw adjacency itself is streamed (the PR-2 snapshot store).  This module runs
the same recurrence

    T <- T @ T          P <- P @ T + P

entirely against a :class:`repro.store.TileStore`-backed scratch: every GEMM
is a walk over output row panels, each computed as a panel-accumulated sum

    C[I, :] = init[I, :] + sign * sum_K  L[I, K] @ R[K, :]

with L[I, K] sliced on the host from the left operand's row panel and R[K, :]
streamed host -> device one panel at a time.  Peak device residency per GEMM
is one accumulator panel + one streamed panel + one (panel x panel) block --
O(n * panel), never O(n^2).  The unary passes (S build, +I, the D^{-1/2}
sandwich, the Laplacian) stream one panel at a time through jitted
module-level panel programs: the row origin is a traced operand, so each
program compiles once per geometry and serves every panel of every snapshot.

Numerics: per-panel accumulation orders the GEMM reductions differently from
the resident single dot, so an out-of-core chain is *allclose* (fp32
accumulation throughout), not bitwise, vs the resident build -- the same
contract as the streamed ``fuse_l`` path, and the blockwise-solve tolerance
argument of Khoa & Chawla (arXiv:1111.4541) for approximate commute-time
embeddings.  Working matrices are stored fp32 regardless of the chain dtype.

The returned :class:`~repro.core.chain.ChainOperator` carries *store-backed*
P1 / P2 handles; :func:`repro.core.distmatrix.matmul_rowblock` and the
Richardson solver stream them per panel, so the whole pipeline -- ingest,
chain build, solve, scoring -- is panel-bounded end-to-end.  All panel
traffic is accounted in :func:`repro.core.tiles.stream_stats`.
"""

from __future__ import annotations

import uuid
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import laplacian as lap
from repro.obs import trace as obs_trace
from repro.core.chain import ChainOperator
from repro.core.distmatrix import F32_PRECISION, DistContext
from repro.core.tiles import (
    cached_program,
    is_streamable,
    program_cache_stats,
    shard_map,
    sharded_zeros,
    stream_stats,
)

# ---------------------------------------------------------------------------
# panel programs (module-level jit: compiled once per geometry, the row
# origin is traced so one program serves every panel)
# ---------------------------------------------------------------------------


@jax.jit
def _s_panel_deflated(blk, r0, inv_sqrt, deg, vol):
    ph = blk.shape[0]
    isr = lax.dynamic_slice(inv_sqrt, (r0,), (ph,))
    s = blk.astype(jnp.float32) * isr[:, None] * inv_sqrt[None, :]
    dr = lax.dynamic_slice(deg, (r0,), (ph,))
    u_r = jnp.sqrt(jnp.maximum(dr, 0.0) / vol)
    u_c = jnp.sqrt(jnp.maximum(deg, 0.0) / vol)
    return s - u_r[:, None] * u_c[None, :]


@jax.jit
def _s_panel_plain(blk, r0, inv_sqrt):
    ph = blk.shape[0]
    isr = lax.dynamic_slice(inv_sqrt, (r0,), (ph,))
    return blk.astype(jnp.float32) * isr[:, None] * inv_sqrt[None, :]


@jax.jit
def _plus_eye_panel(blk, r0):
    ph, n = blk.shape
    rows = r0 + jnp.arange(ph)
    cols = jnp.arange(n)
    return blk + (rows[:, None] == cols[None, :]).astype(blk.dtype)


@jax.jit
def _l_panel(blk, r0, deg):
    ph, n = blk.shape
    rows = r0 + jnp.arange(ph)
    eye = (rows[:, None] == jnp.arange(n)[None, :]).astype(jnp.float32)
    dr = lax.dynamic_slice(deg, (r0,), (ph,))
    return eye * dr[:, None] - blk.astype(jnp.float32)


@jax.jit
def _col_scale_panel(blk, v):
    return blk.astype(jnp.float32) * v[None, :]


@jax.jit
def _gemm_step(acc, block, right):
    """acc + block @ right, fp32 accumulate (one K-term of a panel GEMM)."""
    return acc + jnp.dot(
        block.astype(jnp.float32), right.astype(jnp.float32),
        precision=F32_PRECISION, preferred_element_type=jnp.float32,
    )


@jax.jit
def _gemm_step_neg(acc, block, right):
    return acc - jnp.dot(
        block.astype(jnp.float32), right.astype(jnp.float32),
        precision=F32_PRECISION, preferred_element_type=jnp.float32,
    )


@jax.jit
def _decode_bits_panel(u):
    """bf16 bit-pattern panel (uint16) -> fp32 on device (exact widening,
    same values the host codec would have produced)."""
    return lax.bitcast_convert_type(u, jnp.bfloat16).astype(jnp.float32)


def _kernel_gemm_program(ctx, positive: bool, blk_dtype: str, right_dtype: str,
                         ph: int, n: int):
    """Cached shard_map GEMM step through the fused Pallas kernel.

    SUMMA-style: each device all-gathers the block's column shards and the
    right panel's row shards (at *stored* width -- uint16 gathers move half
    the ICI bytes too), then runs one ``stream_gemm`` with the accumulator as
    the fused init: ``acc + sign * block @ right`` in a single kernel, bf16
    bit patterns widened in VMEM.  Cached per (ctx, sign, operand dtypes,
    geometry) so steady-state chain builds add zero traces.
    """

    def build():
        from repro.kernels.ops import stream_gemm

        def local(acc, blk, right):
            program_cache_stats().note_trace()
            a_pan = blk
            if ctx.n_col_shards > 1:
                a_pan = lax.all_gather(a_pan, ctx.col_axes, axis=1, tiled=True)
            b_pan = right
            if ctx.n_row_shards > 1:
                b_pan = lax.all_gather(b_pan, ctx.row_axes, axis=0, tiled=True)
            return stream_gemm(a_pan, b_pan, acc, sign=1.0 if positive else -1.0)

        return jax.jit(
            shard_map(
                local,
                mesh=ctx.mesh,
                in_specs=(ctx.matrix_spec, ctx.matrix_spec, ctx.matrix_spec),
                out_specs=ctx.matrix_spec,
            )
        )

    key = ("oo_gemm_kernel", ctx, positive, blk_dtype, right_dtype, ph, n)
    return cached_program(key, build)


# ---------------------------------------------------------------------------
# host-side panel plumbing
# ---------------------------------------------------------------------------


def _auto_grid(n: int, quantum: int) -> int:
    """Default working-store grid: panels of >= 32 rows, >= 2 per side.

    Finer grids bound residency tighter but pay per-panel dispatch and tile
    I/O on every GEMM step; 32-row panels keep the inner GEMM MXU-shaped.
    Small n falls back to the finest quantum-aligned grid.
    """
    for g in (8, 4, 2):
        if n % g == 0 and (n // g) % quantum == 0 and n // g >= 32:
            return g
    for g in (16, 8, 4, 2, 1):
        if n % g == 0 and (n // g) % quantum == 0:
            return g
    raise ValueError(f"n={n} is not divisible by the panel quantum {quantum}")


# ---------------------------------------------------------------------------
# the out-of-core chain build
# ---------------------------------------------------------------------------


def chain_product_oocore(
    ctx: DistContext,
    a,
    d_len: int,
    *,
    dtype=jnp.float32,
    deflate: bool = True,
    fuse_l: bool = False,
    work=None,
    panel_rows: int | None = None,
    tile_codec: str = "raw",
    prefetch_depth: int | None = None,
    use_gemm_kernel: bool = False,
    level_sink: dict | None = None,
) -> ChainOperator:
    """Build the chain operator with store-backed working matrices.

    ``level_sink`` retains the squaring levels as *live* scratch snapshots
    for incremental delta updates (see
    :func:`repro.core.chain.chain_product`): the usual eager removal of the
    T intermediates is skipped, ``level_sink["t"]`` gets the T_0 .. T_{d-1}
    handles, and the caller owns their lifetime
    (``delta_chain.BaseChain.release()`` removes them).

    ``a`` is a resident sharded adjacency or a store-backed snapshot handle
    (handles keep even the input off-core).  ``work`` is the scratch
    :class:`~repro.store.TileStore` -- a store instance, a directory path, or
    ``None`` for a host-RAM-backed scratch (device residency is bounded
    either way; the directory form additionally bounds host RAM).
    ``panel_rows`` overrides the streaming unit.

    All panel fetches go through :class:`repro.store.PanelPipeline`: a
    background thread keeps up to ``prefetch_depth`` panels per operand
    decoded and staged ahead of the GEMM/unary passes, so scratch reads (and
    codec decode) overlap device compute.  ``tile_codec`` selects the scratch
    tile encoding when this call creates the scratch store (``raw`` default;
    ``bf16`` halves scratch bytes at a per-level rounding of the working
    matrices, ``zstd`` compresses losslessly where the backend is installed)
    -- a caller-supplied ``work`` store keeps whatever codec it was created
    with.

    Every snapshot id in the scratch is prefixed with a fresh nonce, so one
    scratch store (or directory) can serve many builds -- including resumed
    processes -- without id collisions; intermediates are removed as soon as
    the recurrence no longer needs them, and only P1 / P2 survive the build
    (retired via ``ChainOperator.release_scratch`` by ``detect_anomalies``
    and by ``SequenceDetector`` as the operator leaves the two-snapshot
    window).  ``dtype`` is accepted for signature parity but ignored: the
    scratch and the returned operator are always fp32.

    ``use_gemm_kernel=True`` routes every chain GEMM step through the fused
    Pallas streaming kernel (:mod:`repro.kernels.stream_gemm`): operand
    panels ship in their *stored* form where the codec is device-decodable
    (bf16 bit patterns, half the H2D bytes, widened in VMEM) and the
    accumulate folds into the kernel.  Allclose vs the XLA step (same codec);
    interpret mode on CPU.  The flag rides on the returned operator so the
    solve driver inherits the kernel path for its streamed iterations.
    """
    from repro.store import (  # deferred: core->store only on this path
        DEFAULT_PREFETCH_DEPTH,
        PanelPipeline,
        TileStore,
    )

    if d_len < 1:
        raise ValueError("chain length d must be >= 1")
    n = int(a.shape[0])
    R, C = ctx.n_row_shards, ctx.n_col_shards
    src_quantum = int(a.panel_rows) if is_streamable(a) else 1
    quantum = int(np.lcm.reduce(np.asarray([R, C, src_quantum], np.int64)))
    if work is None:
        work = TileStore.create(None, n=n, grid=_auto_grid(n, quantum), codec=tile_codec)
    elif isinstance(work, (str, Path)):
        work = TileStore.create(
            work, n=n, grid=_auto_grid(n, quantum), codec=tile_codec
        )
    if work.n != n:
        raise ValueError(f"working store holds n={work.n}, adjacency is n={n}")
    ph = int(panel_rows or np.lcm(work.tile_rows, quantum))
    if n % ph or ph % work.tile_rows or ph % quantum:
        raise ValueError(
            f"panel_rows={ph} must divide n={n} and align to store tiles "
            f"({work.tile_rows}) and the mesh/source quantum ({quantum})"
        )
    tag = f"w{uuid.uuid4().hex[:8]}."
    origins = list(range(0, n, ph))

    st = stream_stats()
    st.add(calls=1)
    sharding = ctx.sharding(ctx.matrix_spec)
    rep = ctx.sharding(P(None))

    deg = lap.degrees(ctx, a, prefetch_depth=prefetch_depth)
    vol = lap.volume(ctx, deg)
    deg_r = jax.device_put(deg, rep)
    inv_sqrt_r = jnp.where(deg_r > 0, lax.rsqrt(jnp.maximum(deg_r, 1e-30)), 0.0)

    def put_panel(host, decoded_nbytes: int | None = None):
        dev = jax.device_put(np.ascontiguousarray(np.asarray(host)), sharding)
        inc = {"panels": 1, "bytes_h2d": dev.nbytes}
        if decoded_nbytes is not None and decoded_nbytes > dev.nbytes:
            # Encoded (stored-width) put: the gap vs a host-decoded transfer.
            inc["bytes_h2d_saved"] = decoded_nbytes - dev.nbytes
        st.add(**inc)
        return dev

    def stream(source, walk=None, *, device: bool, encoded: bool = False):
        """A prefetching pipeline over row panels of one operand."""
        return PanelPipeline(
            [source],
            walk if walk is not None else origins,
            ph,
            depth=prefetch_depth,
            sharding=sharding if device else None,
            stats=st,
            encoded=encoded,
        )

    def unary_pass(out_id: str, source, fn, *args):
        """Stream panels through a jitted panel program into the store."""
        with obs_trace.span("oochain.unary", out=out_id), \
                work.writer(out_id) as w, stream(source, device=True) as pipe:
            for r0, (blk,) in pipe:
                # Resident sources bypass the pipeline's staging (and its
                # residency accounting): count the panel we just put ourselves.
                blk = blk if is_streamable(source) else put_panel(blk)
                live = pipe.device_live_bytes if is_streamable(source) else blk.nbytes
                out = fn(blk, jnp.int32(r0), *args)
                st._note_live(live + out.nbytes)
                w.put_row_panel(r0, np.asarray(out))
        return work.snapshot(out_id)

    def oo_gemm(out_id: str, left_h, right_h, *, init: str = "zero", sign: float = 1.0,
                col_scale=None):
        """C[I, :] = init_I + sign * sum_K left[I, K] @ right[K, :] into the store.

        ``init``: "zero", "left" (C = left + ...; the P @ T + P fusion) or
        "left_colscale" (C = left * col_scale - ...; the fuse_l P2 build).
        The left row panel stays on the host; only its (ph, ph) K-blocks, the
        streamed right panels and the accumulator are ever device-resident.
        Both operands are prefetched: the left panels one GEMM row ahead
        (host ring), the right panels along the full nested K-walk (device
        staging), so neither fetch serializes with the MXU.

        On the kernel path (``use_gemm_kernel``) both streams ship stored-
        form panels (bf16 -> uint16 bits) and each K step is one fused
        ``stream_gemm`` with the accumulator as init -- the decode moves into
        VMEM and the stored-vs-decoded H2D gap lands in ``bytes_h2d_saved``.
        """
        step = _gemm_step if sign > 0 else _gemm_step_neg
        nested = [k0 for _ in origins for k0 in origins]  # right walk, per row
        dec_panel = ph * n * 4  # fp32 bytes a host-decoded panel would ship
        with obs_trace.span("oochain.gemm", out=out_id, panels=len(origins)), \
                work.writer(out_id) as w, \
                stream(left_h, device=False, encoded=use_gemm_kernel) as lpipe, \
                stream(right_h, nested, device=True, encoded=use_gemm_kernel) as rpipe:
            right_iter = iter(rpipe)
            for r0, (left_host,) in lpipe:
                left_host = np.asarray(left_host)
                left_enc = left_host.dtype == np.uint16
                if init in ("left", "left_colscale"):
                    lp = put_panel(left_host, dec_panel if left_enc else None)
                    accp = _decode_bits_panel(lp) if left_enc else lp.astype(jnp.float32)
                    acc = accp if init == "left" else _col_scale_panel(accp, col_scale)
                else:
                    acc = sharded_zeros((ph, n), jnp.float32, sharding)
                for k0 in origins:
                    _, (right,) = next(right_iter)
                    if is_streamable(right_h):
                        right_live = rpipe.device_live_bytes
                    else:  # resident: our put_panel, not pipeline staging
                        right = put_panel(right)
                        right_live = right.nbytes
                    block = put_panel(
                        left_host[:, k0 : k0 + ph],
                        ph * ph * 4 if left_enc else None,
                    )
                    if use_gemm_kernel:
                        prog = _kernel_gemm_program(
                            ctx, sign > 0, str(block.dtype), str(right.dtype), ph, n
                        )
                        acc = prog(acc, block, right)
                    else:
                        acc = step(acc, block, right)
                    st._note_live(acc.nbytes + block.nbytes + right_live)
                w.put_row_panel(r0, np.asarray(acc))
        return work.snapshot(out_id)

    # S (= T at level 0) and P0 = I + S, in one pass over A.  Level ids use a
    # "lvl" infix so they can never collide with the final P1 / P2 outputs.
    s_id, p_id = tag + "Tlvl0", tag + "Plvl0"
    with obs_trace.span("oochain.s_build", n=n, panels=len(origins)), \
            work.writer(s_id) as ws, work.writer(p_id) as wp, \
            stream(a, device=True) as apipe:
        for r0, (blk,) in apipe:
            blk = blk if is_streamable(a) else put_panel(blk)
            a_live = apipe.device_live_bytes if is_streamable(a) else blk.nbytes
            if deflate:
                s_blk = _s_panel_deflated(blk, jnp.int32(r0), inv_sqrt_r, deg_r, vol)
            else:
                s_blk = _s_panel_plain(blk, jnp.int32(r0), inv_sqrt_r)
            p_blk = _plus_eye_panel(s_blk, jnp.int32(r0))
            st._note_live(a_live + s_blk.nbytes + p_blk.nbytes)
            ws.put_row_panel(r0, np.asarray(s_blk))
            wp.put_row_panel(r0, np.asarray(p_blk))
    t_h, p_h = work.snapshot(s_id), work.snapshot(p_id)

    # The squaring chain, every operand store-backed.  With a level_sink the
    # intermediates survive the build as live scratch snapshots (the delta
    # path streams skinny GEMMs against them); without one they are removed
    # as soon as the recurrence no longer needs them, as before.
    retain = level_sink is not None
    t_levels = [t_h]
    for lvl in range(1, d_len):
        t_new = oo_gemm(f"{tag}Tlvl{lvl}", t_h, t_h)
        p_new = oo_gemm(f"{tag}Plvl{lvl}", p_h, t_new, init="left")
        t_levels.append(t_new)
        if not retain:
            work.remove_snapshot(t_h.snap_id)
        work.remove_snapshot(p_h.snap_id)
        t_h, p_h = t_new, p_new

    # the P1 sandwich is the same row/col scaling as the undeflated S build
    p1_h = unary_pass(tag + "P1", p_h, _s_panel_plain, inv_sqrt_r)
    if fuse_l:
        p2_h = oo_gemm(tag + "P2", p1_h, a, init="left_colscale", sign=-1.0,
                       col_scale=deg_r)
    else:
        l_h = unary_pass(tag + "L", a, _l_panel, deg_r)
        p2_h = oo_gemm(tag + "P2", p1_h, l_h)
        work.remove_snapshot(l_h.snap_id)
    if retain:
        # T_0..T_{d-1} stay live for the delta path; the final P dies now.
        work.remove_snapshot(p_h.snap_id)
        level_sink["t"] = t_levels
    else:
        work.remove_snapshot(t_h.snap_id)
        work.remove_snapshot(p_h.snap_id)

    # Measure the Richardson contraction rho(S~^{2^d}) once at build: the
    # power iteration wraps the store-backed P2 in a CachingHandle, so the
    # whole estimate costs one real scratch pass (replays from host RAM for
    # the rest).  The solve driver reads it for Chebyshev intervals.
    from repro.core.solvers.power import estimate_rho

    with obs_trace.span("oochain.estimate_rho", n=n):
        rho = estimate_rho(ctx, p2_h, prefetch_depth=prefetch_depth)
    return ChainOperator(
        p1=p1_h, p2=p2_h, deg=deg, vol=vol,
        prefetch_depth=prefetch_depth or DEFAULT_PREFETCH_DEPTH,
        rho=rho,
        use_gemm_kernel=use_gemm_kernel,
    )

"""Fused streaming panel-GEMM Pallas kernels: the out-of-core hot path.

Generalizes :mod:`repro.kernels.block_matmul` for the panel-streaming
executors (``core/oochain.py`` GEMM steps, the streamed solve driver):

* **On-device bf16 decode**: operands may arrive as raw bf16 bit patterns
  (``uint16``, exactly what the store's bf16 codec holds on disk).  The
  kernel widens them to fp32 inside VMEM (``bitcast -> bf16 -> f32``, the
  same exact widening as the host codec), so the panel pipeline ships the
  *stored* bytes -- half the H2D traffic of host-decoded fp32 -- and the
  host prefetch thread stops paying the decode.  Encoded-ness is inferred
  from the operand dtype: ``uint16`` means bf16 bits, anything else is cast
  to fp32 as the XLA path does.
* **Double buffering**: the grid walks (m/bm, n/bn, k/bk) with k innermost;
  Pallas pipelines the next block's HBM->VMEM DMA under the current dot, so
  the copy of block k+1 overlaps compute on block k (same schedule as
  ``block_matmul``, see its VMEM budget note).
* **Fused accumulate-into**: ``stream_gemm(a, b, init)`` computes
  ``init + sign * (a @ b)`` in one kernel -- the per-K-step body of the
  out-of-core GEMM (`acc <- acc + block @ right`) without a separate add.
* **Fused solve epilogue**: :func:`fused_panel_matvec` folds the streamed
  solver's per-iteration update into the mat-vec itself -- one kernel pass
  over a P2 row panel yields the Richardson update ``gy = chi + y - P2 @ y``
  *and* the deflated-residual partials (per-column sums and the sum of
  squares of ``delta = chi - P2 @ y``), so each iteration is exactly one
  pass over the panel stream with no separate epilogue dispatches.

Numerics: fp32 accumulation in VMEM scratch regardless of input encoding.
With unblocked K the ``init``-form is bitwise identical to the XLA
``acc + dot`` step; blocked K reorders the reduction (allclose).  Interpret
mode runs the same kernel bodies on the CPU (see :mod:`repro.kernels.dispatch`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import pallas_call


def _dec(x, encoded: bool):
    """Widen one VMEM block to fp32; ``encoded`` blocks are bf16 bit patterns.

    ``bitcast(uint16 -> bf16) -> f32`` is the exact widening the host codec
    (:func:`repro.store.tilestore._bf16_u16_to_f32`) performs -- decoded
    values are bitwise identical, only the decode site moves on-device.
    """
    if encoded:
        return lax.bitcast_convert_type(x, jnp.bfloat16).astype(jnp.float32)
    return x.astype(jnp.float32)


def _dot(a, b, exact_bf16: bool):
    """fp32-accumulated ``a @ b``: full float32 precision, except where both
    operands are decoded bf16 and the MXU's one bf16 pass is already exact."""
    return jnp.dot(
        a, b, precision=None if exact_bf16 else lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _stream_gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps, a_enc, b_enc, neg):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(
        _dec(a_ref[...], a_enc), _dec(b_ref[...], b_enc), a_enc and b_enc
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        o_ref[...] = (-acc if neg else acc).astype(o_ref.dtype)


def _stream_gemm_init_kernel(
    a_ref, b_ref, i_ref, o_ref, acc_ref, *, k_steps, a_enc, b_enc, neg
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(
        _dec(a_ref[...], a_enc), _dec(b_ref[...], b_enc), a_enc and b_enc
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        base = i_ref[...].astype(jnp.float32)
        o_ref[...] = (base - acc if neg else base + acc).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sign", "bm", "bk", "bn", "out_dtype"),
)
def stream_gemm(
    a: jax.Array,
    b: jax.Array,
    init: jax.Array | None = None,
    *,
    sign: float = 1.0,
    bm: int = 256,
    bk: int = 256,
    bn: int = 256,
    out_dtype=jnp.float32,
) -> jax.Array:
    """``init + sign * (A @ B)`` (init optional), fp32 accumulation.

    ``A`` (m, k) and ``B`` (k, n) may independently be fp32/bf16 values or
    raw bf16 bit patterns (``uint16``), decoded on-device per block; ``init``
    (m, n), when given, is added at the output flush -- with unblocked K this
    is bitwise the XLA ``init + dot`` / ``init - dot`` GEMM step.  ``sign``
    must be +/-1.0 (it selects add vs subtract; no scaling is performed).
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dims mismatch: {a.shape} @ {b.shape}")
    if init is not None and init.shape != (m, n):
        raise ValueError(f"init is {init.shape}, output is {(m, n)}")
    if sign not in (1.0, -1.0):
        raise ValueError(f"sign selects add/subtract and must be +-1.0, got {sign}")
    a_enc = a.dtype == jnp.uint16
    b_enc = b.dtype == jnp.uint16
    from repro.kernels.tiling import fit

    bm, bk, bn = fit(m, bm), fit(k, bk), fit(n, bn)
    grid = (m // bm, n // bn, k // bk)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    operands = [a, b]
    kwargs = dict(k_steps=grid[2], a_enc=a_enc, b_enc=b_enc, neg=sign < 0)
    if init is None:
        kernel = functools.partial(_stream_gemm_kernel, **kwargs)
    else:
        kernel = functools.partial(_stream_gemm_init_kernel, **kwargs)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        operands.append(init)
    return pallas_call(
        kernel,
        *operands,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )


def _fused_matvec_kernel(
    p_ref, y_ref, chi_ref, yp_ref, gy_ref, cs_ref, ss_ref, acc_ref, *, k_steps, enc
):
    i = pl.program_id(0)
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The reduction outputs map every grid point to block (0, 0): they live
    # in VMEM across the whole (sequential) grid walk, initialized once and
    # accumulated at each row block's last K step.
    @pl.when(jnp.logical_and(i == 0, kk == 0))
    def _init_reductions():
        cs_ref[...] = jnp.zeros_like(cs_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    acc_ref[...] += _dot(_dec(p_ref[...], enc), y_ref[...].astype(jnp.float32), False)

    @pl.when(kk == k_steps - 1)
    def _epilogue():
        mv = acc_ref[...]
        chi = chi_ref[...].astype(jnp.float32)
        gy_ref[...] = (chi + yp_ref[...].astype(jnp.float32) - mv).astype(gy_ref.dtype)
        delta = chi - mv  # == gy - y, the residual's panel contribution
        cs_ref[...] += jnp.sum(delta, axis=0, keepdims=True)
        ss_ref[...] += jnp.sum(delta * delta).reshape(1, 1)


@functools.partial(jax.jit, static_argnames=("bm", "bk"))
def fused_panel_matvec(
    p_panel: jax.Array,
    y: jax.Array,
    chi_panel: jax.Array,
    y_panel: jax.Array,
    *,
    bm: int = 256,
    bk: int = 256,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One fused solve-iteration pass over a P2 row panel.

    ``(gy, colsum, sumsq)`` with ``gy = chi_panel + y_panel - p_panel @ y``
    (the Richardson update restricted to this panel's rows) and the
    deflated-residual partials of ``delta = chi_panel - p_panel @ y``:
    ``colsum`` (1, q) holds per-column sums, ``sumsq`` (1, 1) the total sum
    of squares.  The caller reduces panels via
    ``res^2 = sum(sumsq) - sum(colsum^2) / n`` (the mean-subtracted
    Frobenius norm), so mat-vec + AXPY + residual cost one panel pass.

    ``p_panel`` (ph, K) may be fp32 or raw bf16 bit patterns (uint16,
    decoded on-device); ``y`` is (K, q), ``chi_panel`` / ``y_panel`` are
    the (ph, q) row slices of chi / y matching this panel.
    """
    ph, kdim = p_panel.shape
    k2, q = y.shape
    if kdim != k2:
        raise ValueError(f"inner dims mismatch: {p_panel.shape} @ {y.shape}")
    if chi_panel.shape != (ph, q) or y_panel.shape != (ph, q):
        raise ValueError(
            f"chi/y panels must be {(ph, q)}, got {chi_panel.shape}/{y_panel.shape}"
        )
    enc = p_panel.dtype == jnp.uint16
    from repro.kernels.tiling import fit

    bm, bk = fit(ph, bm), fit(kdim, bk)
    grid = (ph // bm, kdim // bk)
    k_steps = grid[1]
    return pallas_call(
        functools.partial(_fused_matvec_kernel, k_steps=k_steps, enc=enc),
        p_panel, y, chi_panel, y_panel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk: (i, kk)),
            pl.BlockSpec((bk, q), lambda i, kk: (kk, 0)),
            pl.BlockSpec((bm, q), lambda i, kk: (i, 0)),
            pl.BlockSpec((bm, q), lambda i, kk: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bm, q), lambda i, kk: (i, 0)),
            pl.BlockSpec((1, q), lambda i, kk: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, kk: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((ph, q), jnp.float32),
            jax.ShapeDtypeStruct((1, q), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((bm, q), jnp.float32)],
    )

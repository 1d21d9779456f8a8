"""Per-kernel allclose vs the pure-jnp oracle: shape/dtype sweeps.

All kernels run in interpret mode on CPU (the kernel body executes on the
host), so these validate the actual Pallas kernel logic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype=np.float32, positive=False):
    x = RNG.normal(size=shape).astype(np.float32)
    if positive:
        x = np.abs(x)
    return jnp.asarray(x.astype(dtype))


# ---------------------------------------------------------------------------
# dispatch: where a kernel runs
# ---------------------------------------------------------------------------


def test_dispatch_interprets_only_on_cpu():
    """Inside a map the device kind decides: TPU interpret mode on the CPU,
    the compiled kernel on a TPU, and no path at all anywhere else."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.dispatch import _interpret_for

    assert isinstance(_interpret_for("cpu"), pltpu.InterpretParams)
    assert _interpret_for("TPU v5 lite") is False
    with pytest.raises(ValueError, match="device kind"):
        _interpret_for("NVIDIA H100 80GB HBM3")


def test_dispatch_lowering_for_other_platform_raises():
    """Outside a map the choice is made at lowering: a platform with no
    branch (here CUDA) is refused, never silently interpreted."""
    a = _arr((128, 128))
    with pytest.raises(NotImplementedError, match="cuda"):
        jax.jit(ops.block_matmul).trace(a, a).lower(lowering_platforms=("cuda",))


def test_dispatch_kernel_in_varying_shard_map(ctx22):
    """A kernel inside a checked shard_map gets outputs typed with the
    operands' varying axes, and runs (interpreted) on the CPU mesh."""
    from repro.core.tiles import shard_map

    spec = ctx22.matrix_spec
    a, b = _arr((128, 128)), _arr((128, 128))
    out = jax.jit(
        shard_map(
            lambda x, y: ops.block_matmul(x, y, bm=64, bk=64, bn=64),
            mesh=ctx22.mesh, in_specs=(spec, spec), out_specs=spec,
        )
    )(a, b)
    # each device multiplies its own (64, 64) blocks
    np.testing.assert_allclose(
        np.asarray(out)[:64, :64],
        np.asarray(ref.block_matmul(a[:64, :64], b[:64, :64])),
        rtol=1e-5, atol=1e-4,
    )


# ---------------------------------------------------------------------------
# block_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128), (64, 512, 256), (120, 72, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_matmul_shapes_dtypes(m, k, n, dtype):
    a, b = _arr((m, k), dtype), _arr((k, n), dtype)
    out = ops.block_matmul(a, b, bm=128, bk=128, bn=128, out_dtype=jnp.float32)
    expect = ref.block_matmul(a, b, out_dtype=jnp.float32)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("tiles", [(64, 64, 64), (128, 256, 128), (32, 32, 32)])
def test_block_matmul_tile_invariance(tiles):
    a, b = _arr((256, 256)), _arr((256, 256))
    bm, bk, bn = tiles
    out = ops.block_matmul(a, b, bm=bm, bk=bk, bn=bn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.block_matmul(a, b)), rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# edge_projection
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k", [(128, 4), (256, 8), (192, 15)])
def test_edge_projection(n, k):
    a = _arr((n, n), positive=True)
    out = ops.edge_projection(a, seed=3, k=k, bm=64, bn=64)
    expect = ref.edge_projection(a, seed=3, k=k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5, atol=1e-4)


def test_edge_projection_tile_invariance():
    a = _arr((256, 256), positive=True)
    o1 = ops.edge_projection(a, seed=1, k=4, bm=64, bn=64)
    o2 = ops.edge_projection(a, seed=1, k=4, bm=128, bn=256)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# cad_scores
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k", [(128, 8), (256, 16)])
def test_cad_scores(n, k):
    a1, a2 = _arr((n, n), positive=True), _arr((n, n), positive=True)
    z1, z2 = _arr((n, k)), _arr((n, k))
    v1, v2 = jnp.float32(10.0), jnp.float32(12.5)
    out = ops.cad_scores(a1, a2, z1, z2, v1, v2, bm=64, bn=64)
    expect = ref.cad_scores(a1, a2, z1, z2, v1, v2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-2)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,d", [(128, 64), (256, 128), (64, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(s, d, causal):
    q, k, v = _arr((2, s, d)), _arr((2, s, d)), _arr((2, s, d))
    out = ops.flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    expect = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-4)


def test_flash_attention_matches_model_chunked():
    """Pallas flash == the model's pure-JAX chunked flash (same math)."""
    from repro.models.attention import _chunked_flash
    from repro.models.common import ArchConfig

    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=64, n_heads=2,
                     n_kv_heads=2, d_ff=64, vocab=16, attn_chunk=64,
                     compute_dtype="float32")
    b, s, h, hd = 2, 128, 2, 32
    q, k, v = _arr((b, s, h, hd)), _arr((b, s, h, hd)), _arr((b, s, h, hd))
    out_model = _chunked_flash(cfg, q, k, v, causal=True, rules={})
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, s, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, s, hd)
    out_pallas = ops.flash_attention(qf, kf, vf, causal=True, bq=64, bk=64)
    out_pallas = jnp.moveaxis(out_pallas.reshape(b, h, s, hd), 1, 2)
    np.testing.assert_allclose(np.asarray(out_model), np.asarray(out_pallas), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# wkv (RWKV6 recurrence)
# ---------------------------------------------------------------------------


# NOTE: chunk sizes stay <= ~32 under strong decay -- the factorized
# exp(cum_t - cum_i) form loses precision when per-chunk cumulative decay
# exceeds ~e^30 (documented in kernels/wkv.py); production chunk is 128 with
# the much gentler decays of trained RWKV models.
@pytest.mark.parametrize("s,chunk", [(64, 16), (128, 32), (96, 24)])
def test_wkv_kernel(s, chunk):
    BH, dk, dv = 3, 16, 16
    r = _arr((BH, s, dk))
    k = _arr((BH, s, dk))
    v = _arr((BH, s, dv))
    lw = -jnp.exp(_arr((BH, s, dk)) * 0.5 - 1.0)
    u = 0.1 * _arr((BH, dk))
    out = ops.wkv(r, k, v, lw, u, chunk=chunk)
    expect = ref.wkv(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-3, atol=1e-3)


def test_wkv_kernel_state_carries_across_chunks():
    """Same inputs, different chunking -> identical output (state flows)."""
    BH, s, dk = 2, 64, 8
    r, k, v = _arr((BH, s, dk)), _arr((BH, s, dk)), _arr((BH, s, dk))
    lw = -jnp.exp(_arr((BH, s, dk)) * 0.3 - 1.0)
    u = 0.1 * _arr((BH, dk))
    o1 = ops.wkv(r, k, v, lw, u, chunk=8)
    o2 = ops.wkv(r, k, v, lw, u, chunk=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4, atol=1e-4)

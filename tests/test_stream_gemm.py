"""Fused Pallas stream-GEMM kernel path: interpret-mode parity + accounting.

Three layers of guarantees, all runnable off-TPU (interpret mode):

* kernel primitives -- ``stream_gemm`` fp32 is *bitwise* the XLA
  ``_gemm_step`` with unblocked K; the in-kernel bf16 bit-pattern decode is
  bitwise the host codec's widening; the fused mat-vec epilogue's residual
  moments satisfy the deflation identity;
* solve parity -- the fused-epilogue streamed solve stays allclose (<= 1e-4)
  to the two-pass XLA driver on 1x1 AND 2x2 meshes, and the raw-codec kernel
  path stays allclose to the fully resident solve;
* traffic accounting -- stored-form bf16 shipping halves solve-phase H2D
  (<= 0.55x the fp32-decode baseline), ``bytes_h2d_saved`` records the gap,
  and each fused iteration makes exactly one pass over the panel stream.
"""

from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.chain import chain_product
from repro.core.oochain import _gemm_step, _gemm_step_neg
from repro.core.solvers import SolverSpec, solve
from repro.core.tiles import reset_stream_stats, stream_stats
from repro.kernels.stream_gemm import fused_panel_matvec, stream_gemm


def _rng(seed=0):
    return np.random.default_rng(seed)


def _sym(n, seed=0):
    a = _rng(seed).uniform(0.1, 1.0, (n, n)).astype(np.float32)
    a = (a + a.T) / 2
    np.fill_diagonal(a, 0.0)
    return a


def _bf16_bits(x: np.ndarray) -> np.ndarray:
    """Host bf16 round-to-nearest-even encode -> uint16 bit patterns."""
    return np.asarray(jnp.asarray(x).astype(jnp.bfloat16)).view(np.uint16)


def _host_decode(u: np.ndarray) -> np.ndarray:
    return (u.astype(np.uint32) << 16).view(np.float32)


# ---------------------------------------------------------------------------
# kernel primitives
# ---------------------------------------------------------------------------


def test_stream_gemm_fp32_bitwise_vs_xla_step():
    r = _rng(1)
    a = r.normal(size=(32, 48)).astype(np.float32)
    b = r.normal(size=(48, 24)).astype(np.float32)
    init = r.normal(size=(32, 24)).astype(np.float32)
    # whole-dim K block: identical reduction order to the single XLA dot
    got = stream_gemm(jnp.asarray(a), jnp.asarray(b), jnp.asarray(init))
    want = _gemm_step(jnp.asarray(init), jnp.asarray(a), jnp.asarray(b))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_stream_gemm_negative_sign_bitwise():
    r = _rng(2)
    a = r.normal(size=(16, 32)).astype(np.float32)
    b = r.normal(size=(32, 16)).astype(np.float32)
    init = r.normal(size=(16, 16)).astype(np.float32)
    got = stream_gemm(jnp.asarray(a), jnp.asarray(b), jnp.asarray(init), sign=-1.0)
    want = _gemm_step_neg(jnp.asarray(init), jnp.asarray(a), jnp.asarray(b))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_stream_gemm_no_init_is_plain_dot():
    r = _rng(3)
    a = r.normal(size=(16, 16)).astype(np.float32)
    b = r.normal(size=(16, 8)).astype(np.float32)
    got = stream_gemm(jnp.asarray(a), jnp.asarray(b))
    want = jnp.dot(jnp.asarray(a), jnp.asarray(b),
                   preferred_element_type=jnp.float32)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_stream_gemm_blocked_k_allclose():
    r = _rng(4)
    a = r.normal(size=(64, 128)).astype(np.float32)
    b = r.normal(size=(128, 32)).astype(np.float32)
    got = stream_gemm(jnp.asarray(a), jnp.asarray(b), bm=32, bk=32, bn=32)
    want = a.astype(np.float64) @ b.astype(np.float64)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_in_kernel_bf16_decode_bitwise_vs_host_codec():
    r = _rng(5)
    a_bits = _bf16_bits(r.normal(size=(32, 64)).astype(np.float32))
    b = r.normal(size=(64, 16)).astype(np.float32)
    got = stream_gemm(jnp.asarray(a_bits), jnp.asarray(b))
    want = jnp.dot(jnp.asarray(_host_decode(a_bits)), jnp.asarray(b),
                   preferred_element_type=jnp.float32)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_fused_panel_matvec_epilogue():
    r = _rng(6)
    ph, n, q = 32, 64, 5
    p = r.normal(size=(ph, n)).astype(np.float32)
    y = r.normal(size=(n, q)).astype(np.float32)
    chi_p = r.normal(size=(ph, q)).astype(np.float32)
    y_p = y[:ph]
    gy, cs, ss = fused_panel_matvec(
        jnp.asarray(p), jnp.asarray(y), jnp.asarray(chi_p), jnp.asarray(y_p)
    )
    mv = p.astype(np.float64) @ y.astype(np.float64)
    np.testing.assert_allclose(np.asarray(gy), chi_p + y_p - mv,
                               rtol=1e-5, atol=1e-5)
    delta = chi_p - mv
    np.testing.assert_allclose(np.asarray(cs)[0], delta.sum(0),
                               rtol=1e-5, atol=1e-5)
    # the deflation identity the solver relies on:
    #   ||delta - colmean(delta)||_F^2 = ss - sum_c cs_c^2 / n_rows
    ss_v = float(np.asarray(ss)[0, 0])
    cs_v = np.asarray(cs, np.float64)[0]
    defl = ((delta - delta.mean(0, keepdims=True)) ** 2).sum()
    np.testing.assert_allclose(ss_v - (cs_v ** 2).sum() / ph, defl,
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# solve parity + traffic accounting (1x1 and 2x2 meshes)
# ---------------------------------------------------------------------------


def _build_and_solve(ctx, n, codec, kernel, *, d=3, q=5, k=4, seed=0):
    a = jax.device_put(_sym(n, seed), ctx.sharding(ctx.matrix_spec))
    op = chain_product(ctx, a, d, oocore=True, tile_codec=codec,
                       use_gemm_kernel=kernel)
    b = _rng(seed + 100).normal(size=(n, k)).astype(np.float32)
    b = jax.device_put(b, ctx.sharding(ctx.rowblock_spec))
    st = stream_stats()
    h2d0, panels0 = st.bytes_h2d, st.panels
    y, rep = solve(ctx, op, b, SolverSpec(), fixed_q=q)
    st = stream_stats()
    op.release_scratch()
    return (np.asarray(y), rep,
            st.bytes_h2d - h2d0, st.panels - panels0)


def _resident_solve(ctx, n, *, d=3, q=5, k=4, seed=0):
    a = jax.device_put(_sym(n, seed), ctx.sharding(ctx.matrix_spec))
    op = chain_product(ctx, a, d)
    b = _rng(seed + 100).normal(size=(n, k)).astype(np.float32)
    b = jax.device_put(b, ctx.sharding(ctx.rowblock_spec))
    y, _ = solve(ctx, op, b, SolverSpec(), fixed_q=q)
    return np.asarray(y)


@pytest.mark.parametrize("mesh", ["ctx1", "ctx22"])
def test_fused_solve_allclose_vs_two_pass_driver(mesh, request):
    ctx = request.getfixturevalue(mesh)
    n = 64
    y_xla, _, _, _ = _build_and_solve(ctx, n, "raw", False)
    y_ker, _, _, _ = _build_and_solve(ctx, n, "raw", True)
    np.testing.assert_allclose(y_ker, y_xla, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mesh", ["ctx1", "ctx22"])
def test_fused_solve_bf16_allclose_vs_xla_same_codec(mesh, request):
    ctx = request.getfixturevalue(mesh)
    n = 64
    y_xla, _, _, _ = _build_and_solve(ctx, n, "bf16", False)
    y_ker, _, _, _ = _build_and_solve(ctx, n, "bf16", True)
    np.testing.assert_allclose(y_ker, y_xla, rtol=1e-4, atol=1e-4)


def test_raw_kernel_path_allclose_vs_resident(ctx1):
    n = 64
    y_res = _resident_solve(ctx1, n)
    y_ker, _, _, _ = _build_and_solve(ctx1, n, "raw", True)
    np.testing.assert_allclose(y_ker, y_res, rtol=1e-4, atol=1e-4)


def test_bf16_kernel_halves_solve_h2d(ctx1):
    """Stored-form bf16 shipping: solve-phase H2D <= 0.55x the fp32-decode
    baseline at equal accuracy (the PR acceptance bound)."""
    n = 64
    reset_stream_stats()
    y_xla, rep_x, h2d_xla, panels_xla = _build_and_solve(ctx1, n, "bf16", False)
    y_ker, rep_k, h2d_ker, panels_ker = _build_and_solve(ctx1, n, "bf16", True)
    np.testing.assert_allclose(y_ker, y_xla, rtol=1e-4, atol=1e-4)
    # per-pass comparison: iteration counts may differ by an early stop when
    # the kernel's exact residual moments cancel to zero at convergence
    per_pass_xla = h2d_xla / panels_xla
    per_pass_ker = h2d_ker / panels_ker
    assert per_pass_ker <= 0.55 * per_pass_xla
    assert h2d_ker <= 0.55 * h2d_xla * (panels_ker / panels_xla) + 1e-9


def test_bytes_h2d_saved_counter(ctx1):
    reset_stream_stats()
    saved0 = stream_stats().bytes_h2d_saved
    _build_and_solve(ctx1, 64, "bf16", True)
    st = stream_stats()
    assert st.bytes_h2d_saved > saved0
    # raw-codec kernel path ships fp32 either way: nothing saved
    reset_stream_stats()
    _build_and_solve(ctx1, 64, "raw", True)
    assert stream_stats().bytes_h2d_saved == 0


def test_fused_iteration_is_one_panel_pass(ctx1):
    """Each fused solve iteration streams the P2 scratch exactly once."""
    n = 64
    a = jax.device_put(_sym(n, 0), ctx1.sharding(ctx1.matrix_spec))
    op = chain_product(ctx1, a, 3, oocore=True, tile_codec="bf16",
                       use_gemm_kernel=True)
    b = _rng(100).normal(size=(n, 4)).astype(np.float32)
    b = jax.device_put(b, ctx1.sharding(ctx1.rowblock_spec))
    n_panels = n // int(np.lcm(int(op.p2.panel_rows), ctx1.n_row_shards))
    st = stream_stats()
    p0 = st.panels
    y, rep = solve(ctx1, op, b, SolverSpec(), fixed_q=5)
    panels = stream_stats().panels - p0
    op.release_scratch()
    # one chi pass (P1) + one pass per iteration (P2), nothing else
    assert panels == n_panels * (rep.iterations + 1)


def _panel_store(n=64):
    from repro.store import TileStore

    store = TileStore.create(None, n=n, grid=4)
    return store.put_snapshot("a", _sym(n, 0))


def test_pinned_host_fallback_on_cpu(ctx1):
    """A CPU sharding stages pageable: the device already is host memory, so
    panels flow straight to it and pipeline.pinned is False."""
    from repro.store import PanelPipeline

    n = 64
    h = _panel_store(n)
    sharding = ctx1.sharding(ctx1.matrix_spec)
    with PanelPipeline([h], range(0, n, 16), 16, sharding=sharding) as pipe:
        seen = 0
        for r0, (panel,) in pipe:
            assert panel.shape == (16, n)
            seen += 1
        assert seen == 4
        assert pipe.pinned is False


class _FakeTpuSharding:
    """A sharding whose devices report the TPU platform (steers the staging
    rule without a chip); ``memory_kind`` records where a put would land."""

    device_set = (SimpleNamespace(platform="tpu"),)

    def __init__(self, memory_kind="device"):
        self.memory_kind = memory_kind

    def with_memory_kind(self, kind):
        return _FakeTpuSharding(kind)


def test_pinned_host_staging_on_tpu():
    """A TPU sharding stages every panel through pinned host memory first."""
    from repro.store import PanelPipeline

    n = 64
    h = _panel_store(n)
    kinds = []

    def put(x, sharding):
        kinds.append(sharding.memory_kind)
        return np.asarray(x)

    with PanelPipeline([h], range(0, n, 16), 16, sharding=_FakeTpuSharding(),
                       device_put=put) as pipe:
        assert pipe.pinned is True
        assert sum(1 for _ in pipe) == 4
    assert kinds == ["pinned_host", "device"] * 4


def test_pinned_put_failure_raises_on_tpu():
    """A failing pinned put on TPU is a staging fault: it surfaces, and the
    pipeline does not quietly switch to pageable staging."""
    from repro.store import PanelPipeline

    n = 64
    h = _panel_store(n)

    def put(x, sharding):
        if sharding.memory_kind == "pinned_host":
            raise RuntimeError("pinned DMA fault")
        return np.asarray(x)

    with PanelPipeline([h], range(0, n, 16), 16, sharding=_FakeTpuSharding(),
                       device_put=put) as pipe:
        with pytest.raises(RuntimeError, match="pinned DMA fault"):
            list(pipe)


@pytest.mark.slow
def test_stream_gemm_blocked_grid_bitwise_bf16(ctx1):
    """Heavier grid: blocked M/N with whole K, bf16 bits, still bitwise vs
    the host-decoded XLA dot (per-output-tile reduction order matches)."""
    r = _rng(7)
    a_bits = _bf16_bits(r.normal(size=(256, 128)).astype(np.float32))
    b = r.normal(size=(128, 256)).astype(np.float32)
    init = r.normal(size=(256, 256)).astype(np.float32)
    got = stream_gemm(jnp.asarray(a_bits), jnp.asarray(b), jnp.asarray(init),
                      bm=64, bk=128, bn=64)
    want = _gemm_step(jnp.asarray(init), jnp.asarray(_host_decode(a_bits)),
                      jnp.asarray(b))
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_fused_solve_2x2_bf16_end_to_end_scores(ctx22):
    """2x2 mesh, bf16 scratch, kernel path vs same-codec XLA path at a
    larger n -- the full distributed epilogue (psum moments, row slicing)."""
    n = 128
    y_xla, _, _, _ = _build_and_solve(ctx22, n, "bf16", False, d=4, q=6)
    y_ker, _, _, _ = _build_and_solve(ctx22, n, "bf16", True, d=4, q=6)
    np.testing.assert_allclose(y_ker, y_xla, rtol=1e-4, atol=1e-4)

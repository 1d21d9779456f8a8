"""Main-path kernels and programs compile for a TPU v5e chip.

The TPU compiler is installed without a chip: each test describes a v5e
(``v5e:2x2``) topology and compiles for its devices with ``interpret=False``
chosen by :mod:`repro.kernels.dispatch`, at the widths the write, read and
out-of-core paths run (n=16384 resident, n=8192 out-of-core with 1024-row
panels, k_RP=17, top-20 queries over 256-row embedding panels).  A compile
that passes is not a chip run: it shows the Mosaic and XLA compilers accept
the block shapes and that the resident chain fits one chip's 16 GB.

The topology is described inside a module fixture, never at import time:
only one process at a time may hold the TPU library.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.distmatrix import make_context
from repro.kernels.block_matmul import block_matmul
from repro.kernels.cad_score import cad_scores_tile
from repro.kernels.edge_projection import edge_projection
from repro.kernels.emb_query import panel_topk_update
from repro.kernels.stream_gemm import fused_panel_matvec, stream_gemm

N_RESIDENT, N_OOCORE, PANEL, K_RP, TOPK, EMB_PANEL = 16384, 8192, 1024, 17, 20, 256
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _ctx(topo, rows, cols):
    devs = np.array(topo.devices[: rows * cols]).reshape(rows, cols)
    return make_context(Mesh(devs, ("data", "model")))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_compiled(fn, *args):
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


def _s(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---------------------------------------------------------------------------
# kernels, one chip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.uint16], ids=["fp32", "bf16bits"])
def test_stream_gemm_chain_step(one_chip, dtype):
    """One out-of-core chain GEMM step: acc + block @ right panel."""
    _kernel_compiled(
        lambda a, b, c: stream_gemm(a, b, c, sign=-1.0),
        _s(one_chip, (PANEL, PANEL), dtype),
        _s(one_chip, (PANEL, N_OOCORE), dtype),
        _s(one_chip, (PANEL, N_OOCORE)),
    )


def test_stream_gemm_skinny_matvec(one_chip):
    """The streamed chi build: a panel against K_RP = 17 lanes."""
    _kernel_compiled(
        stream_gemm,
        _s(one_chip, (PANEL, N_OOCORE), jnp.uint16),
        _s(one_chip, (N_OOCORE, K_RP)),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.uint16], ids=["fp32", "bf16bits"])
def test_fused_panel_matvec(one_chip, dtype):
    _kernel_compiled(
        fused_panel_matvec,
        _s(one_chip, (PANEL, N_OOCORE), dtype),
        _s(one_chip, (N_OOCORE, K_RP)),
        _s(one_chip, (PANEL, K_RP)),
        _s(one_chip, (PANEL, K_RP)),
    )


@pytest.mark.parametrize(
    "dtype,largest,corrected",
    [(jnp.float32, True, False), (jnp.uint16, False, True)],
    ids=["top_anomalies", "corrected_neighbors_bf16"],
)
def test_panel_topk_update(one_chip, dtype, largest, corrected):
    """The read path's masked top-20 merge over one embedding panel."""
    _kernel_compiled(
        lambda rv, ri, zq, zp, iq, ip, vol, r0, ex: panel_topk_update(
            rv, ri, zq, zp, iq, ip, vol, r0, ex,
            topk=TOPK, largest=largest, corrected=corrected,
        ),
        _s(one_chip, (1, TOPK)),
        _s(one_chip, (1, TOPK), jnp.int32),
        _s(one_chip, (1, K_RP)),
        _s(one_chip, (EMB_PANEL, K_RP), dtype),
        _s(one_chip, (1, 1)),
        _s(one_chip, (1, EMB_PANEL)),
        _s(one_chip, ()),
        _s(one_chip, (), jnp.int32),
        _s(one_chip, (1, 1), jnp.int32),
    )


def test_cad_scores_tile(one_chip):
    n = N_RESIDENT
    _kernel_compiled(
        cad_scores_tile,
        *[_s(one_chip, (n, n))] * 2,
        *[_s(one_chip, (n, K_RP))] * 4,
        _s(one_chip, ()),
        _s(one_chip, ()),
    )


def test_edge_projection(one_chip):
    _kernel_compiled(
        lambda a: edge_projection(a, seed=0, k=K_RP),
        _s(one_chip, (N_RESIDENT, N_RESIDENT)),
    )


def test_block_matmul(one_chip):
    a = _s(one_chip, (N_RESIDENT, N_RESIDENT))
    _kernel_compiled(block_matmul, a, a)


# ---------------------------------------------------------------------------
# the shard_map programs that call the kernels (trace-time TPU choice)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("grid", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_oochain_kernel_gemm_program(topo, grid):
    from repro.core.oochain import _kernel_gemm_program

    ctx = _ctx(topo, *grid)
    m = NamedSharding(ctx.mesh, ctx.matrix_spec)
    prog = _kernel_gemm_program(ctx, True, "uint16", "uint16", PANEL, N_OOCORE)
    text = prog.lower(
        _s(m, (PANEL, N_OOCORE)),
        _s(m, (PANEL, PANEL), jnp.uint16),
        _s(m, (PANEL, N_OOCORE), jnp.uint16),
    ).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("grid", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "matvec"])
def test_streamed_solve_panel_program(topo, grid, fused):
    from repro.core.solvers.driver import _kernel_panel_program

    ctx = _ctx(topo, *grid)
    rep = NamedSharding(ctx.mesh, P(None, None))
    args = [
        _s(NamedSharding(ctx.mesh, P()), (), jnp.int32),
        _s(NamedSharding(ctx.mesh, ctx.matrix_spec), (PANEL, N_OOCORE), jnp.uint16),
        _s(rep, (N_OOCORE, K_RP)),
    ]
    if fused:
        args.append(_s(rep, (N_OOCORE, K_RP)))
    prog = _kernel_panel_program(ctx, PANEL, N_OOCORE, K_RP, "uint16", fused)
    assert "tpu_custom_call" in prog.lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "grid,schedule",
    [((1, 1), "cannon"), ((2, 2), "cannon"), ((2, 2), "summa")],
    ids=["1x1-cannon", "2x2-cannon", "2x2-summa"],
)
def test_resident_chain_fits_each_chip(topo, grid, schedule):
    """The n=16384, d=6 resident chain as one program fits a v5e's 16 GB
    HBM on each chip of the one-chip and the 2x2 meshes."""
    from repro.core.chain import _resident_chain

    ctx = _ctx(topo, *grid)
    a = _s(NamedSharding(ctx.mesh, ctx.matrix_spec), (N_RESIDENT, N_RESIDENT))
    compiled = _compile(
        lambda a: _resident_chain(
            ctx, a, 6, schedule=schedule, dtype=jnp.float32, deflate=True,
            fuse_l=False, use_kernel=False, prefetch_depth=None, level_sink=None,
        ),
        a,
    )
    m = compiled.memory_analysis()
    used = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB"


@pytest.mark.parametrize("grid", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_factored_chain_and_solve_fit_each_chip(topo, grid):
    """The n=16384, d=6 factored build (five Cannon squarings, six T levels
    kept) and its solve program (the levels, the degrees and the adjacency
    as operands of one while_loop) compile and fit each chip."""
    from repro.core.chain import factored_chain
    from repro.core.solvers.driver import _resident_program

    ctx = _ctx(topo, *grid)
    mat = NamedSharding(ctx.mesh, ctx.matrix_spec)
    a = _s(mat, (N_RESIDENT, N_RESIDENT))
    build = _compile(lambda a: factored_chain(ctx, a, 6, schedule="cannon").t_levels, a)
    chi = _s(NamedSharding(ctx.mesh, ctx.rowblock_spec), (N_RESIDENT, K_RP))
    ops = (tuple([a] * 6), _s(NamedSharding(ctx.mesh, P(None)), (N_RESIDENT,)), a)
    scalar = NamedSharding(ctx.mesh, P())
    prog = _resident_program(ctx, "richardson", True, chi, None, 6)
    solve = prog.lower(
        ops, chi, chi, _s(scalar, ()), _s(scalar, (), jnp.int32), _s(scalar, ())
    ).compile()
    for compiled in (build, solve):
        m = compiled.memory_analysis()
        used = (
            m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes
        )
        assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB"

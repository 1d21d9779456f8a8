"""Pluggable solver subsystem: driver, methods, stopping, telemetry.

The acceptance bars (ISSUE 5): Chebyshev and adaptive Richardson stay
allclose (rtol <= 1e-4) to the fixed-q Richardson baseline on 1x1 AND 2x2
meshes, resident and out-of-core; and at equal tolerance Chebyshev reads
strictly fewer (>= 1.5x fewer) scratch bytes than Richardson on an
out-of-core solve.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    CommuteConfig,
    SolverSpec,
    chain_product,
    detect_anomalies,
    estimate_rho,
    estimate_solution,
    residual_norm,
    reset_stream_stats,
    solve,
    stream_stats,
)
from repro.core import laplacian as lap
from repro.core.solvers import SolveReport, iters_from_delta
from repro.core.solvers.driver import deflate_constant
from repro.graphs import gmm_graph_sequence
from repro.store import TileStore


@pytest.fixture(params=["ctx1", "ctx22"])
def ctx(request):
    return request.getfixturevalue(request.param)


def _clustered(ctx, n=64, seed=0):
    """GMM similarity graph: well-separated clusters -> lambda_2 near 1, so
    the solve actually needs iterations (rho(S^{2^d}) stays substantial)."""
    return gmm_graph_sequence(ctx, n=n, seed=seed).a1


def _rhs(ctx, n, k=4, seed=0):
    b = np.random.default_rng(seed).normal(size=(n, k)).astype(np.float32)
    b -= b.mean(0, keepdims=True)
    return ctx.put_rowblock(b)


# ---------------------------------------------------------------------------
# spec / contract
# ---------------------------------------------------------------------------


def test_delta_derives_paper_iteration_bound():
    """q = ceil(log 1/delta): the paper default delta=1e-4 gives q=10, i.e.
    9 refinement steps -- matching the CommuteConfig default q."""
    assert iters_from_delta(1e-4) == 10
    assert SolverSpec(delta=1e-4).max_steps() == 9
    assert SolverSpec(delta=0.5).max_steps() == 1
    # precedence: explicit cap > delta > tolerance cap > fixed q
    assert SolverSpec(max_iters=3, delta=1e-4).max_steps() == 3
    assert SolverSpec(tolerance=1e-6).max_steps() == 300
    assert SolverSpec().max_steps(fixed_q=7) == 6
    with pytest.raises(ValueError, match="delta"):
        SolverSpec(delta=1.5)
    with pytest.raises(ValueError, match="solver"):
        SolverSpec(method="conjugate_gradient")


def test_commute_config_builds_spec():
    cfg = CommuteConfig(solver="chebyshev", solver_tol=1e-5, delta=1e-3)
    spec = cfg.solver_spec()
    assert spec.method == "chebyshev"
    assert spec.tolerance == 1e-5
    assert spec.max_steps() == iters_from_delta(1e-3) - 1


def test_rho_cached_on_operator_and_survives_pytree(ctx1):
    a = _clustered(ctx1)
    op = chain_product(ctx1, a, d_len=4, schedule="xla")
    assert op.rho is not None and 0.0 < op.rho < 1.0
    leaves, treedef = jax.tree_util.tree_flatten(op)
    op2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert op2.rho == op.rho and op2.prefetch_depth == op.prefetch_depth
    # the direct estimator agrees with the build-time cache (same seed/iters)
    assert estimate_rho(ctx1, op.p2) == pytest.approx(op.rho)


def test_fixed_q_shim_matches_driver_contract(ctx1):
    """estimate_solution(q) is the fixed-iteration driver: q=1 returns chi
    exactly (zero refinement steps), and the report counts q-1 mat-vecs."""
    from repro.core.distmatrix import matmul_rowblock

    a = _clustered(ctx1)
    op = chain_product(ctx1, a, d_len=4, schedule="xla")
    b = _rhs(ctx1, 64)
    chi = deflate_constant(ctx1, matmul_rowblock(ctx1, op.p1, b))
    np.testing.assert_array_equal(
        np.asarray(estimate_solution(ctx1, op, b, q_iters=1)), np.asarray(chi)
    )
    _, rep = solve(ctx1, op, b, SolverSpec(), fixed_q=6)
    assert rep.iterations == 5 and rep.converged and rep.method == "richardson"
    with pytest.raises(ValueError, match="q must be"):
        estimate_solution(ctx1, op, b, q_iters=0)


# ---------------------------------------------------------------------------
# solver equivalence: adaptive richardson + chebyshev vs fixed-q baseline,
# 1x1 AND 2x2 meshes, resident AND out-of-core
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("storage", ["resident", "oocore"])
def test_methods_allclose_to_fixed_q_baseline(ctx, storage):
    n, d, tol = 64, 5, 3e-5
    a = _clustered(ctx, n)
    if storage == "oocore":
        store = TileStore.create(None, n=n, grid=8)
        src = store.put_snapshot("a", np.asarray(a))
    else:
        src = a
    op = chain_product(ctx, src, d, schedule="xla", oocore=storage == "oocore")
    b = _rhs(ctx, n)

    sols, reports = {}, {}
    for method in ("richardson", "chebyshev", "cg"):
        sols[method], reports[method] = solve(
            ctx, op, b, SolverSpec(method=method, tolerance=tol)
        )
        assert reports[method].converged, reports[method]
        assert reports[method].streamed == (storage == "oocore")
    # fixed-q baseline at the adaptive Richardson iteration count
    q_fix = reports["richardson"].iterations + 1
    ref = np.asarray(estimate_solution(ctx, op, b, q_fix))
    for method, x in sols.items():
        np.testing.assert_allclose(
            np.asarray(x), ref, rtol=1e-4, atol=1e-3, err_msg=method
        )
    # the accelerators actually accelerated (rho is large on this graph)
    assert reports["chebyshev"].iterations < reports["richardson"].iterations
    assert reports["cg"].iterations < reports["chebyshev"].iterations
    op.release_scratch()


def test_chebyshev_cuts_oocore_iterations_and_scratch_bytes(ctx1):
    """Acceptance: at equal tolerance, Chebyshev reduces BOTH the iteration
    count and stream_stats().bytes_read of an out-of-core solve by >= 1.5x,
    and strictly reads fewer scratch bytes than Richardson."""
    n, d, tol = 64, 4, 1e-5
    store = TileStore.create(None, n=n, grid=8)
    h = store.put_snapshot("a", np.asarray(_clustered(ctx1, n)))
    op = chain_product(ctx1, h, d, oocore=True)
    b = _rhs(ctx1, n)

    bread, reports = {}, {}
    for method in ("richardson", "chebyshev"):
        reset_stream_stats()
        _, rep = solve(ctx1, op, b, SolverSpec(method=method, tolerance=tol))
        bread[method] = stream_stats().bytes_read
        reports[method] = rep
        assert rep.converged, rep
        # the report's own counters agree with the global stats delta
        assert rep.bytes_read == bread[method]
    op.release_scratch()
    r, c = reports["richardson"], reports["chebyshev"]
    assert r.iterations >= 1.5 * c.iterations, (r.iterations, c.iterations)
    assert bread["richardson"] >= 1.5 * bread["chebyshev"], bread
    assert bread["chebyshev"] < bread["richardson"]  # strictly fewer


def test_chebyshev_solver_batch_replays_bitwise(ctx1):
    """Iteration batching composes with Chebyshev: CachingHandle replays are
    bitwise, so solver_batch cannot change the accelerated solution either."""
    n = 64
    store = TileStore.create(None, n=n, grid=8)
    h = store.put_snapshot("a", np.asarray(_clustered(ctx1, n)))
    op = chain_product(ctx1, h, 4, oocore=True)
    b = _rhs(ctx1, n)
    sols, reads = {}, {}
    for batch in (1, 4):
        reset_stream_stats()
        x, _ = solve(
            ctx1, op, b, SolverSpec(method="chebyshev", tolerance=1e-5),
            solver_batch=batch,
        )
        sols[batch], reads[batch] = np.asarray(x), stream_stats().bytes_read
    op.release_scratch()
    np.testing.assert_array_equal(sols[1], sols[4])
    assert reads[4] < reads[1]


def test_scores_allclose_and_telemetry_end_to_end(ctx1):
    """End-to-end acceptance: chebyshev-to-tolerance scores allclose
    (rtol <= 1e-4) to the fixed-q Richardson baseline, and the CADResult
    carries both endpoints' SolveReports."""
    seq = gmm_graph_sequence(ctx1, n=64, seed=3, inject_p=0.02)
    base = CommuteConfig(eps_rp=1e-2, d=5, q=61, schedule="xla", k_override=4)
    cheb = CommuteConfig(
        eps_rp=1e-2, d=5, q=61, schedule="xla", k_override=4,
        solver="chebyshev", solver_tol=1e-5,
    )
    res_base = detect_anomalies(ctx1, seq.a1, seq.a2, base, top_k=5)
    res_cheb = detect_anomalies(ctx1, seq.a1, seq.a2, cheb, top_k=5)
    np.testing.assert_allclose(
        np.asarray(res_cheb.scores), np.asarray(res_base.scores),
        rtol=1e-4, atol=1e-3,
    )
    assert len(res_cheb.solve_reports) == 2
    for rep in res_cheb.solve_reports:
        assert isinstance(rep, SolveReport)
        assert rep.method == "chebyshev" and rep.converged
        assert rep.iterations < 60  # far under the fixed-q worst case
    for rep in res_base.solve_reports:
        assert rep.method == "richardson" and rep.iterations == 60


# ---------------------------------------------------------------------------
# residual_norm over a store-backed Laplacian (adaptive stopping oocore)
# ---------------------------------------------------------------------------


def test_residual_norm_streamed_matches_resident(ctx):
    n = 64
    a = _clustered(ctx, n)
    deg = lap.degrees(ctx, a)
    l_mat = lap.laplacian(ctx, a, deg)
    store = TileStore.create(None, n=n, grid=8)
    l_handle = store.put_snapshot("L", np.asarray(l_mat))

    op = chain_product(ctx, a, d_len=6, schedule="xla")
    b = _rhs(ctx, n)
    x = estimate_solution(ctx, op, b, q_iters=8)
    r_res = float(residual_norm(ctx, l_mat, x, b))
    r_str = float(residual_norm(ctx, l_handle, x, b, prefetch_depth=2))
    assert r_str == pytest.approx(r_res, rel=1e-5)
    # sanity: the metric is meaningful (solver actually reduced the residual)
    assert r_res < 0.5


# ---------------------------------------------------------------------------
# release_scratch diagnosability
# ---------------------------------------------------------------------------


def test_release_scratch_warns_on_store_failure(ctx1, monkeypatch):
    n = 32
    store = TileStore.create(None, n=n, grid=4)
    h = store.put_snapshot("a", np.asarray(_clustered(ctx1, n)))
    op = chain_product(ctx1, h, 3, oocore=True)
    work = op.p1.store

    def wedged(snap_id):
        raise OSError("scratch dir wedged")

    monkeypatch.setattr(work, "remove_snapshot", wedged)
    with pytest.warns(RuntimeWarning, match="scratch"):
        op.release_scratch()
    monkeypatch.undo()
    op.release_scratch()  # real removal still works afterwards
    assert not [s for s in work.snapshot_ids if "P1" in s or "P2" in s]


def test_release_scratch_raises_on_unexpected_error(ctx1, monkeypatch):
    """Only the expected store errors are swallowed -- a genuine bug (wrong
    type, attribute error) must surface, not vanish into a warning."""
    n = 32
    store = TileStore.create(None, n=n, grid=4)
    h = store.put_snapshot("a", np.asarray(_clustered(ctx1, n)))
    op = chain_product(ctx1, h, 3, oocore=True)

    def buggy(snap_id):
        raise TypeError("programming error")

    monkeypatch.setattr(op.p1.store, "remove_snapshot", buggy)
    with pytest.raises(TypeError):
        op.release_scratch()


# ---------------------------------------------------------------------------
# warm starts: y0 seeds the solve, cold and warm share one compiled program
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["richardson", "chebyshev", "cg"])
def test_warm_start_from_solution_converges_immediately(ctx, method):
    """Seeding y0 with the converged solution collapses the solve to <= 2
    steps (the first measured residual is already under tolerance) while the
    warm solution stays allclose to the cold one -- warm starting changes the
    iteration count, never the answer."""
    a = _clustered(ctx)
    op = chain_product(ctx, a, d_len=4, schedule="xla")
    b = _rhs(ctx, 64)
    tol = 1e-5
    cold, rep_c = solve(ctx, op, b, SolverSpec(method=method, tolerance=tol))
    warm, rep_w = solve(ctx, op, b, SolverSpec(method=method, tolerance=tol), y0=cold)
    assert rep_c.converged and not rep_c.warm_start
    assert rep_w.converged and rep_w.warm_start
    assert rep_w.iterations <= 2 < rep_c.iterations
    np.testing.assert_allclose(
        np.asarray(warm), np.asarray(cold), rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("method", ["chebyshev", "cg"])
def test_warm_start_streamed(ctx1, method):
    """Out-of-core warm start: the streamed solve accepts y0 too, and a
    solve seeded with the resident solution converges in a few passes.

    The seed only just meets the tolerance on the resident operator (cold
    chebyshev stops at 9.96e-6); the streamed P2 differs from the resident
    one by rounding (5e-7 max), so the seed starts at 1.14e-5 on the
    streamed operator.  A restarted Chebyshev recurrence contracts by about
    rho / (2 - rho) = 0.92 per early step, which takes 3 passes; CG takes 1.
    """
    n = 64
    store = TileStore.create(None, n=n, grid=8)
    a = _clustered(ctx1, n)
    h = store.put_snapshot("a", np.asarray(a))
    op_res = chain_product(ctx1, a, 4, schedule="xla")
    op_str = chain_product(ctx1, h, 4, oocore=True)
    b = _rhs(ctx1, n)
    cold, _ = solve(ctx1, op_res, b, SolverSpec(method=method, tolerance=1e-5))
    warm, rep = solve(
        ctx1, op_str, b, SolverSpec(method=method, tolerance=1e-5), y0=cold
    )
    op_str.release_scratch()
    assert rep.streamed and rep.warm_start and rep.converged
    assert rep.iterations <= {"chebyshev": 3, "cg": 2}[method]
    np.testing.assert_allclose(
        np.asarray(warm), np.asarray(cold), rtol=1e-4, atol=1e-5
    )


def test_warm_start_shape_mismatch_raises(ctx1):
    a = _clustered(ctx1)
    op = chain_product(ctx1, a, d_len=4, schedule="xla")
    b = _rhs(ctx1, 64, k=4)
    bad = _rhs(ctx1, 64, k=3)
    with pytest.raises(ValueError, match="warm start"):
        solve(ctx1, op, b, SolverSpec(tolerance=1e-5), y0=bad)


# ---------------------------------------------------------------------------
# adaptive Chebyshev interval (Manteuffel-style)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("storage", ["resident", "oocore"])
def test_chebyshev_adapts_underestimated_interval(ctx1, storage):
    """An operator carrying a badly underestimated rho used to stall or
    diverge Chebyshev; the adaptive interval grows it from the measured
    contraction and the solve still converges to the same answer.  A correct
    rho must NOT adapt (rho_final == rho)."""
    import dataclasses

    n, tol = 64, 1e-5
    a = _clustered(ctx1, n)
    if storage == "oocore":
        store = TileStore.create(None, n=n, grid=8)
        src = store.put_snapshot("a", np.asarray(a))
    else:
        src = a
    op = chain_product(ctx1, src, 4, schedule="xla", oocore=storage == "oocore")
    b = _rhs(ctx1, n)
    ref, rep_ref = solve(ctx1, op, b, SolverSpec(method="chebyshev", tolerance=tol))
    assert rep_ref.converged
    assert rep_ref.rho_final == pytest.approx(rep_ref.rho)  # no false trigger

    op_lo = dataclasses.replace(op, rho=0.5 * op.rho)
    x, rep = solve(ctx1, op_lo, b, SolverSpec(method="chebyshev", tolerance=tol))
    op.release_scratch()
    assert rep.converged, rep
    assert rep.rho_final is not None and rep.rho_final > rep.rho
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


# ---------------------------------------------------------------------------
# non-convergence is reported, not hidden
# ---------------------------------------------------------------------------


def test_unreachable_tolerance_reports_not_converged(ctx1):
    a = _clustered(ctx1)
    op = chain_product(ctx1, a, d_len=4, schedule="xla")
    b = _rhs(ctx1, 64)
    _, rep = solve(
        ctx1, op, b, SolverSpec(method="richardson", tolerance=1e-6, max_iters=3)
    )
    assert rep.iterations == 3 and not rep.converged
    assert rep.max_iters == 3 and rep.residual > 1e-6


@pytest.mark.parametrize("storage", ["resident", "oocore"])
def test_zero_iteration_budget_reports_no_residual(ctx1, storage):
    """max_iters=0 measures nothing: the report must say NaN residual and
    converged=False (it used to claim residual 0.0 / converged=True)."""
    import math

    n = 32
    a = _clustered(ctx1, n)
    if storage == "oocore":
        store = TileStore.create(None, n=n, grid=4)
        src = store.put_snapshot("a", np.asarray(a))
    else:
        src = a
    op = chain_product(ctx1, src, 3, schedule="xla", oocore=storage == "oocore")
    b = _rhs(ctx1, n)
    y, rep = solve(
        ctx1, op, b, SolverSpec(method="richardson", tolerance=1e-5, max_iters=0)
    )
    op.release_scratch()
    assert rep.iterations == 0
    assert math.isnan(rep.residual)
    assert not rep.converged
    assert rep.residuals == ()
    assert np.asarray(y).shape == (n, 4)  # still returns chi


# ---------------------------------------------------------------------------
# residual-history ring buffer
# ---------------------------------------------------------------------------


def test_residual_history_rotates_past_ring_capacity(ctx1, monkeypatch):
    """Runs longer than the ring capacity must return the last CAP residuals
    in chronological order -- the raw buffer is rotated by iters mod cap
    (it used to come back unrotated, splicing newest and oldest entries)."""
    from repro.core.solvers import driver as drv

    a = _clustered(ctx1)
    op = chain_product(ctx1, a, d_len=4, schedule="xla")
    b = _rhs(ctx1, 64)
    spec = SolverSpec(method="richardson", tolerance=1e-30, max_iters=20)
    _, full = solve(ctx1, op, b, spec)
    assert len(full.residuals) == 20
    assert full.residuals[-1] == pytest.approx(full.residual)

    # RES_HIST_CAP is part of the program cache key, so shrinking it compiles
    # a fresh program rather than replaying the stale 512-slot one.
    monkeypatch.setattr(drv, "RES_HIST_CAP", 8)
    _, small = solve(ctx1, op, b, spec)
    assert len(small.residuals) == 8
    np.testing.assert_array_equal(
        np.asarray(small.residuals), np.asarray(full.residuals[-8:])
    )
    assert small.residuals[-1] == pytest.approx(small.residual)


# ---------------------------------------------------------------------------
# full bench grid (weekly CI): richardson vs chebyshev x resident/oocore x mesh
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_bench_solver_grid_passes():
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.bench_solver import run

    res = run(n=96, d=4, tol=1e-5, out=lambda *a, **k: None)
    assert res["verdicts"], "no oocore verdicts produced"
    assert res["all_pass"], res["verdicts"]

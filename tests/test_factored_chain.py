"""The factored chain operator: the d - 1 squarings kept as T levels, applied
as factors by the solve, in place of the materialized P1 / P2.

It must be the same operator as the materialized one (P1 x, P2 x and the
fixed-q solution, each checked on its own, since the solve loop alone can
hide a mismatch); ``commute_time_embedding`` must take it exactly where its
rule says; Chebyshev alone measures a rho for it; a sequence compiles its
solve once; and retiring the operator frees its levels."""

import jax
import numpy as np
import pytest

import repro.core.chain as chain_mod
import repro.core.embedding as emb_mod
import repro.core.solvers.power as power
from repro.core import (
    CommuteConfig,
    SequenceDetector,
    chain_product,
    commute_time_embedding,
    detect_anomalies,
    factored_chain,
    program_cache_stats,
)
from repro.core.chain import PASS_COST_DIVISOR, factored_p1, use_factored
from repro.core.distmatrix import matmul_rowblock
from repro.core.solvers import SolverSpec, solve
from repro.core.solvers.driver import _factored_matvec
from repro.graphs.synthetic import gmm_snapshot_sequence
from repro.obs import REGISTRY
from repro.store import TileStore

N = 512  # n / PASS_COST_DIVISOR = 4: a q of 3 takes the factored form, 4 does not
Q_FACTORED = 3


def _snapshots(ctx, n=N, t_steps=2, seed=3):
    """GMM similarity graphs: lambda_2 of S near 0.995, so every T level of a
    six-level chain still carries weight."""
    snaps = gmm_snapshot_sequence(ctx, n, max(t_steps, 2), seed=seed).snapshots()
    return [next(snaps) for _ in range(t_steps)]


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(params=["ctx1", "ctx22"])
def ctx(request):
    return request.getfixturevalue(request.param)


@pytest.mark.parametrize("deflate", [True, False])
@pytest.mark.parametrize("d", [1, 3, 6])
def test_factored_is_the_materialized_operator(ctx, d, deflate):
    """P1 x, P2 x and the fixed-q solution agree to float32 rounding, and
    the factored build counts its d - 1 GEMMs."""
    (a,) = _snapshots(ctx, t_steps=1)
    x = ctx.put_rowblock(np.random.default_rng(d).normal(size=(N, 8)).astype(np.float32))
    mat = chain_product(ctx, a, d, schedule="xla", deflate=deflate)
    m0 = REGISTRY.snapshot()
    fac = factored_chain(ctx, a, d, schedule="xla", deflate=deflate)
    moved = REGISTRY.delta(m0)
    assert fac.factored and fac.p1 is None and fac.p2 is None and len(fac.t_levels) == d
    leaves, treedef = jax.tree_util.tree_flatten(fac)
    assert jax.tree_util.tree_unflatten(treedef, leaves).t_levels == fac.t_levels
    assert moved.get("chain.gemm_flops", 0.0) == (d - 1) * 2.0 * N**3
    assert moved["chain.builds"] == 1.0

    ops = (fac.t_levels, fac.deg, fac.adj)
    assert _rel(factored_p1(ctx, fac.t_levels, fac.deg, x), matmul_rowblock(ctx, mat.p1, x)) < 2e-5
    assert _rel(_factored_matvec(ctx, ops, x), matmul_rowblock(ctx, mat.p2, x)) < 2e-5
    y_mat, rep_mat = solve(ctx, mat, x, SolverSpec(), fixed_q=10, deflate=deflate)
    y_fac, rep_fac = solve(ctx, fac, x, SolverSpec(), fixed_q=10, deflate=deflate)
    assert rep_fac.iterations == rep_mat.iterations == 9
    assert _rel(y_fac, y_mat) < 1e-4


def _small_limit(monkeypatch):
    # d + 4 shards of N x N float32 do not fit a 1 MB device
    monkeypatch.setattr(chain_mod, "_device_bytes_limit", lambda ctx: 2**20)


def _streamed(ctx1, a):
    store = TileStore.create(None, n=N, grid=4)
    return store.put_snapshot("t0", np.asarray(a))


# case -> (config changes, how the snapshot is handed over, expected form)
SELECTION = {
    "plain_fixed_q": ({}, None, True),
    "oocore": ({"oocore": True}, None, False),
    "step_cap_at_n_over_c": ({"q": N // PASS_COST_DIVISOR}, None, False),
    "tolerance_cap": ({"solver_tol": 1e-6}, None, False),
    "chebyshev_power_iterations": ({"solver": "chebyshev"}, None, False),
    "memory_would_not_fit": ({}, _small_limit, False),
    "streamed_adjacency": ({}, _streamed, False),
}


@pytest.mark.parametrize("case", sorted(SELECTION))
def test_selection_rule(ctx1, case, monkeypatch):
    changes, hand, factored = SELECTION[case]
    cfg = CommuteConfig(**{**dict(eps_rp=1e-2, d=3, q=Q_FACTORED, schedule="xla",
                                  k_override=4), **changes})
    (a,) = _snapshots(ctx1, t_steps=1)
    if hand is _small_limit:
        hand(monkeypatch)
    elif hand is not None:
        a = hand(ctx1, a)
    m0 = REGISTRY.snapshot()
    emb = commute_time_embedding(ctx1, a, cfg)
    assert emb.op.factored is factored
    assert REGISTRY.delta(m0).get("chain.factored_builds", 0.0) == float(factored)
    assert REGISTRY.value("chain.factored_builds", None) is not None
    emb.op.release_scratch()


def test_a_given_operator_is_used_as_it_is(ctx1):
    """An operator passed in serves many solves: nothing is built, so no
    factored build is counted, and the solve applies the operator given."""
    cfg = CommuteConfig(eps_rp=1e-2, d=3, q=Q_FACTORED, schedule="xla", k_override=4)
    (a,) = _snapshots(ctx1, t_steps=1)
    op = chain_product(ctx1, a, cfg.d, schedule="xla")
    m0 = REGISTRY.snapshot()
    emb = commute_time_embedding(ctx1, a, cfg, op=op)
    moved = REGISTRY.delta(m0)
    assert emb.op is op and not op.factored
    assert "chain.builds" not in moved and "chain.factored_builds" not in moved
    assert use_factored(ctx1, a, cfg.d, Q_FACTORED)  # built here, it would be factored


def test_rule_reads_shapes_and_memory(ctx1, monkeypatch):
    (a,) = _snapshots(ctx1, t_steps=1)
    assert use_factored(ctx1, a, 6, N // PASS_COST_DIVISOR - 1)
    assert not use_factored(ctx1, a, 6, N // PASS_COST_DIVISOR)
    shard = 4 * N * N
    monkeypatch.setattr(chain_mod, "_device_bytes_limit", lambda ctx: 10 * shard)
    assert use_factored(ctx1, a, 6, 1)  # d + 4 = 10 shards fit
    assert not use_factored(ctx1, a, 7, 1)


@pytest.mark.parametrize("method", ["richardson", "chebyshev", "cg"])
def test_rho_only_where_chebyshev_asks(ctx1, method, monkeypatch):
    """Richardson and CG on a factored operator never estimate rho;
    Chebyshev measures one through the factored mat-vec that agrees with the
    materialized build's.  Each method solves as on the materialized one."""
    (a,) = _snapshots(ctx1, t_steps=1)
    x = ctx1.put_rowblock(np.random.default_rng(0).normal(size=(N, 4)).astype(np.float32))
    mat = chain_product(ctx1, a, 4, schedule="xla")
    fac = factored_chain(ctx1, a, 4, schedule="xla")
    calls = []
    orig = power.estimate_rho

    def counted(*args, **kwargs):
        calls.append(args[1])
        return orig(*args, **kwargs)

    monkeypatch.setattr(power, "estimate_rho", counted)
    y, rep = solve(ctx1, fac, x, SolverSpec(method=method), fixed_q=6)
    if method == "chebyshev":
        assert len(calls) == 1 and callable(calls[0])
        assert fac.rho == pytest.approx(mat.rho, rel=1e-4)
        assert rep.rho == pytest.approx(min(mat.rho, 0.999), rel=1e-4)
    else:
        assert calls == [] and fac.rho is None and rep.rho is None
    y_mat, _ = solve(ctx1, mat, x, SolverSpec(method=method), fixed_q=6)
    assert _rel(y, y_mat) < 1e-4


def test_plain_richardson_embedding_estimates_no_rho(ctx1, monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("estimate_rho called for a Richardson solve")

    monkeypatch.setattr(power, "estimate_rho", refuse)
    cfg = CommuteConfig(eps_rp=1e-2, d=3, q=Q_FACTORED, schedule="xla", k_override=4)
    (a,) = _snapshots(ctx1, t_steps=1)
    emb = commute_time_embedding(ctx1, a, cfg)
    assert emb.op.factored and emb.op.rho is None


def test_sequence_compiles_the_factored_solve_once(ctx1):
    """Steady-state pushes of a factored sequence add no program-cache miss
    and no compile: the T levels, degrees and adjacency are operands of the
    one cached solve program."""
    cfg = CommuteConfig(eps_rp=1e-2, d=3, q=Q_FACTORED, schedule="xla", k_override=4)
    snaps = _snapshots(ctx1, t_steps=6)
    det = SequenceDetector(ctx1, cfg, top_k=5)
    st = program_cache_stats()
    # the first embedding, the first scoring, the first merge of two top-k
    # lists: every shape of the steady state
    for a in snaps[:3]:
        det.push(a)
    per_push = []
    for a in snaps[3:]:
        m0, misses0 = REGISTRY.snapshot(), st.misses
        det.push(a)
        moved = REGISTRY.delta(m0)
        per_push.append((st.misses - misses0, moved.get("jit.compiles", 0.0),
                         moved.get("chain.factored_builds")))
    assert per_push == [(0, 0.0, 1.0)] * 3
    assert len(det.finalize().transitions) == 5


@pytest.mark.parametrize("caller", ["sequence", "pairwise"])
def test_retiring_the_operator_frees_its_levels(ctx1, caller, monkeypatch):
    """``SequenceDetector._retire_op`` and ``detect_anomalies`` delete a
    factored operator's T levels once it has solved; the adjacency, the
    snapshot's own, stays for scoring."""
    built = []

    def recording(*args, **kwargs):
        op = factored_chain(*args, **kwargs)
        built.append((op.t_levels, op.adj))
        return op

    monkeypatch.setattr(emb_mod, "factored_chain", recording)
    cfg = CommuteConfig(eps_rp=1e-2, d=3, q=Q_FACTORED, schedule="xla", k_override=4)
    a1, a2 = _snapshots(ctx1)
    if caller == "sequence":
        det = SequenceDetector(ctx1, cfg, top_k=5)
        det.push(a1)
        assert det._prev[1].op is None
        det.push(a2)
    else:
        detect_anomalies(ctx1, a1, a2, cfg, top_k=5)
    assert len(built) == 2
    for levels, adj in built:
        assert len(levels) == cfg.d and all(t.is_deleted() for t in levels)
        assert not adj.is_deleted()

"""Incremental delta-chain updates (ISSUE 9): correctness, telemetry, lifecycle.

Covers the acceptance bars end to end:

* combination matrix -- incremental x warm-start x (resident, oocore) x
  (1x1, 2x2 mesh) -- scores allclose to the full-rebuild path within the
  documented tolerance (1e-3 of the commute-distance scale ``V_G E||z||^2``;
  on a quiet drifting sequence the raw scores sit orders of magnitude below
  that scale, so relative-to-score tolerances would be meaningless),
* the >= 3x chain-phase GEMM FLOP / scratch-byte reduction, asserted from the
  registry counters each scored transition records,
* the drift monitor's fallback on an abrupt-change transition,
* the shared-base scratch lifecycle (satellite: no leak, no double-free),
* ``truncate_factors`` optimality (the rank-r recompression the level
  propagation leans on).

The heavy rank x solver x storage sweep rides behind ``-m slow``.
"""

import warnings as _warnings
from dataclasses import replace

import numpy as np
import pytest

from repro.core import (
    CommuteConfig,
    build_base_chain,
    detect_sequence_anomalies,
    full_build_gemm_cost,
    truncate_factors,
    try_delta_update,
)
from repro.core.embedding import commute_time_embedding
from repro.graphs import gmm_snapshot_sequence


@pytest.fixture(params=["ctx1", "ctx22"])
def ctx(request):
    return request.getfixturevalue(request.param)


# Localized drift (3 movers / step) keeps dS near-low-rank -- the regime the
# delta path targets; global point noise would make dS full-rank and the
# drift monitor would (correctly) reject every transition.
_DRIFT_KW = dict(seed=5, noise=0.02, inject_steps=set(), drift_nodes=3)

_BASE_CFG = CommuteConfig(
    eps_rp=1e-2, d=3, q=8, schedule="xla", k_override=4,
    solver="cg", solver_tol=1e-5, warm_start=True,
)
_INC_CFG = replace(_BASE_CFG, incremental_chain=True, delta_rank=6, delta_budget=0.1)


def _drifting_snapshots(ctx, n, t_steps, storage):
    """Slowly-drifting localized-movement GMM sequence; oocore variants are
    served as store-backed handles so the whole transition streams."""
    seq = gmm_snapshot_sequence(ctx, n, t_steps, **_DRIFT_KW)
    if storage == "oocore":
        from repro.store import TileStore

        store = TileStore.create(None, n=n, grid=4)
        for t, a in enumerate(seq.snapshots()):
            store.put_snapshot(f"t{t:03d}", np.asarray(a))
        return store.iter_snapshots()
    return seq.snapshots()


def _commute_scale(ctx, cfg, n, t_steps):
    """The commute-distance scale V_G * E||z_i||^2 -- the natural atol anchor
    (same convention as the warm-start acceptance tests)."""
    seq = gmm_snapshot_sequence(ctx, n, t_steps, **_DRIFT_KW)
    emb = commute_time_embedding(ctx, next(seq.snapshots()), cfg)
    z = np.asarray(emb.z, np.float64)
    return float(emb.vol) * float((z * z).sum(1).mean())


def _counter(metrics: dict, name: str) -> float:
    return float(metrics.get(f"chain.{name}", 0.0))


# ---------------------------------------------------------------------------
# combination matrix: incremental x warm x storage x mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("storage", ["resident", "oocore"])
def test_incremental_scores_allclose_full_rebuild(ctx, storage):
    """Acceptance (1x1 AND 2x2 mesh, resident AND out-of-core, warm-started):
    incremental-chain scores stay allclose (rtol 1e-3, atol 1e-3 of the
    commute-distance scale) to the full-rebuild run, with every transition
    after the first push served by a delta update (no fallbacks)."""
    n, t_steps = 48, 3
    full_cfg = replace(_BASE_CFG, oocore=storage == "oocore")
    inc_cfg = replace(_INC_CFG, oocore=storage == "oocore")
    full = detect_sequence_anomalies(
        ctx, _drifting_snapshots(ctx, n, t_steps, storage), full_cfg, top_k=5
    )
    inc = detect_sequence_anomalies(
        ctx, _drifting_snapshots(ctx, n, t_steps, storage), inc_cfg, top_k=5
    )
    scale = _commute_scale(ctx, replace(full_cfg, oocore=False), n, t_steps)
    for t, (f, i) in enumerate(zip(full.transitions, inc.transitions)):
        np.testing.assert_allclose(
            np.asarray(i.scores), np.asarray(f.scores),
            rtol=1e-3, atol=1e-3 * scale, err_msg=f"transition {t}",
        )
    # the first push was the one full build; everything after was a delta
    assert _counter(inc.warmup_metrics, "full_rebuilds") == 1
    assert sum(_counter(m, "incremental_updates") for m in inc.transition_metrics) == t_steps - 1
    assert sum(_counter(m, "drift_fallbacks") for m in inc.transition_metrics) == 0
    assert sum(_counter(m, "full_rebuilds") for m in inc.transition_metrics) == 0


@pytest.mark.parametrize("method", ["richardson", "chebyshev"])
def test_incremental_all_solver_methods(ctx1, method):
    """The low-rank correction rides inside every solver's mat-vec: the
    non-CG methods match their own full-rebuild runs too (CG is covered by
    the combination matrix above)."""
    n, t_steps = 48, 3
    full_cfg = replace(_BASE_CFG, solver=method, solver_tol=1e-4)
    inc_cfg = replace(full_cfg, incremental_chain=True, delta_rank=6, delta_budget=0.1)
    full = detect_sequence_anomalies(
        ctx1, _drifting_snapshots(ctx1, n, t_steps, "resident"), full_cfg, top_k=5
    )
    inc = detect_sequence_anomalies(
        ctx1, _drifting_snapshots(ctx1, n, t_steps, "resident"), inc_cfg, top_k=5
    )
    scale = _commute_scale(ctx1, full_cfg, n, t_steps)
    for t, (f, i) in enumerate(zip(full.transitions, inc.transitions)):
        np.testing.assert_allclose(
            np.asarray(i.scores), np.asarray(f.scores),
            rtol=1e-3, atol=1e-3 * scale, err_msg=f"{method} transition {t}",
        )
    assert sum(_counter(m, "incremental_updates") for m in inc.transition_metrics) == t_steps - 1


# ---------------------------------------------------------------------------
# the >= 3x FLOP / scratch reduction (registry counters)
# ---------------------------------------------------------------------------


def test_incremental_gemm_flops_and_scratch_at_least_3x_less(ctx1):
    """Acceptance: every incremental transition's chain-phase GEMM FLOPs and
    materialized scratch bytes (registry counters ``chain.gemm_flops`` /
    ``chain.scratch_bytes``) are >= 3x below one full rebuild's cost at the
    benchmark size n=96, d=3, rank 6."""
    n, t_steps = 96, 3
    cfg = replace(_INC_CFG, k_override=6)
    res = detect_sequence_anomalies(
        ctx1, _drifting_snapshots(ctx1, n, t_steps, "resident"), cfg, top_k=5
    )
    full_flops, _, full_scratch = full_build_gemm_cost(n, cfg.d)
    assert sum(_counter(m, "drift_fallbacks") for m in res.transition_metrics) == 0
    for t, m in enumerate(res.transition_metrics):
        assert _counter(m, "incremental_updates") == 1, f"transition {t}"
        flops = _counter(m, "gemm_flops")
        scratch = _counter(m, "scratch_bytes")
        assert 0 < flops <= full_flops / 3.0, (t, flops, full_flops)
        assert 0 < scratch <= full_scratch / 3.0, (t, scratch, full_scratch)


# ---------------------------------------------------------------------------
# drift monitor: abrupt change falls back to a full rebuild
# ---------------------------------------------------------------------------


def test_drift_monitor_falls_back_on_abrupt_change(ctx1):
    """A structurally-different snapshot mid-sequence trips the sketched
    drift monitor: that transition pays one fallback + one full rebuild (and
    becomes the new base), while the quiet transitions stay incremental."""
    n = 48
    quiet = list(gmm_snapshot_sequence(ctx1, n, 3, **_DRIFT_KW).snapshots())
    abrupt = next(
        gmm_snapshot_sequence(
            ctx1, n, 2, seed=99, noise=0.02, inject_steps=set()
        ).snapshots()
    )
    res = detect_sequence_anomalies(ctx1, [*quiet, abrupt], _INC_CFG, top_k=5)
    # pushes: 0 = rebuild (warmup), 1..2 = delta updates, 3 = fallback+rebuild
    assert _counter(res.warmup_metrics, "full_rebuilds") == 1
    per_t = res.transition_metrics
    assert [_counter(m, "incremental_updates") for m in per_t] == [1, 1, 0]
    assert [_counter(m, "drift_fallbacks") for m in per_t] == [0, 0, 1]
    assert [_counter(m, "full_rebuilds") for m in per_t] == [0, 0, 1]
    for t in res.transitions:
        assert np.isfinite(np.asarray(t.scores)).all()


# ---------------------------------------------------------------------------
# shared-base scratch lifecycle (satellite: no leak, no double-free)
# ---------------------------------------------------------------------------


def test_shared_base_scratch_lifecycle_oocore(ctx1):
    """The base chain is the single owner of the out-of-core scratch: a
    corrected operator's ``release_scratch()`` is a no-op (its P1/P2 *are*
    the base's handles), ``BaseChain.release()`` empties the scratch store
    exactly once, and a second release is a clean no-op -- no warning, no
    double-free."""
    n = 48
    cfg = replace(_INC_CFG, oocore=True)
    snaps = list(gmm_snapshot_sequence(ctx1, n, 2, **_DRIFT_KW).snapshots())
    base = build_base_chain(ctx1, snaps[0], cfg)
    store = base.op.p1.store
    live = set(store.snapshot_ids)
    # p1 + p2 + d retained T levels (no P level: P_l is a product of T's)
    assert len(live) == 2 + cfg.d

    p2_id = base.op.p2.snap_id
    base.solved(1e-6)  # the base's own solve is done: its P2 goes
    assert base.op.p2 is None
    live.discard(p2_id)
    assert set(store.snapshot_ids) == live and len(live) == 1 + cfg.d

    corrected = try_delta_update(ctx1, base, snaps[1], cfg)
    assert corrected is not None and corrected.shared_base
    corrected.release_scratch()  # shares the base: must NOT retire scratch
    assert set(store.snapshot_ids) == live

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        base.release()  # the one real release: every retained handle dies
        assert store.snapshot_ids == []
        base.release()  # idempotent: no second remove, no warning


# ---------------------------------------------------------------------------
# factor truncation: exact best-rank-r recompression
# ---------------------------------------------------------------------------


def test_truncate_factors_is_optimal_rank_r():
    """``truncate_factors(u, v, r)`` matches the optimal (SVD) rank-r
    approximation of u v^T: the residual equals the singular-value tail."""
    rng = np.random.default_rng(0)
    u = rng.normal(size=(40, 6)).astype(np.float32)
    v = rng.normal(size=(40, 6)).astype(np.float32)
    prod = u.astype(np.float64) @ v.astype(np.float64).T
    s = np.linalg.svd(prod, compute_uv=False)
    for r in (2, 4, 6):
        ut, vt = truncate_factors(u, v, r)
        assert ut.shape == (40, r) and vt.shape == (40, r)
        err = np.linalg.norm(prod - np.asarray(ut, np.float64) @ np.asarray(vt, np.float64).T)
        opt = np.linalg.norm(s[r:])
        np.testing.assert_allclose(err, opt, rtol=1e-4, atol=1e-4)


def test_jitted_propagate_matches_the_passes(ctx1):
    """Resident levels propagate as one compiled program: the same dP
    factors' product as the pass-by-pass path, and the same ledger."""
    from repro.core.delta_chain import _GemmLedger, _Passes, _propagate, _propagate_passes

    rng = np.random.default_rng(3)
    n, r, d = 64, 4, 4
    s = rng.normal(size=(n, n)).astype(np.float32)
    t0 = ctx1.put_matrix(0.05 * (s + s.T))
    t_lv = [t0]
    for _ in range(d - 1):
        t_lv.append(t_lv[-1] @ t_lv[-1])
    u, v = rng.normal(size=(n, r)).astype(np.float32), rng.normal(size=(n, r)).astype(np.float32)
    dt0 = truncate_factors(ctx1.put_rowblock(1e-2 * u), ctx1.put_rowblock(1e-2 * v), r)
    got, want = _GemmLedger(), _GemmLedger()
    for _ in range(2):  # the second call reuses the compiled program and its ledger
        e_j, f_j = _propagate(_Passes(ctx1, None, got), t_lv, dt0, r)
    e_p, f_p = _propagate_passes(_Passes(ctx1, None, want), t_lv, dt0, r)
    prod_j = np.asarray(e_j, np.float64) @ np.asarray(f_j, np.float64).T
    prod_p = np.asarray(e_p, np.float64) @ np.asarray(f_p, np.float64).T
    np.testing.assert_allclose(prod_j, prod_p, rtol=1e-4, atol=1e-4 * np.abs(prod_p).max())
    assert (got.flops, got.bytes, got.scratch) == (2 * want.flops, 2 * want.bytes, 2 * want.scratch)


def test_drift_monitor_reads_the_frobenius_ratio(ctx1):
    """At a grid of 48x48 the monitor sketches 128 columns, and its drift
    reads the exact ``||S~' - S~||_F / ||S~||_F`` within 5% (six columns
    were 6-14% off on the same snapshots)."""
    from bench import traffic as tf
    from repro.core.delta_chain import DRIFT_SKETCH_COLS
    from repro.obs import REGISTRY

    grid = dict(n_lat=48, n_lon=48, n=2304)
    assert min(DRIFT_SKETCH_COLS, grid["n"] // 16) == 128
    cfg = CommuteConfig(eps_rp=1e-3, d=2, q=4, schedule="xla", seed=11, incremental_chain=True)

    def s_tilde(a, deflate):
        a = np.asarray(a, np.float64)
        deg = a.sum(axis=1)
        inv = 1.0 / np.sqrt(deg)
        s = inv[:, None] * a * inv[None, :]
        if deflate:
            u = np.sqrt(deg / deg.sum())
            s -= np.outer(u, u)
        return s

    for seed in (7, 8):
        snaps = tf.snapshots(_CLIMATE_TRAFFIC, grid, seed)
        a0 = snaps.adjacency(ctx1, 0)
        base = build_base_chain(ctx1, a0, cfg)
        s0 = s_tilde(a0, base.deflate)
        for t in (6, 12):
            a1 = snaps.adjacency(ctx1, t)
            assert try_delta_update(ctx1, base, a1, cfg) is not None
            drift = REGISTRY.gauge("chain.drift_last")
            exact = np.linalg.norm(s_tilde(a1, base.deflate) - s0) / np.linalg.norm(s0)
            assert 0.01 < exact < cfg.delta_budget
            assert abs(drift / exact - 1.0) < 0.05, (seed, t, drift, exact)
        base.release()


# ---------------------------------------------------------------------------
# heavy sweep: rank x storage x mesh (slow marker)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("storage", ["resident", "oocore"])
@pytest.mark.parametrize("rank", [4, 8])
def test_incremental_sweep_rank_storage_mesh(ctx, rank, storage):
    """Heavy combination sweep: delta rank x storage x mesh at n=96, T=4,
    warm-started CG -- scores allclose to full rebuild, zero fallbacks."""
    n, t_steps = 96, 4
    full_cfg = replace(_BASE_CFG, k_override=6, oocore=storage == "oocore")
    inc_cfg = replace(
        full_cfg, incremental_chain=True, delta_rank=rank, delta_budget=0.1
    )
    full = detect_sequence_anomalies(
        ctx, _drifting_snapshots(ctx, n, t_steps, storage), full_cfg, top_k=5
    )
    inc = detect_sequence_anomalies(
        ctx, _drifting_snapshots(ctx, n, t_steps, storage), inc_cfg, top_k=5
    )
    scale = _commute_scale(ctx, replace(full_cfg, oocore=False), n, t_steps)
    for t, (f, i) in enumerate(zip(full.transitions, inc.transitions)):
        np.testing.assert_allclose(
            np.asarray(i.scores), np.asarray(f.scores),
            rtol=1e-3, atol=1e-3 * scale,
            err_msg=f"rank={rank} {storage} transition {t}",
        )
    assert sum(_counter(m, "incremental_updates") for m in inc.transition_metrics) == t_steps - 1
    assert sum(_counter(m, "drift_fallbacks") for m in inc.transition_metrics) == 0


# ---------------------------------------------------------------------------
# the slowly drifting climate deployment against the plain references
# ---------------------------------------------------------------------------

# The benchmark's climate-drift traffic (drift 0.01, no event) on a grid the
# CPU holds, with the chain cut to d=3.
_CLIMATE = dict(n_lat=8, n_lon=8, n=64, eps_rp=1e-3, d=3, q=10)
_CLIMATE_TRAFFIC = {
    "kind": "climate_fields", "loop": "write", "channels": 12, "smooth_passes": 8,
    "drift": 0.01, "sigma": 1.0,
    "event": {"frac": 0.02, "strength": 0.0, "smooth_passes": 2, "period": 4, "phase": 1},
}
_CLIMATE_SEED = 7


def _climate_scores(ctx, incremental: bool, k: int | None):
    """Scores of five transitions through ``SequenceDetector``; with
    ``incremental`` both flags are on and the drift budget is 0 for the push
    of snapshot 3, which the drift monitor must then hand to a rebuild."""
    from bench import reference
    from bench import traffic as tf
    from repro.core import SequenceDetector

    snaps = tf.snapshots(_CLIMATE_TRAFFIC, _CLIMATE, _CLIMATE_SEED)
    cfg = CommuteConfig(
        eps_rp=_CLIMATE["eps_rp"], d=_CLIMATE["d"], q=_CLIMATE["q"], schedule="xla",
        seed=reference.projection_seed(_CLIMATE_SEED), k_override=k,
        warm_start=incremental, incremental_chain=incremental,
    )
    det = SequenceDetector(ctx, cfg, top_k=5)
    scores = {}
    for t in range(6):
        det.cfg = replace(cfg, delta_budget=0.0) if incremental and t == 3 else cfg
        res = det.push(snaps.adjacency(ctx, t))
        if res is not None:
            scores[t] = np.asarray(res.scores, np.float64)
    return snaps, scores, det.finalize().transition_metrics


def test_incremental_climate_matches_the_references(ctx1):
    """The drifting climate deployment with ``warm_start`` and
    ``incremental_chain`` on scores every transition -- deltas and the drift
    fallback's rebuild alike -- as close to ``bench.reference`` (the exact
    full chain in plain jax.numpy) and to the eigendecomposition oracle
    ``exact_commute_distances`` as the full-rebuild path does on the same
    data."""
    from bench import check, reference
    from repro.core.embedding import exact_commute_distances

    def exact(snaps, t):
        a1, a2 = (np.asarray(snaps.adjacency(ctx1, s), np.float64) for s in (t - 1, t))
        c1, c2 = (np.asarray(exact_commute_distances(x)) for x in (a1, a2))
        return (np.abs(a1 - a2) * np.abs(c1 - c2)).sum(1)

    sharding = ctx1.sharding(ctx1.matrix_spec)
    # 1e-3 of the top score against the reference (the full rebuild reads
    # up to 4.9e-4 here: float32 rounding over score differences that shrink
    # with the drift); 0.1 against the oracle, which has no random
    # projection (k_RP 4096 leaves about 5e-2 of Johnson-Lindenstrauss error).
    for k, tol, target in (
        (None, 1e-3, lambda s, t: reference.transition_scores(s, t, _CLIMATE, sharding)),
        (4096, 0.1, exact),
    ):
        snaps, full, _ = _climate_scores(ctx1, False, k)
        _, inc, metrics = _climate_scores(ctx1, True, k)
        for t in full:
            want = target(snaps, t)
            assert check.score_gap(full[t], want) <= tol, (k, t)
            assert check.score_gap(inc[t], want) <= tol, (k, t)
        modes = [(_counter(m, "incremental_updates"), _counter(m, "drift_fallbacks"))
                 for m in metrics]
        assert modes == [(1, 0), (1, 0), (0, 1), (1, 0), (1, 0)], modes


def test_residual_short_of_the_base_falls_back_to_a_rebuild(ctx1, monkeypatch):
    """A delta transition whose solve ends above the bar the base's own solve
    set is not scored with that iterate: the engine rebuilds and re-solves
    (``chain.solve_fallbacks``), so its scores are the full rebuild's."""
    import repro.core.sequence as seq_mod

    monkeypatch.setattr(seq_mod, "SOLVE_RESIDUAL_SLACK", 0.0)
    _, full, _ = _climate_scores(ctx1, False, None)
    _, inc, metrics = _climate_scores(ctx1, True, None)
    for m in metrics:
        assert _counter(m, "full_rebuilds") == 1
        assert _counter(m, "incremental_updates") + _counter(m, "drift_fallbacks") == 1
        assert _counter(m, "solve_fallbacks") == _counter(m, "incremental_updates")
    for t in full:
        np.testing.assert_allclose(inc[t], full[t], rtol=2e-3, atol=1e-3 * full[t].max())


def _live_nn(n: int) -> int:
    import jax

    return sum(1 for x in jax.live_arrays() if tuple(x.shape) == (n, n))


def test_live_square_arrays_through_delta_and_fallback(ctx1, monkeypatch):
    """Peak of live (n, n) arrays, sampled after every call that makes one
    (the chain's GEMMs, its adds and tile programs, S~ and L): a delta
    transition holds the two snapshots, the base's d T levels and P1
    (d + 3; the base's P2 goes once the base is solved); a drift fallback
    frees the old base before it builds, and its build peaks at the two
    snapshots, d T levels and three build arrays (d + 5).  n is unique to
    this test, so nothing else counts."""
    import repro.core.chain as chain_mod
    from bench import traffic as tf
    from repro.core import SequenceDetector

    geom = dict(_CLIMATE, n_lat=8, n_lon=9, n=72)
    n, d = geom["n"], geom["d"]
    peak = {"now": 0}

    def sampled(fn):
        def call(*args, **kwargs):
            out = fn(*args, **kwargs)
            peak["now"] = max(peak["now"], _live_nn(n))
            return out
        return call

    class _Sampled:
        def __init__(self, mod, names):
            self._mod, self._names = mod, names

        def __getattr__(self, name):
            fn = getattr(self._mod, name)
            return sampled(fn) if name in self._names else fn

    for name in ("matmul", "tile_map", "add_scaled_identity"):
        monkeypatch.setattr(chain_mod, name, sampled(getattr(chain_mod, name)))
    monkeypatch.setattr(chain_mod, "jnp", _Sampled(chain_mod.jnp, {"add"}))
    monkeypatch.setattr(
        chain_mod, "lap", _Sampled(chain_mod.lap, {"normalized_adjacency", "laplacian"})
    )

    snaps = tf.snapshots(_CLIMATE_TRAFFIC, geom, _CLIMATE_SEED)
    cfg = CommuteConfig(
        eps_rp=geom["eps_rp"], d=d, q=geom["q"], schedule="xla",
        warm_start=True, incremental_chain=True,
    )
    det = SequenceDetector(ctx1, cfg, top_k=5)
    peaks = []
    for t in range(4):
        det.cfg = replace(cfg, delta_budget=0.0) if t == 3 else cfg
        a = snaps.adjacency(ctx1, t)
        peak["now"] = _live_nn(n)
        det.push(a)
        del a
        peaks.append(max(peak["now"], _live_nn(n)))
    modes = [
        "delta" if _counter(m, "incremental_updates") else "fallback"
        for m in det.finalize().transition_metrics
    ]
    assert modes == ["delta", "delta", "fallback"], modes
    assert peaks[1:] == [d + 3, d + 3, d + 5], peaks

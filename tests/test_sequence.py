"""SequenceDetector: amortized sequence scoring == fresh pairwise scoring."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CommuteConfig,
    SequenceDetector,
    chain_build_count,
    detect_anomalies,
    detect_sequence_anomalies,
)
from repro.graphs import climate_snapshot_sequence, gmm_snapshot_sequence

CFG = CommuteConfig(eps_rp=1e-2, d=6, q=8, schedule="xla")


@pytest.fixture(params=["ctx1", "ctx22"])
def ctx(request):
    return request.getfixturevalue(request.param)


def test_sequence_matches_pairwise_and_builds_once(ctx1):
    """T=4: transition scores == three fresh detect_anomalies calls, with
    exactly 4 chain builds (vs 6 for the pairwise path)."""
    t_steps = 4

    def seq():
        return gmm_snapshot_sequence(ctx1, 64, t_steps, seed=1, inject_p=0.02)

    builds0 = chain_build_count()
    res = detect_sequence_anomalies(ctx1, seq().snapshots(), CFG, top_k=5)
    assert chain_build_count() - builds0 == t_steps
    assert res.chain_builds == t_steps
    assert len(res.transitions) == t_steps - 1

    snaps = list(seq().snapshots())
    for t in range(t_steps - 1):
        fresh = detect_anomalies(ctx1, snaps[t], snaps[t + 1], CFG, top_k=5)
        np.testing.assert_array_equal(
            np.asarray(res.transitions[t].scores), np.asarray(fresh.scores)
        )


def test_sequence_global_topk(ctx1):
    """Streaming global top-k == top-k over the concatenated score matrix."""
    res = detect_sequence_anomalies(
        ctx1, gmm_snapshot_sequence(ctx1, 64, 3, seed=2).snapshots(), CFG, top_k=7
    )
    allsc = np.stack([np.asarray(r.scores) for r in res.transitions])
    order = np.argsort(allsc.ravel())[::-1][:7]
    want_step, want_idx = np.unravel_index(order, allsc.shape)
    got = sorted(zip(np.asarray(res.global_top_step), np.asarray(res.global_top_idx)))
    assert got == sorted(zip(want_step.tolist(), want_idx.tolist()))
    np.testing.assert_allclose(
        np.sort(np.asarray(res.global_top_val))[::-1],
        np.sort(allsc.ravel())[::-1][:7],
        rtol=1e-6,
    )


def test_global_topk_merge_partially_replicated(ctx22):
    """The streaming top-k merge is exact when the per-transition candidates
    are sharded P(row_axes) -- *partially replicated* over the column mesh
    axes (an eager concatenate that summed the replicas would double every
    candidate on a 2x2 mesh)."""
    import jax

    det = SequenceDetector(ctx22, CFG, top_k=4)
    sh = ctx22.sharding(ctx22.vector_spec)

    def put(vals, dtype):
        return jax.device_put(np.asarray(vals, dtype), sh)

    det._merge_topk(put([0, 1, 2, 3], np.int32), put([4.0, 3.0, 2.0, 1.0], np.float32), 0)
    det._merge_topk(put([7, 8, 9, 10], np.int32), put([5.0, 3.0, 0.5, 0.25], np.float32), 1)
    np.testing.assert_array_equal(np.asarray(det._g_val), [5.0, 4.0, 3.0, 3.0])
    np.testing.assert_array_equal(np.asarray(det._g_idx), [7, 0, 1, 8])
    # lax.top_k tie semantics: equal values keep candidate order (step 0 first)
    np.testing.assert_array_equal(np.asarray(det._g_step), [1, 0, 0, 1])


def test_global_topk_sharded_matches_host(ctx22):
    """End-to-end on the multi-axis mesh: the merged global top-k equals a
    host-side top-k over all transition scores."""
    res = detect_sequence_anomalies(
        ctx22, gmm_snapshot_sequence(ctx22, 64, 3, seed=6).snapshots(), CFG, top_k=6
    )
    allsc = np.stack([np.asarray(r.scores) for r in res.transitions])
    want = np.sort(allsc.ravel())[::-1][:6]
    np.testing.assert_array_equal(np.sort(np.asarray(res.global_top_val))[::-1], want)


def test_sequence_sharded_matches_single(ctx1, ctx22):
    r1 = detect_sequence_anomalies(
        ctx1, gmm_snapshot_sequence(ctx1, 64, 3, seed=3).snapshots(), CFG, top_k=5
    )
    r2 = detect_sequence_anomalies(
        ctx22, gmm_snapshot_sequence(ctx22, 64, 3, seed=3).snapshots(), CFG, top_k=5
    )
    for a, b in zip(r1.transitions, r2.transitions):
        np.testing.assert_allclose(
            np.asarray(a.scores), np.asarray(b.scores), rtol=1e-3, atol=1e-2
        )


def test_sequence_donate_frees_previous(ctx1):
    seq = gmm_snapshot_sequence(ctx1, 64, 3, seed=4)
    det = SequenceDetector(ctx1, CFG, top_k=5, donate=True)
    snaps = list(seq.snapshots())
    det.push(snaps[0])
    det.push(snaps[1])  # scores 0->1, then donates snapshot 0's buffers
    assert snaps[0].is_deleted()
    assert not snaps[1].is_deleted()
    res = det.finalize()
    assert len(res.transitions) == 1


def test_sequence_requires_two_snapshots(ctx1):
    det = SequenceDetector(ctx1, CFG)
    with pytest.raises(ValueError, match="0 snapshots"):
        det.finalize()


def test_single_snapshot_finalizes_to_empty_result(ctx1):
    """T=1 has zero transitions by definition: finalize() returns an empty
    SequenceResult (not an exception -- only T=0 is a caller bug)."""
    from repro.graphs import gmm_graph_sequence

    det = SequenceDetector(ctx1, CFG, top_k=5)
    assert det.push(gmm_graph_sequence(ctx1, n=32, seed=0).a1) is None
    res = det.finalize()
    assert res.transitions == [] and res.n_snapshots == 1
    assert res.global_top_idx.shape == (0,)
    assert res.global_top_val.shape == (0,)
    assert res.global_top_step.shape == (0,)
    assert res.chain_builds == 1
    assert res.warmup_metrics is not None


# ---------------------------------------------------------------------------
# _release diagnosability (donate path)
# ---------------------------------------------------------------------------


class _FailingBuf:
    """Device-buffer stand-in whose delete fails like an already-donated
    buffer does."""

    def __init__(self, exc):
        self.exc = exc
        self.calls = 0

    def delete(self):
        self.calls += 1
        raise self.exc


def test_release_warns_and_continues_on_delete_failure(ctx1):
    """Expected delete failures (the double-buffering race) warn instead of
    vanishing, and the release keeps going past the first failure."""
    from repro.core.embedding import Embedding

    det = SequenceDetector(ctx1, CFG, donate=True)
    a = _FailingBuf(RuntimeError("buffer already donated"))
    z = _FailingBuf(OSError("device gone"))
    with pytest.warns(RuntimeWarning, match="delete failed") as rec:
        det._release(a, Embedding(z=z, vol=1.0, op=None))
    assert a.calls == 1 and z.calls == 1
    assert len(rec) == 2


def test_release_propagates_unexpected_errors(ctx1):
    """Only the expected buffer errors are downgraded to warnings -- a
    genuine programming error must surface (the former bare `except
    Exception` ate everything)."""
    from repro.core.embedding import Embedding

    det = SequenceDetector(ctx1, CFG, donate=True)
    bad = _FailingBuf(TypeError("programming error"))
    with pytest.raises(TypeError, match="programming error"):
        det._release(bad, Embedding(z=bad, vol=1.0, op=None))


def test_release_skips_handles_without_delete(ctx1):
    """Store-backed snapshot handles (no .delete) are the user's data: the
    donate path skips them silently, no warning, no error."""
    import warnings as _warnings

    from repro.core.embedding import Embedding

    class Plain:
        pass

    det = SequenceDetector(ctx1, CFG, donate=True)
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        det._release(Plain(), Embedding(z=Plain(), vol=1.0, op=None))


# ---------------------------------------------------------------------------
# warm-started sequences: same scores, far fewer iterations
# ---------------------------------------------------------------------------


def _drifting_snapshots(ctx, n, t_steps, storage):
    """A slowly-drifting GMM sequence (no injections); oocore variants are
    served as store-backed handles so the whole transition streams."""
    seq = gmm_snapshot_sequence(
        ctx, n, t_steps, seed=5, noise=1e-4, inject_steps=set()
    )
    if storage == "oocore":
        from repro.store import TileStore

        store = TileStore.create(None, n=n, grid=4)
        for t, a in enumerate(seq.snapshots()):
            store.put_snapshot(f"t{t:03d}", np.asarray(a))
        return store.iter_snapshots()
    return seq.snapshots()


def _commute_scale(ctx, cfg, n, t_steps):
    """The commute-distance scale V_G * E||z_i||^2 the anomaly scores are
    measured in -- the natural atol anchor for warm-vs-cold comparisons (on
    a quiet sequence the scores themselves sit orders of magnitude below
    it)."""
    from repro.core.embedding import commute_time_embedding

    seq = gmm_snapshot_sequence(
        ctx, n, t_steps, seed=5, noise=1e-4, inject_steps=set()
    )
    emb = commute_time_embedding(ctx, next(seq.snapshots()), cfg)
    z = np.asarray(emb.z, np.float64)
    return float(emb.vol) * float((z * z).sum(1).mean())


@pytest.mark.parametrize("storage", ["resident", "oocore"])
def test_warm_start_scores_allclose_cold(ctx, storage):
    """Acceptance (1x1 AND 2x2 mesh, resident AND out-of-core): warm-started
    sequence scores stay allclose (rtol 1e-4, atol 1e-4 of the
    commute-distance scale) to the cold run, every right-endpoint report is
    flagged warm, and warm iterations never exceed cold."""
    from dataclasses import replace

    n, t_steps = 48, 3
    cold_cfg = CommuteConfig(
        eps_rp=1e-2, d=3, q=8, schedule="xla", k_override=4,
        solver="richardson", solver_tol=1e-4, oocore=storage == "oocore",
    )
    warm_cfg = replace(cold_cfg, warm_start=True)
    cold = detect_sequence_anomalies(
        ctx, _drifting_snapshots(ctx, n, t_steps, storage), cold_cfg, top_k=5
    )
    warm = detect_sequence_anomalies(
        ctx, _drifting_snapshots(ctx, n, t_steps, storage), warm_cfg, top_k=5
    )
    scale = _commute_scale(ctx, replace(cold_cfg, oocore=False), n, t_steps)
    for t, (c, w) in enumerate(zip(cold.transitions, warm.transitions)):
        np.testing.assert_allclose(
            np.asarray(w.scores), np.asarray(c.scores),
            rtol=1e-4, atol=1e-4 * scale, err_msg=f"transition {t}",
        )
        assert w.solve_reports[1].warm_start
        assert not c.solve_reports[1].warm_start
        assert w.solve_reports[1].iterations <= c.solve_reports[1].iterations


@pytest.mark.slow
def test_warm_start_halves_iterations_on_drifting_sequence(ctx1):
    """ISSUE 8 acceptance: on a slowly-drifting sequence, warm-started
    tolerance-targeted solves (all three methods) take >= 2x fewer
    iterations than cold from transition 2 onward, with scores allclose."""
    from dataclasses import replace

    n, t_steps = 96, 4
    base = CommuteConfig(
        eps_rp=1e-2, d=3, q=8, schedule="xla", k_override=6, solver_tol=1e-5
    )
    scale = _commute_scale(ctx1, replace(base, solver="cg"), n, t_steps)
    for method in ("richardson", "chebyshev", "cg"):
        cold_cfg = replace(base, solver=method)
        warm_cfg = replace(cold_cfg, warm_start=True)
        cold = detect_sequence_anomalies(
            ctx1, _drifting_snapshots(ctx1, n, t_steps, "resident"),
            cold_cfg, top_k=5,
        )
        warm = detect_sequence_anomalies(
            ctx1, _drifting_snapshots(ctx1, n, t_steps, "resident"),
            warm_cfg, top_k=5,
        )
        cold_its = [r.solve_reports[1].iterations for r in cold.transitions]
        warm_its = [r.solve_reports[1].iterations for r in warm.transitions]
        for t in range(1, t_steps - 1):  # transition 2 onward (1-based)
            assert warm.transitions[t].solve_reports[1].converged
            assert cold.transitions[t].solve_reports[1].converged
            assert cold_its[t] >= 2 * warm_its[t], (method, cold_its, warm_its)
        for t, (c, w) in enumerate(zip(cold.transitions, warm.transitions)):
            np.testing.assert_allclose(
                np.asarray(w.scores), np.asarray(c.scores),
                rtol=1e-4, atol=1e-4 * scale,
                err_msg=f"{method} transition {t}",
            )


def test_climate_sequence_truth_at_event(ctx1):
    """The event transition carries truth; quiet transitions don't."""
    seq = climate_snapshot_sequence(ctx1, 8, 8, 4, seed=0, event_frac=0.05)
    assert seq.t_steps == 4
    # event at t=2: transitions 1->2 (appears) and 2->3 (disappears) have truth
    assert len(seq.truth[0]) == 0
    assert len(seq.truth[1]) > 0
    assert len(seq.truth[2]) > 0
    snaps = list(seq.snapshots())
    assert all(s.shape == (64, 64) for s in snaps)


def test_deflate_constant_preserves_sharding(ctx22):
    """Satellite: deflate_constant constrains output to the rowblock layout."""
    from repro.core.solver import deflate_constant

    y = ctx22.put_rowblock(np.random.default_rng(0).normal(size=(32, 4)).astype(np.float32))
    out = deflate_constant(ctx22, y)
    assert float(jnp.max(jnp.abs(jnp.mean(out, axis=0)))) < 1e-5
    assert out.sharding.spec == ctx22.rowblock_spec

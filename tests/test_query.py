"""Query-scale read path: EmbeddingStore artifacts, the fused distance/top-k
kernel, and the query API -- pinned against the exact eigendecomposition
oracle and brute-force numpy on 1x1 AND 2x2 meshes.
"""

import jax
import numpy as np
import pytest

from repro.core import CommuteConfig, SequenceDetector
from repro.core.embedding import (
    commute_distance_block,
    commute_time_embedding,
    exact_commute_distances,
)
from repro.core.query import (
    commute_block,
    nearest_neighbors,
    rank_auc,
    top_anomalies_from_store,
)
from repro.graphs import gmm_graph_sequence, gmm_snapshot_sequence
from repro.core.tiles import reset_stream_stats, stream_stats
from repro.obs import REGISTRY, disable_tracing, enable_tracing, tracer
from repro.store.embstore import EmbeddingStore

CFG = CommuteConfig(eps_rp=1e-3, d=8, q=12, schedule="xla", k_override=64)


def _publish(ctx, n=128, *, root=None, codec="raw", seed_graph=0):
    """One embedding pushed through the detector into a store; returns
    (store, resident Embedding, adjacency)."""
    seq = gmm_graph_sequence(ctx, n, seed=seed_graph, inject_p=0.02)
    emb = commute_time_embedding(ctx, seq.a1, CFG)
    store = EmbeddingStore.create(
        root, n=n, k=CFG.k_override, codec=codec, seed=CFG.seed
    )
    store.put_embedding("t0000", emb.z, emb.vol, emb.op.deg)
    return store, emb, np.asarray(seq.a1)


# ---------------------------------------------------------------------------
# EmbeddingStore artifact lifecycle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", ["raw", "bf16"])
def test_embstore_roundtrip(tmp_path, codec):
    rng = np.random.default_rng(0)
    n, k = 96, 16
    z = rng.normal(size=(n, k)).astype(np.float32)
    deg = rng.uniform(1.0, 3.0, size=n).astype(np.float32)
    store = EmbeddingStore.create(
        tmp_path, n=n, k=k, codec=codec, seed=3, panel_rows=32
    )
    store.put_embedding("t0000", z, 123.5, deg)

    reopened = EmbeddingStore.open(tmp_path)
    h = reopened.embedding("t0000")
    assert h.shape == (n, k)
    tol = dict(rtol=1e-2, atol=1e-2) if codec == "bf16" else dict(rtol=0, atol=0)
    np.testing.assert_allclose(h.to_numpy(), z, **tol)
    np.testing.assert_allclose(h.deg, deg)
    assert h.vol == 123.5
    np.testing.assert_allclose(h.zbar, z.mean(0), rtol=1e-2, atol=1e-2)
    rows = [0, 17, n - 1]
    np.testing.assert_allclose(h.read_rows(rows), z[rows], **tol)
    # panels round through the (row0, height) protocol PanelPipeline speaks
    pr = h.panel_rows
    np.testing.assert_allclose(h.read_panel(pr, pr), z[pr : 2 * pr], **tol)


def test_embstore_bf16_stored_form_is_half_width(tmp_path):
    z = np.random.default_rng(1).normal(size=(64, 8)).astype(np.float32)
    store = EmbeddingStore.create(tmp_path, n=64, k=8, codec="bf16")
    store.put_embedding("t0000", z, 1.0, np.ones(64))
    stored = store.read_panel_stored("t0000", 0)
    assert stored.dtype == np.uint16
    assert stored.nbytes * 2 == z[: store.panel_rows].nbytes


def test_embstore_fingerprint_mismatch_rejected(tmp_path):
    EmbeddingStore.create(tmp_path, n=64, k=8, seed=0)
    with pytest.raises(ValueError, match="fingerprint"):
        EmbeddingStore.create(tmp_path, n=64, k=16, seed=0)  # different k
    with pytest.raises(ValueError, match="fingerprint"):
        EmbeddingStore.create(tmp_path, n=64, k=8, seed=1)  # different sketch


def test_embstore_commit_on_complete(tmp_path):
    """An artifact is served only once its Z file AND the aux sidecar exist;
    a torn publish (missing aux) never reaches the manifest."""
    store = EmbeddingStore.create(tmp_path, n=64, k=8)
    z = np.zeros((64, 8), np.float32)
    store._store_z("torn", np.asarray(store.codec.encode(z)))  # crash before aux
    with pytest.raises(ValueError, match="incomplete"):
        store._commit("torn")
    assert "torn" not in store.embedding_ids
    with pytest.raises(KeyError):
        store.embedding("torn")
    # resume: put_embedding completes the torn publish in place
    h = store.put_embedding("torn", z, 1.0, np.ones(64))
    assert h.emb_id in store.embedding_ids


@pytest.mark.parametrize("codec", ["raw", "bf16"])
def test_embstore_artifact_is_one_z_file(tmp_path, codec):
    """A publish writes one Z file and one aux sidecar, whatever the panel
    count, and every panel reads back from its slice of that file."""
    rng = np.random.default_rng(0)
    store = EmbeddingStore.create(tmp_path, n=128, k=8, codec=codec, panel_rows=16)
    z = rng.normal(size=(128, 8)).astype(np.float32)
    store.put_embedding("t0000", z, 2.0, np.ones(128))
    assert sorted(f.name for f in (tmp_path / "t0000").iterdir()) == ["aux.npz", "z.npy"]
    want = store.codec.decode(store.codec.encode(z), 128, np.dtype(np.float32))
    itemsize = 2 if codec == "bf16" else 4
    for p in range(store.manifest.panels):
        np.testing.assert_array_equal(store.read_panel("t0000", p), want[16 * p : 16 * (p + 1)])
        assert store.panel_nbytes_stored("t0000", p) == 16 * 8 * itemsize


def test_embstore_rejects_panel_file_layout(tmp_path):
    """A directory of the older layout (a file per Z panel, format v1) is
    refused, never read as empty or torn."""
    EmbeddingStore.create(tmp_path, n=64, k=8)
    path = tmp_path / "manifest.json"
    path.write_text(path.read_text().replace('"version": 2', '"version": 1'))
    with pytest.raises(ValueError, match="fresh directory"):
        EmbeddingStore.open(tmp_path)


def test_embstore_rejects_tilestore_dir(tmp_path):
    from repro.store import TileStore

    TileStore.create(tmp_path / "tiles", n=64, grid=2)
    with pytest.raises(ValueError):
        EmbeddingStore.open(tmp_path / "tiles")


# ---------------------------------------------------------------------------
# query path vs oracle / brute force (1x1 and 2x2 meshes)
# ---------------------------------------------------------------------------


def _ctx(request, name):
    return request.getfixturevalue(name)


@pytest.mark.parametrize("ctxname", ["ctx1", "ctx22"])
def test_store_commute_block_matches_resident(request, ctxname, tmp_path):
    ctx = _ctx(request, ctxname)
    store, emb, _ = _publish(ctx, root=tmp_path)
    rows, cols = np.arange(0, 128, 7), np.arange(3, 128, 11)
    resident = np.asarray(commute_distance_block(emb, rows, cols))
    from_store = commute_block(store, rows, cols)
    np.testing.assert_allclose(from_store, resident, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("ctxname", ["ctx1", "ctx22"])
def test_store_block_approximates_exact(request, ctxname, tmp_path):
    """Store-backed distances carry the same oracle accuracy as the resident
    embedding (the artifact adds no error beyond the sketch's own)."""
    ctx = _ctx(request, ctxname)
    store, _, a = _publish(ctx, root=tmp_path)
    exact = np.asarray(exact_commute_distances(a))
    idx = np.arange(128)
    approx = commute_block(store, idx, idx)
    mask = ~np.eye(128, dtype=bool)
    rel = np.abs(approx - exact)[mask] / np.maximum(exact[mask], 1e-9)
    assert np.median(rel) < 0.25, f"median rel err {np.median(rel)}"


@pytest.mark.parametrize("ctxname", ["ctx1", "ctx22"])
@pytest.mark.parametrize("corrected", [False, True])
def test_top_anomalies_matches_bruteforce(request, ctxname, corrected):
    ctx = _ctx(request, ctxname)
    store, _, _ = _publish(ctx)  # RAM-backed
    h = store.latest()
    res = top_anomalies_from_store(store, 12, corrected=corrected)

    z = h.to_numpy().astype(np.float64)
    dist2 = ((z - z.mean(0)) ** 2).sum(1)
    if corrected:
        brute = dist2 - h.inv_deg().mean() - h.inv_deg()
    else:
        brute = h.vol * dist2
    order = np.argsort(-brute)[:12]
    np.testing.assert_allclose(res.val, brute[order], rtol=1e-4, atol=1e-4)
    assert set(res.idx.tolist()) == set(order.tolist())
    assert res.panels == 128 // h.panel_rows
    assert res.emb_id == "t0000"


@pytest.mark.parametrize("ctxname", ["ctx1", "ctx22"])
def test_nearest_neighbors_matches_bruteforce(request, ctxname):
    ctx = _ctx(request, ctxname)
    store, _, _ = _publish(ctx)
    h = store.latest()
    node = 41
    res = nearest_neighbors(store, node, 8)

    z = h.to_numpy().astype(np.float64)
    d = h.vol * ((z - z[node]) ** 2).sum(1)
    d[node] = np.inf  # self excluded in-kernel
    order = np.argsort(d)[:8]
    np.testing.assert_allclose(res.val, d[order], rtol=1e-4, atol=1e-3)
    assert set(res.idx.tolist()) == set(order.tolist())
    assert node not in res.idx


def test_bf16_artifact_query_close_to_raw(ctx1, tmp_path):
    store_raw, emb, _ = _publish(ctx1, root=tmp_path / "raw")
    store_bf16 = EmbeddingStore.create(
        tmp_path / "bf16", n=128, k=CFG.k_override, codec="bf16", seed=CFG.seed
    )
    store_bf16.put_embedding("t0000", emb.z, emb.vol, emb.op.deg)
    r_raw = top_anomalies_from_store(store_raw, 10)
    r_bf16 = top_anomalies_from_store(store_bf16, 10)
    # half-width storage, same ranking to within bf16 rounding
    assert len(set(r_raw.idx.tolist()) & set(r_bf16.idx.tolist())) >= 8
    np.testing.assert_allclose(r_bf16.val, r_raw.val, rtol=2e-2)
    assert r_bf16.bytes_read < r_raw.bytes_read


def test_topk_larger_than_n_pads_with_minus_one(ctx1):
    store, _, _ = _publish(ctx1, n=64)
    res = top_anomalies_from_store(store, 500)
    assert (res.idx >= 0).sum() == 64
    assert len(res.idx) == 64  # clamped to n, not padded past it


def test_query_registry_counters(ctx1):
    store, _, _ = _publish(ctx1)
    m0 = REGISTRY.snapshot()
    res = top_anomalies_from_store(store, 5)
    d = REGISTRY.delta(m0)
    assert d.get("query.calls") == 1
    assert d.get("query.panels", 0) >= 1
    assert d.get("query.bytes_read", 0) > 0
    assert res.latency_ms > 0  # the latency is the result's, not a counter
    assert "query.latency_ms" not in d
    # one query.panel span per panel, counted only while tracing is enabled
    assert "query.panel.calls" not in d
    enable_tracing()
    try:
        m0 = REGISTRY.snapshot()
        res = top_anomalies_from_store(store, 5)
        d = REGISTRY.delta(m0)
    finally:
        disable_tracing()
        tracer().clear()
    assert d.get("query.panel.calls") == d["query.panels"] == res.panels
    assert d.get("query.panel.seconds", 0) > 0


# ---------------------------------------------------------------------------
# the device-resident copy: fill on the first query, walk it on the next
# ---------------------------------------------------------------------------


def _random_store(root=None, *, n=96, k=8, codec="raw", emb_id="t0000", seed=0):
    rng = np.random.default_rng(seed)
    store = EmbeddingStore.create(root, n=n, k=k, codec=codec, seed=1, panel_rows=32)
    store.put_embedding(emb_id, rng.normal(size=(n, k)).astype(np.float32),
                        float(n), rng.uniform(1.0, 3.0, n).astype(np.float32))
    return store


def _ask(kind, store, k=6):
    if kind == "nearest_neighbors":
        return nearest_neighbors(store, 41, k)
    return top_anomalies_from_store(store, k)


def _brute(kind, h, k=6):
    z = h.to_numpy().astype(np.float64)
    if kind == "nearest_neighbors":
        d = h.vol * ((z - z[41]) ** 2).sum(1)
        d[41] = np.inf
        order = np.argsort(d)[:k]
    else:
        d = h.vol * ((z - z.mean(0)) ** 2).sum(1)
        order = np.argsort(-d)[:k]
    return order, d[order]


def _counted(kind, store):
    """One traced query and the registry counters it moved."""
    enable_tracing()
    try:
        m0 = REGISTRY.snapshot()
        res = _ask(kind, store)
        d = REGISTRY.delta(m0)
    finally:
        disable_tracing()
        tracer().clear()
    return res, d


@pytest.mark.parametrize("codec", ["raw", "bf16"])
@pytest.mark.parametrize("kind", ["nearest_neighbors", "top_anomalies"])
def test_resident_hit_is_bitwise_the_streamed_answer(kind, codec, tmp_path, host_resident):
    store = _random_store(tmp_path, codec=codec)
    reset_stream_stats()
    miss, d_miss = _counted(kind, store)
    peak = stream_stats().peak_live_bytes
    hit, d_hit = _counted(kind, store)

    assert d_miss.get("query.resident.fills") == 1 and "query.resident.hits" not in d_miss
    assert d_miss["query.bytes_read"] > 0
    # every kept panel and 1/deg slice, live at the fill's end
    assert d_miss["query.resident.bytes"] == peak == store.resident_nbytes()
    assert d_hit.get("query.resident.hits") == 1 and "query.resident.fills" not in d_hit
    assert d_hit.get("query.bytes_read", 0) == 0 and hit.bytes_read == 0
    for d, res in ((d_miss, miss), (d_hit, hit)):
        assert d["query.panels"] == d["query.panel.calls"] == res.panels == 3
    np.testing.assert_array_equal(hit.idx, miss.idx)
    np.testing.assert_array_equal(hit.val, miss.val)
    order, want = _brute(kind, store.latest())
    assert set(hit.idx.tolist()) == set(order.tolist())
    np.testing.assert_allclose(hit.val, want, rtol=1e-4, atol=1e-3)


def test_resident_copy_follows_puts_and_removes(host_resident):
    store = _random_store()
    first = _ask("top_anomalies", store)
    _ask("top_anomalies", store)
    assert store.resident("t0000", jax.devices()[0]) is not None

    # a new publish is served from its own artifact on its first query
    rng = np.random.default_rng(5)
    store.put_embedding("t0001", rng.normal(size=(96, 8)).astype(np.float32), 96.0,
                        rng.uniform(1.0, 3.0, 96).astype(np.float32))
    assert store.resident("t0000", jax.devices()[0]) is None  # a commit drops the copy
    m0 = REGISTRY.snapshot()
    newer = _ask("top_anomalies", store)
    assert REGISTRY.delta(m0).get("query.resident.fills") == 1
    assert newer.emb_id == "t0001" and store.resident("t0001", jax.devices()[0]) is not None
    order, want = _brute("top_anomalies", store.latest())
    assert set(newer.idx.tolist()) == set(order.tolist())
    np.testing.assert_allclose(newer.val, want, rtol=1e-4, atol=1e-3)

    # a re-put of the served id, or its removal, drops the kept copy
    z = store.latest().to_numpy()
    store.put_embedding("t0001", z, 96.0, np.full(96, 2.0, np.float32))
    assert store.resident("t0001", jax.devices()[0]) is None
    _ask("top_anomalies", store)
    assert store.resident("t0001", jax.devices()[0]) is not None
    store.remove_embedding("t0001")
    assert store.resident("t0001", jax.devices()[0]) is None
    again = _ask("top_anomalies", store)
    np.testing.assert_array_equal(again.idx, first.idx)
    np.testing.assert_array_equal(again.val, first.val)


def test_fill_streaming_across_a_put_is_not_kept(host_resident):
    """A fill that was streaming while the same store published is dropped."""
    store = _random_store()
    dev = jax.devices()[0]
    stale = store.resident_fill("t0000", dev, np.ones(96, np.float32))
    store.put_embedding("t0001", np.zeros((96, 8), np.float32), 1.0, np.ones(96))
    assert not store.finish_fill(stale)
    assert store.resident("t0000", dev) is None
    fresh = store.resident_fill("t0000", dev, np.ones(96, np.float32))
    assert store.finish_fill(fresh) and store.resident("t0000", dev) is fresh


@pytest.mark.parametrize("kind", ["nearest_neighbors", "top_anomalies"])
def test_no_budget_streams_every_query(kind, monkeypatch, host_resident):
    import repro.store.embstore as embstore

    kept = _ask(kind, _random_store())  # a store with a budget: streamed, then kept
    monkeypatch.setattr(embstore, "RESIDENT_SHARE", 0.0)
    store = _random_store()
    panel_bytes = 32 * 8 * 4
    for _ in range(2):
        reset_stream_stats()
        m0 = REGISTRY.snapshot()
        res = _ask(kind, store)
        d = REGISTRY.delta(m0)
        assert d["query.bytes_read"] > 0 and d["query.panels"] == 3
        assert stream_stats().peak_live_bytes <= 2 * panel_bytes  # panel-bounded
        assert not {"query.resident.hits", "query.resident.fills"} & set(d)
        assert store.resident("t0000", jax.devices()[0]) is None
        np.testing.assert_array_equal(res.idx, kept.idx)
        np.testing.assert_array_equal(res.val, kept.val)


def test_resident_bytes_and_budget(monkeypatch):
    """The read cell's artifact (n=259200, k=20, 27 panels of 9600 rows)
    keeps its Z panels at stored width and one float32 1/deg per row; the
    budget is a share of what the device has free, and nothing on a device
    that reports no memory stats."""
    import repro.store.embstore as embstore
    from repro.store.embstore import RESIDENT_SHARE, resident_budget

    store = EmbeddingStore.create(None, n=259200, k=20, panel_rows=9600)
    assert store.resident_nbytes() == 259200 * (20 * 4 + 4)
    bf16 = EmbeddingStore.create(None, n=259200, k=20, panel_rows=9600, codec="bf16")
    assert bf16.resident_nbytes() == 259200 * (20 * 2 + 4)

    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    limit = 16 * 2**30
    assert resident_budget(Chip({"bytes_limit": limit, "bytes_in_use": 0})) == int(
        RESIDENT_SHARE * limit)
    busy = Chip({"bytes_limit": limit, "bytes_in_use": limit - 2**30})
    assert resident_budget(busy) == int(RESIDENT_SHARE * 2**30)
    assert resident_budget(Chip({"bytes_limit": limit, "bytes_in_use": limit})) == 0
    assert resident_budget(Chip(None)) == resident_budget(Chip({})) == 0  # no stats
    monkeypatch.setattr(embstore, "NO_STATS_FREE_BYTES", 2**30)
    assert resident_budget(Chip(None)) == int(RESIDENT_SHARE * 2**30)


def test_no_memory_stats_streams_every_query():
    """The CPU reports no memory stats: a store keeps nothing there, and its
    queries stream Z each time, panel-bounded."""
    assert "bytes_limit" not in (jax.devices()[0].memory_stats() or {})
    store = _random_store()
    for kind in ("nearest_neighbors", "top_anomalies", "top_anomalies"):
        reset_stream_stats()
        m0 = REGISTRY.snapshot()
        res = _ask(kind, store)
        d = REGISTRY.delta(m0)
        assert d["query.bytes_read"] > 0 and res.bytes_read > 0
        assert not {"query.resident.hits", "query.resident.fills"} & set(d)
        assert stream_stats().peak_live_bytes <= 2 * 32 * 8 * 4
        assert store.resident("t0000", jax.devices()[0]) is None


# ---------------------------------------------------------------------------
# index validation + warm-start satellites
# ---------------------------------------------------------------------------


def test_commute_distance_block_rejects_bad_indices(ctx1):
    seq = gmm_graph_sequence(ctx1, 32, seed=0)
    cfg = CommuteConfig(eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4)
    emb = commute_time_embedding(ctx1, seq.a1, cfg)
    with pytest.raises(IndexError, match=r"rows index 32 .*n=32"):
        commute_distance_block(emb, np.array([0, 32]), np.array([1]))
    with pytest.raises(IndexError, match=r"cols index -33 .*n=32"):
        commute_distance_block(emb, np.array([0]), np.array([-33]))


def test_store_queries_reject_bad_indices(ctx1):
    store, _, _ = _publish(ctx1, n=64)
    with pytest.raises(IndexError, match=r"node index 64 .*n=64"):
        nearest_neighbors(store, 64)
    with pytest.raises(IndexError, match=r"rows index 99 .*n=64"):
        commute_block(store, [99], [0])


def test_warm_from_shape_mismatch_warns_and_counts(ctx1):
    seq = gmm_graph_sequence(ctx1, 32, seed=0)
    cfg = CommuteConfig(eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4)
    stale = np.zeros((32, 9), np.float32)  # k changed mid-stream
    m0 = REGISTRY.snapshot()
    with pytest.warns(RuntimeWarning, match="warm_from shape"):
        emb = commute_time_embedding(ctx1, seq.a1, cfg, warm_from=stale)
    assert REGISTRY.delta(m0).get("solve.warm_skipped") == 1
    assert emb.z.shape == (32, 4)  # cold solve still delivered


# ---------------------------------------------------------------------------
# labeled fixture + rank AUC
# ---------------------------------------------------------------------------


def test_labeled_fixture_plants_outliers(ctx1):
    seq = gmm_snapshot_sequence(ctx1, 64, 2, seed=0, anomaly_nodes=5, dim_nodes=6)
    assert seq.labels is not None and seq.labels.sum() == 5
    plain = gmm_snapshot_sequence(ctx1, 64, 2, seed=0)
    assert plain.labels is None
    # the clump is structurally planted: snapshot builds still work sharded
    a = np.asarray(next(iter(seq.snapshots())))
    assert a.shape == (64, 64) and np.isfinite(a).all()


def test_rank_auc():
    labels = np.array([0, 0, 0, 1, 1])
    assert rank_auc(labels, np.array([0.1, 0.2, 0.3, 0.8, 0.9])) == 1.0
    assert rank_auc(labels, np.array([0.9, 0.8, 0.7, 0.2, 0.1])) == 0.0
    assert rank_auc(labels, np.ones(5)) == 0.5  # all tied
    with pytest.raises(ValueError):
        rank_auc(np.zeros(4), np.arange(4))


def test_detector_publishes_to_store(ctx1, tmp_path):
    cfg = CommuteConfig(eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4)
    store = EmbeddingStore.create(tmp_path, n=32, k=4, seed=cfg.seed)
    seq = gmm_snapshot_sequence(ctx1, 32, 3, seed=0)
    det = SequenceDetector(ctx1, cfg, emb_store=store)
    for a in seq.snapshots():
        det.push(a)
    assert store.embedding_ids == ["t0000", "t0001", "t0002"]
    # the artifact is query-ready straight off the detector
    res = top_anomalies_from_store(store, 3)
    assert (res.idx >= 0).all()

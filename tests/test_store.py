"""TileStore + streaming executor: round-trip, resume, streamed == resident."""

import numpy as np
import pytest

from repro.core import (
    CommuteConfig,
    SequenceDetector,
    chain_build_count,
    detect_anomalies,
    detect_sequence_anomalies,
    reset_stream_stats,
    stream_stats,
)
from repro.graphs import gmm_store_sequence, gmm_snapshot_sequence, store_snapshot_sequence
from repro.store import TileStore

# Tiny accuracy knobs: store tests exercise plumbing, not convergence.
CFG = CommuteConfig(eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4)


def _sym(n: int, seed: int) -> np.ndarray:
    a = np.abs(np.random.default_rng(seed).normal(size=(n, n))).astype(np.float32)
    a = (a + a.T) / 2.0
    np.fill_diagonal(a, 0.0)
    return a


@pytest.fixture(params=["ctx1", "ctx22"])
def ctx(request):
    return request.getfixturevalue(request.param)


# ---------------------------------------------------------------------------
# manifest / tile round-trip
# ---------------------------------------------------------------------------


def test_manifest_tile_roundtrip(tmp_path):
    a = _sym(32, 0)
    store = TileStore.create(tmp_path / "s", n=32, grid=4)
    store.put_snapshot("t000", a)

    re = TileStore.open(tmp_path / "s")
    assert (re.n, re.grid, re.dtype) == (32, 4, np.dtype(np.float32))
    assert re.snapshot_ids == ["t000"]
    h = re.snapshot("t000")
    np.testing.assert_array_equal(h.to_numpy(), a)
    # tile-level read sees the exact block
    np.testing.assert_array_equal(np.asarray(re.read_tile("t000", 1, 2)), a[8:16, 16:24])
    # panels are tile-aligned
    np.testing.assert_array_equal(h.read_panel(8, 8), a[8:16])
    with pytest.raises(ValueError):
        h.read_panel(3, 8)


def test_ram_backend_roundtrip():
    a = _sym(16, 1)
    store = TileStore.create(None, n=16, grid=2)
    store.put_snapshot("x", a)
    np.testing.assert_array_equal(store.snapshot("x").to_numpy(), a)


def test_ram_backend_copies_on_put():
    """The store captures put-time values, not a view of the caller's array."""
    a = _sym(16, 1)
    want = a.copy()
    store = TileStore.create(None, n=16, grid=1)  # grid=1: whole-array tile
    store.put_snapshot("x", a)
    a[:] = 0.0
    np.testing.assert_array_equal(store.snapshot("x").to_numpy(), want)


def test_create_rejects_incompatible_geometry(tmp_path):
    TileStore.create(tmp_path / "s", n=32, grid=4)
    with pytest.raises(ValueError, match="incompatible"):
        TileStore.create(tmp_path / "s", n=32, grid=2)


def test_create_rejects_stale_content(tmp_path):
    """Same geometry but different content meta must not silently resume."""
    TileStore.create(tmp_path / "s", n=32, grid=4, meta={"dataset": "gmm", "seed": 0})
    # same meta resumes fine
    TileStore.create(tmp_path / "s", n=32, grid=4, meta={"dataset": "gmm", "seed": 0})
    with pytest.raises(ValueError, match="different content"):
        TileStore.create(tmp_path / "s", n=32, grid=4, meta={"dataset": "climate", "seed": 0})
    # meta survives reopen
    assert TileStore.open(tmp_path / "s").manifest.meta == {"dataset": "gmm", "seed": 0}

    # an unlabeled store WITH committed snapshots must not adopt a new label
    unlabeled = TileStore.create(tmp_path / "u", n=16, grid=2)
    unlabeled.put_snapshot("t000", _sym(16, 9))
    with pytest.raises(ValueError, match="different content"):
        TileStore.create(tmp_path / "u", n=16, grid=2, meta={"dataset": "gmm"})
    # ... but an empty unlabeled store may be stamped and resumed
    TileStore.create(tmp_path / "e", n=16, grid=2)
    TileStore.create(tmp_path / "e", n=16, grid=2, meta={"dataset": "gmm"})
    assert TileStore.open(tmp_path / "e").manifest.meta == {"dataset": "gmm"}


# ---------------------------------------------------------------------------
# resume after partial write
# ---------------------------------------------------------------------------


def test_resume_after_partial_write(tmp_path):
    a = _sym(32, 2)
    store = TileStore.create(tmp_path / "s", n=32, grid=4)

    # simulate a crash: write 5 of 16 tiles, never commit
    w = store.writer("t000")
    for r, c in w.missing_tiles()[:5]:
        w.put_tile(r, c, a[r * 8 : r * 8 + 8, c * 8 : c * 8 + 8])
    with pytest.raises(ValueError, match="incomplete"):
        w.commit()

    # a fresh open sees no committed snapshot, but the tiles survived
    re = TileStore.create(tmp_path / "s", n=32, grid=4)
    assert re.snapshot_ids == []
    w2 = re.writer("t000")
    assert len(w2.missing_tiles()) == 11  # resumes, doesn't rewrite
    with w2:
        for r, c in w2.missing_tiles():
            w2.put_tile(r, c, a[r * 8 : r * 8 + 8, c * 8 : c * 8 + 8])
    assert re.snapshot_ids == ["t000"]
    np.testing.assert_array_equal(re.snapshot("t000").to_numpy(), a)

    # put_snapshot on a committed id is a no-op resume, not a rewrite
    re.put_snapshot("t000", a)
    assert re.snapshot_ids == ["t000"]


def test_store_writer_sequence_resumes(tmp_path, ctx1):
    seq = gmm_snapshot_sequence(ctx1, 32, 3, seed=5, inject_p=0.02)
    store = TileStore.create(tmp_path / "s", n=32, grid=2)
    ids = store_snapshot_sequence(store, seq)
    assert store.snapshot_ids == ids == ["t0000", "t0001", "t0002"]
    # re-running skips everything already committed
    again = store_snapshot_sequence(store, gmm_snapshot_sequence(ctx1, 32, 3, seed=5, inject_p=0.02))
    assert again == ids


# ---------------------------------------------------------------------------
# streamed == resident, bitwise (1x1 and 2x2 meshes)
# ---------------------------------------------------------------------------


def test_streamed_detect_bitwise_equals_resident(ctx, tmp_path):
    n = 32
    a1, a2 = _sym(n, 3), _sym(n, 4)
    store = TileStore.create(tmp_path / "s", n=n, grid=4)
    h1, h2 = store.put_snapshot("t0", a1), store.put_snapshot("t1", a2)

    res_r = detect_anomalies(ctx, ctx.put_matrix(a1), ctx.put_matrix(a2), CFG, top_k=5)
    res_s = detect_anomalies(ctx, h1, h2, CFG, top_k=5)
    np.testing.assert_array_equal(np.asarray(res_s.scores), np.asarray(res_r.scores))
    np.testing.assert_array_equal(np.asarray(res_s.top_idx), np.asarray(res_r.top_idx))

    # mixed resident/store endpoints stream too
    res_m = detect_anomalies(ctx, ctx.put_matrix(a1), h2, CFG, top_k=5)
    np.testing.assert_array_equal(np.asarray(res_m.scores), np.asarray(res_r.scores))


def test_streamed_sequence_bitwise_equals_resident(ctx):
    n, t_steps = 32, 3
    snaps = [_sym(n, 10 + t) for t in range(t_steps)]
    store = TileStore.create(None, n=n, grid=2)  # RAM-backed
    for t, s in enumerate(snaps):
        store.put_snapshot(f"t{t}", s)

    res_r = detect_sequence_anomalies(ctx, (ctx.put_matrix(s) for s in snaps), CFG, top_k=5)
    builds0 = chain_build_count()
    res_s = detect_sequence_anomalies(ctx, store.iter_snapshots(), CFG, top_k=5)
    assert chain_build_count() - builds0 == t_steps  # one chain build per snapshot
    for a, b in zip(res_r.transitions, res_s.transitions):
        np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    np.testing.assert_array_equal(
        np.asarray(res_r.global_top_val), np.asarray(res_s.global_top_val)
    )


def test_streamed_residency_bounded_by_panels(ctx1):
    """The executor holds at most two in-flight panels per streamed operand."""
    n, grid = 64, 8
    snaps = [_sym(n, 20 + t) for t in range(2)]
    store = TileStore.create(None, n=n, grid=grid)
    for t, s in enumerate(snaps):
        store.put_snapshot(f"t{t}", s)
    panel_bytes = (n // grid) * n * 4

    reset_stream_stats()
    detect_anomalies(ctx1, store.snapshot("t0"), store.snapshot("t1"), CFG, top_k=5)
    st = stream_stats()
    assert st.panels > 0
    # scoring streams two operands, double-buffered: <= 4 panels live
    assert st.peak_live_bytes <= 4 * panel_bytes
    assert st.bytes_h2d >= 2 * n * n * 4  # both endpoints streamed at least once


def test_streamed_fuse_l_close_and_counted(ctx1):
    """The streamed fuse_l chain build (per-panel GEMM accumulation) stays
    allclose to the resident fuse_l run and its panels enter stream_stats."""
    n = 32
    a1, a2 = _sym(n, 30), _sym(n, 31)
    store = TileStore.create(None, n=n, grid=4)
    h1, h2 = store.put_snapshot("t0", a1), store.put_snapshot("t1", a2)
    cfg = CommuteConfig(eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4, fuse_l=True)

    res_r = detect_anomalies(ctx1, ctx1.put_matrix(a1), ctx1.put_matrix(a2), cfg, top_k=5)
    reset_stream_stats()
    res_s = detect_anomalies(ctx1, h1, h2, cfg, top_k=5)
    np.testing.assert_allclose(
        np.asarray(res_s.scores), np.asarray(res_r.scores), rtol=1e-4, atol=1e-3
    )
    # 2 embeddings x (degrees + S build + fuse_l GEMM + edge proj) + scorer,
    # each >= grid panels; the fuse_l GEMM's H2D must be accounted too.
    assert stream_stats().panels >= 9 * 4


# ---------------------------------------------------------------------------
# out-of-core chain: allclose scores, panel-bounded residency
# ---------------------------------------------------------------------------


def test_oocore_chain_scores_allclose(ctx, tmp_path):
    """chain_product(oocore=True) end-to-end: scores allclose (rtol<=1e-4) to
    the resident build on 1x1 and 2x2 meshes, adjacency AND chain streamed."""
    n = 32
    a1, a2 = _sym(n, 40), _sym(n, 41)
    store = TileStore.create(tmp_path / "s", n=n, grid=4)
    h1, h2 = store.put_snapshot("t0", a1), store.put_snapshot("t1", a2)
    cfg_oo = CommuteConfig(
        eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4, oocore=True
    )

    res_r = detect_anomalies(ctx, ctx.put_matrix(a1), ctx.put_matrix(a2), CFG, top_k=5)
    res_o = detect_anomalies(ctx, h1, h2, cfg_oo, top_k=5)
    np.testing.assert_allclose(
        np.asarray(res_o.scores), np.asarray(res_r.scores), rtol=1e-4, atol=1e-3
    )

    # resident-adjacency input with an out-of-core chain also works
    res_m = detect_anomalies(ctx, ctx.put_matrix(a1), ctx.put_matrix(a2), cfg_oo, top_k=5)
    np.testing.assert_allclose(
        np.asarray(res_m.scores), np.asarray(res_r.scores), rtol=1e-4, atol=1e-3
    )


def test_oocore_chain_residency_bounded_by_panels(ctx1):
    """During an out-of-core chain build, peak live panel bytes stay under
    2 * panel * n * 4 bytes per GEMM operand (left, right, accumulator) --
    bounded by panels, not by the 5 * n^2 resident working set."""
    from repro.core import chain_product

    n, grid = 64, 8
    store = TileStore.create(None, n=n, grid=grid)
    h = store.put_snapshot("t0", _sym(n, 42))
    work = TileStore.create(None, n=n, grid=grid)
    ph = n // grid

    reset_stream_stats()
    op = chain_product(
        ctx1, h, 3, schedule="xla", oocore=True, oocore_work=work, oocore_panel_rows=ph
    )
    st = stream_stats()
    panel_bytes = ph * n * 4
    assert st.panels > 0
    assert st.peak_live_bytes <= 3 * 2 * panel_bytes  # 2 panels per GEMM operand
    assert st.peak_live_bytes < 5 * n * n * 4  # and far under the resident set
    # the operator itself is store-backed: the solver streams it
    assert hasattr(op.p1, "read_panel") and hasattr(op.p2, "read_panel")
    # intermediates were retired: only P1 and P2 survive in the scratch
    assert len(work.snapshot_ids) == 2


def test_oocore_chain_sequence_retires_scratch(ctx1, tmp_path):
    """Each operator's scratch snapshots are retired once its solve is done
    -- with or without donate -- so a disk scratch holds at most the push in
    flight, not the sequence.  The user's input store is never touched."""
    n = 32
    scratch = tmp_path / "scratch"
    cfg_oo = CommuteConfig(
        eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4,
        oocore=True, oocore_dir=str(scratch),
    )
    store = TileStore.create(None, n=n, grid=4)
    for t in range(4):
        store.put_snapshot(f"t{t}", _sym(n, 50 + t))
    det = SequenceDetector(ctx1, cfg_oo, top_k=5)  # donate=False
    res = det.run(store.iter_snapshots())
    assert len(res.transitions) == 3
    assert store.snapshot_ids == ["t0", "t1", "t2", "t3"]  # user data untouched
    # each operator's P1 + P2 are retired once its solve is done (scoring
    # reads only z and vol), so nothing of the window remains
    assert len(TileStore.open(scratch).snapshot_ids) == 0


# ---------------------------------------------------------------------------
# tile codecs: round-trip, fingerprint, accuracy contracts
# ---------------------------------------------------------------------------


def test_bf16_codec_roundtrip_halves_stored_bytes(tmp_path):
    from repro.store.tilestore import _bf16_u16_to_f32, _f32_to_bf16_u16

    n = 32
    a = _sym(n, 60)
    want = _bf16_u16_to_f32(_f32_to_bf16_u16(a))  # bf16-rounded values
    store = TileStore.create(tmp_path / "s", n=n, grid=1, codec="bf16")
    h = store.put_snapshot("t", a)
    np.testing.assert_array_equal(h.to_numpy(), want)
    # the rounding is the documented contract: relative error <= 2^-8
    np.testing.assert_allclose(want, a, rtol=2 ** -8, atol=1e-7)
    # stored bytes are half the logical bytes (modulo .npy headers)
    _, stored = h.read_panel_info(0, n)
    assert stored < 0.6 * n * n * 4
    # survives reopen (codec comes from the manifest, not the caller)
    np.testing.assert_array_equal(TileStore.open(tmp_path / "s").snapshot("t").to_numpy(), want)


def test_codec_joins_geometry_fingerprint(tmp_path):
    TileStore.create(tmp_path / "s", n=32, grid=4, codec="bf16")
    with pytest.raises(ValueError, match="codec"):
        TileStore.create(tmp_path / "s", n=32, grid=4)  # raw != bf16: loud error
    with pytest.raises(ValueError, match="unknown tile codec"):
        TileStore.create(tmp_path / "x", n=32, grid=4, codec="lz77")
    # bf16 squeezes an 8-bit mantissa: wider store dtypes must error loudly
    with pytest.raises(ValueError, match="float32"):
        TileStore.create(tmp_path / "y", n=32, grid=4, dtype="float64", codec="bf16")


def test_zstd_roundtrip_or_clean_fallback(tmp_path):
    """With a zstd backend: lossless round-trip.  Without: create() falls back
    to raw with a warning and the manifest records what the tiles really are."""
    from repro.store.tilestore import _zstd_backend

    a = _sym(32, 61)
    if _zstd_backend() is None:
        with pytest.warns(UserWarning, match="falling back"):
            store = TileStore.create(tmp_path / "s", n=32, grid=2, codec="zstd")
        assert store.manifest.codec == "raw"
        h = store.put_snapshot("t", a)
        np.testing.assert_array_equal(h.to_numpy(), a)
    else:
        store = TileStore.create(tmp_path / "s", n=32, grid=2, codec="zstd")
        assert store.manifest.codec == "zstd"
        h = store.put_snapshot("t", a)
        np.testing.assert_array_equal(h.to_numpy(), a)  # zstd is lossless
        _, stored = h.read_panel_info(0, 32)
        assert stored != 32 * 32 * 4  # actually compressed


def test_streamed_bf16_scores_bitwise_vs_resident_on_rounded(ctx1):
    """The bf16 codec's accuracy contract: rounding happens once at write
    time, and the streamed run is *bitwise* identical to a resident run on
    the rounded adjacencies -- the codec never adds compute-path error."""
    from repro.store.tilestore import _bf16_u16_to_f32, _f32_to_bf16_u16

    n = 32
    a1, a2 = _sym(n, 62), _sym(n, 63)
    store = TileStore.create(None, n=n, grid=4, codec="bf16")
    h1, h2 = store.put_snapshot("t0", a1), store.put_snapshot("t1", a2)
    r1 = _bf16_u16_to_f32(_f32_to_bf16_u16(a1))
    r2 = _bf16_u16_to_f32(_f32_to_bf16_u16(a2))

    res_s = detect_anomalies(ctx1, h1, h2, CFG, top_k=5)
    res_r = detect_anomalies(ctx1, ctx1.put_matrix(r1), ctx1.put_matrix(r2), CFG, top_k=5)
    np.testing.assert_array_equal(np.asarray(res_s.scores), np.asarray(res_r.scores))


def test_oocore_bf16_scratch_scores_close(ctx1):
    """bf16 *scratch* rounds the working matrices at every level: looser
    contract (documented in the README codec table), still anomaly-ranking
    grade."""
    n = 32
    a1, a2 = _sym(n, 64), _sym(n, 65)
    store = TileStore.create(None, n=n, grid=4)
    h1, h2 = store.put_snapshot("t0", a1), store.put_snapshot("t1", a2)
    cfg = CommuteConfig(
        eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4,
        oocore=True, tile_codec="bf16",
    )
    res_r = detect_anomalies(ctx1, ctx1.put_matrix(a1), ctx1.put_matrix(a2), CFG, top_k=5)
    res_o = detect_anomalies(ctx1, h1, h2, cfg, top_k=5)
    np.testing.assert_allclose(
        np.asarray(res_o.scores), np.asarray(res_r.scores), rtol=5e-2, atol=5e-2
    )


# ---------------------------------------------------------------------------
# iteration-batched Richardson: fewer scratch reads, identical scores
# ---------------------------------------------------------------------------


def test_solver_batch_cuts_scratch_reads_scores_allclose(ctx):
    """Acceptance: solver_batch=4 drops solve-phase scratch reads >= 2x and
    out-of-core scores stay allclose (rtol <= 1e-4) to resident, on the 1x1
    and 2x2 meshes."""
    from repro.core import chain_product, estimate_solution
    from repro.core.embedding import edge_projection

    n, d, q = 32, 3, 9
    a1, a2 = _sym(n, 70), _sym(n, 71)
    store = TileStore.create(None, n=n, grid=4)
    h1, h2 = store.put_snapshot("t0", a1), store.put_snapshot("t1", a2)

    # solve-phase traffic, measured directly on one operator
    op = chain_product(ctx, h1, d, oocore=True)
    y = edge_projection(ctx, h1, 0, 4)
    reads, sols = {}, {}
    for batch in (1, 4):
        reset_stream_stats()
        sols[batch] = np.asarray(estimate_solution(ctx, op, y, q, solver_batch=batch))
        reads[batch] = stream_stats().bytes_read
    op.release_scratch()
    assert reads[1] >= 2 * reads[4]
    # replayed panels are bitwise: batching cannot change the solution
    np.testing.assert_array_equal(sols[1], sols[4])

    # end-to-end: batched oocore detect stays allclose to resident
    cfg_oo = CommuteConfig(
        eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4,
        oocore=True, solver_batch=4, prefetch_depth=4,
    )
    res_r = detect_anomalies(ctx, ctx.put_matrix(a1), ctx.put_matrix(a2), CFG, top_k=5)
    res_o = detect_anomalies(ctx, h1, h2, cfg_oo, top_k=5)
    np.testing.assert_allclose(
        np.asarray(res_o.scores), np.asarray(res_r.scores), rtol=1e-4, atol=1e-3
    )


def test_stream_stats_byte_counters_track_codec(ctx1):
    """bytes_read (pre-codec) vs bytes_decoded (post-codec): raw moves them
    together; bf16 reads roughly half of what it decodes."""
    n = 32
    a1, a2 = _sym(n, 72), _sym(n, 73)
    ratios = {}
    for codec in ("raw", "bf16"):
        store = TileStore.create(None, n=n, grid=4, codec=codec)
        h1, h2 = store.put_snapshot("t0", a1), store.put_snapshot("t1", a2)
        reset_stream_stats()
        detect_anomalies(ctx1, h1, h2, CFG, top_k=5)
        st = stream_stats()
        assert st.bytes_decoded > 0
        ratios[codec] = st.bytes_read / st.bytes_decoded
    assert ratios["raw"] == pytest.approx(1.0)  # RAM raw backend: no headers
    assert ratios["bf16"] == pytest.approx(0.5)


def test_out_of_core_writer_matches_resident_build(ctx1):
    """gmm_store_sequence (numpy, tile-by-tile) == similarity_graph (sharded)."""
    from repro.graphs import gmm_points, similarity_graph

    n = 32
    store = TileStore.create(None, n=n, grid=4)
    (sid,) = gmm_store_sequence(store, 1, seed=7)
    pts, _ = gmm_points(n, 7)
    resident = np.asarray(similarity_graph(ctx1, pts))
    np.testing.assert_allclose(store.snapshot(sid).to_numpy(), resident, rtol=1e-6, atol=1e-6)

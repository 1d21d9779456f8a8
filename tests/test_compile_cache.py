"""Retrace budget: the tile-program compile cache pays each compile once.

Guards the "a T-snapshot run retraces the same ~5 programs T times"
regression (ROADMAP) forever: tile bodies execute in Python only while jax
traces them, so ``program_cache_stats().traces`` is an exact count of tile
program (re)traces, and a steady-state snapshot push must add zero.
"""

import numpy as np
import pytest

from repro.core import (
    CommuteConfig,
    SequenceDetector,
    detect_anomalies,
    program_cache_stats,
)
from repro.core.tiles import tile_map

CFG = CommuteConfig(eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4)


def _sym(n: int, seed: int) -> np.ndarray:
    a = np.abs(np.random.default_rng(seed).normal(size=(n, n))).astype(np.float32)
    a = (a + a.T) / 2.0
    np.fill_diagonal(a, 0.0)
    return a


def test_tile_map_traces_body_once(ctx1):
    """Trace-counting body: repeated tile_map calls with the same body and
    geometry reuse one compiled program (the body's Python code runs once)."""
    traces = []

    def body(tile, blk):
        traces.append(1)
        return blk

    x = ctx1.put_matrix(np.zeros((16, 16), np.float32))
    tile_map(ctx1, body, x)
    tile_map(ctx1, body, x)
    tile_map(ctx1, body, x)
    assert len(traces) == 1

    # a different geometry is a different program: exactly one more trace
    y = ctx1.put_matrix(np.zeros((32, 32), np.float32))
    tile_map(ctx1, body, y)
    assert len(traces) == 2


def test_fresh_lambda_misses_safely(ctx1):
    """Per-call lambdas (which may close over data) never false-hit."""
    x = ctx1.put_matrix(np.full((16, 16), 2.0, np.float32))
    outs = []
    for scale in (1.0, 3.0):
        outs.append(np.asarray(tile_map(ctx1, lambda tile, blk: blk * scale, x)))
    np.testing.assert_allclose(outs[0], 2.0)
    np.testing.assert_allclose(outs[1], 6.0)


@pytest.mark.parametrize("schedule", ["xla", "cannon"])
def test_second_transition_zero_new_compiles(ctx1, schedule):
    """Acceptance: the second snapshot pair compiles nothing new."""
    cfg = CommuteConfig(eps_rp=1e-2, d=3, q=3, schedule=schedule, k_override=4)
    n = 32
    detect_anomalies(ctx1, ctx1.put_matrix(_sym(n, 0)), ctx1.put_matrix(_sym(n, 1)), cfg)
    st = program_cache_stats()
    t0, m0 = st.traces, st.misses
    detect_anomalies(ctx1, ctx1.put_matrix(_sym(n, 2)), ctx1.put_matrix(_sym(n, 3)), cfg)
    assert st.traces == t0, "second transition retraced a tile program"
    assert st.misses == m0, "second transition missed the program cache"


def test_sequence_retrace_budget(ctx1):
    """4-snapshot SequenceDetector run: every tile program compiles exactly
    once.  Snapshot 1 compiles the chain/embedding programs, snapshot 2 adds
    only the (first-use) scorer programs; snapshots 3 and 4 add zero."""
    snaps = [_sym(32, 10 + t) for t in range(4)]
    det = SequenceDetector(ctx1, CFG, top_k=5)
    st = program_cache_stats()
    det.push(ctx1.put_matrix(snaps[0]))
    after_first = st.traces
    det.push(ctx1.put_matrix(snaps[1]))  # first transition: scorer compiles
    warm_traces, warm_misses = st.traces, st.misses
    det.push(ctx1.put_matrix(snaps[2]))
    det.push(ctx1.put_matrix(snaps[3]))
    res = det.finalize()
    assert len(res.transitions) == 3
    assert st.traces == warm_traces, "steady-state push retraced a tile program"
    assert st.misses == warm_misses, "steady-state push missed the program cache"
    assert st.hits > 0
    assert after_first > 0  # sanity: the cold build did trace programs


def test_adaptive_solver_retrace_budget(ctx1):
    """The lax.while_loop solve driver keeps the retrace budget: steady-state
    pushes add ZERO traces/program-cache misses, and because the tolerance,
    the step cap and the Chebyshev interval bound are *operands* (not trace
    constants), changing them between runs must not compile anything new."""
    from dataclasses import replace

    cfg = CommuteConfig(
        eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4,
        solver="chebyshev", solver_tol=1e-4,
    )
    snaps = [_sym(32, 40 + t) for t in range(4)]
    det = SequenceDetector(ctx1, cfg, top_k=5)
    det.push(ctx1.put_matrix(snaps[0]))
    det.push(ctx1.put_matrix(snaps[1]))
    st = program_cache_stats()
    warm_traces, warm_misses = st.traces, st.misses
    det.push(ctx1.put_matrix(snaps[2]))
    det.push(ctx1.put_matrix(snaps[3]))
    assert st.traces == warm_traces, "steady-state adaptive push retraced"
    assert st.misses == warm_misses, "steady-state adaptive push missed the cache"

    # different tolerance / cap, same geometry: still zero new programs
    det2 = SequenceDetector(
        ctx1, replace(cfg, solver_tol=1e-6, solver_max_iters=7), top_k=5
    )
    det2.push(ctx1.put_matrix(snaps[0]))
    det2.push(ctx1.put_matrix(snaps[1]))
    assert st.traces == warm_traces, "tolerance change retraced a program"
    assert st.misses == warm_misses, "tolerance leaked into a program cache key"


def test_warm_cg_retrace_budget(ctx1):
    """Warm-started CG keeps the retrace budget: y0 is an *operand* of one
    compiled program (cold pushes pass y0 = chi through the same program), so
    steady-state pushes of a warm CG sequence add ZERO traces and ZERO cache
    misses -- and a different tolerance / step cap still compiles nothing."""
    from dataclasses import replace

    cfg = CommuteConfig(
        eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4,
        solver="cg", solver_tol=1e-4, warm_start=True,
    )
    snaps = [_sym(32, 60 + t) for t in range(4)]
    det = SequenceDetector(ctx1, cfg, top_k=5)
    det.push(ctx1.put_matrix(snaps[0]))  # cold solve compiles the CG program
    det.push(ctx1.put_matrix(snaps[1]))  # first warm solve: same program
    st = program_cache_stats()
    warm_traces, warm_misses = st.traces, st.misses
    det.push(ctx1.put_matrix(snaps[2]))
    det.push(ctx1.put_matrix(snaps[3]))
    assert st.traces == warm_traces, "steady-state warm CG push retraced"
    assert st.misses == warm_misses, "steady-state warm CG push missed the cache"

    # tolerance / cap are operands of the CG program too
    det2 = SequenceDetector(
        ctx1, replace(cfg, solver_tol=1e-5, solver_max_iters=9), top_k=5
    )
    det2.push(ctx1.put_matrix(snaps[0]))
    det2.push(ctx1.put_matrix(snaps[1]))
    assert st.traces == warm_traces, "tolerance change retraced the CG program"
    assert st.misses == warm_misses, "tolerance leaked into the CG cache key"


def test_incremental_chain_retrace_budget(ctx1):
    """The delta-chain path keeps the retrace budget: its factor algebra runs
    eagerly (host QR/SVD + rowblock passes), so the only new compiled program
    is the corrected resident solve loop -- keyed once by correction rank on
    the FIRST incremental push.  Steady-state incremental pushes add ZERO
    traces and ZERO program-cache misses."""
    from repro.core import CommuteConfig as _Cfg

    cfg = _Cfg(
        eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4,
        solver="cg", solver_tol=1e-4, warm_start=True,
        incremental_chain=True, delta_rank=4, delta_budget=0.5,
    )
    # slowly-drifting snapshots: a0 plus a small symmetric perturbation per
    # step, so the drift monitor accepts every transition after the base build
    a0 = _sym(32, 70)
    snaps = [
        np.abs(a0 + 2e-3 * t * _sym(32, 71 + t)).astype(np.float32)
        for t in range(4)
    ]
    det = SequenceDetector(ctx1, cfg, top_k=5)
    det.push(ctx1.put_matrix(snaps[0]))  # full base build
    det.push(ctx1.put_matrix(snaps[1]))  # first delta: corrected CG compiles
    st = program_cache_stats()
    warm_traces, warm_misses = st.traces, st.misses
    det.push(ctx1.put_matrix(snaps[2]))
    det.push(ctx1.put_matrix(snaps[3]))
    res = det.finalize()
    assert st.traces == warm_traces, "steady-state incremental push retraced"
    assert st.misses == warm_misses, "steady-state incremental push missed the cache"
    # sanity: the steady-state pushes really were delta updates, not rebuilds
    for m in res.transition_metrics[1:]:
        assert m.get("chain.incremental_updates", 0.0) == 1.0


def test_streamed_sequence_retrace_budget(ctx1):
    """The retrace budget holds out-of-core too: store-backed snapshots and
    the oocore chain reuse one compiled program set across the sequence."""
    from repro.store import TileStore

    n = 32
    cfg = CommuteConfig(eps_rp=1e-2, d=3, q=3, schedule="xla", k_override=4, oocore=True)
    store = TileStore.create(None, n=n, grid=4)
    for t in range(4):
        store.put_snapshot(f"t{t}", _sym(n, 20 + t))
    det = SequenceDetector(ctx1, cfg, top_k=5)
    it = store.iter_snapshots()
    det.push(next(it))
    det.push(next(it))
    st = program_cache_stats()
    warm_traces, warm_misses = st.traces, st.misses
    det.push(next(it))
    det.push(next(it))
    assert st.traces == warm_traces
    assert st.misses == warm_misses


def test_query_path_retrace_budget(ctx1):
    """Artifact publishing and repeated queries stay off the retrace path:
    ``push`` publishes with host numpy only (zero tile programs), and every
    panel of every query reuses one compiled kernel program (the running
    top-k state threads through as operands, so shapes never change)."""
    from repro.core.query import nearest_neighbors, top_anomalies_from_store
    from repro.store.embstore import EmbeddingStore

    n = 32
    store = EmbeddingStore.create(
        None, n=n, k=CFG.k_override, panel_rows=8, seed=CFG.seed
    )
    det = SequenceDetector(ctx1, CFG, top_k=5, emb_store=store)
    det.push(ctx1.put_matrix(_sym(n, 40)))
    det.push(ctx1.put_matrix(_sym(n, 41)))
    top_anomalies_from_store(store, 5)  # warm-up: kernel compiles here
    nearest_neighbors(store, 3, 5)
    st = program_cache_stats()
    warm_traces, warm_misses = st.traces, st.misses
    det.push(ctx1.put_matrix(_sym(n, 42)))
    det.push(ctx1.put_matrix(_sym(n, 43)))
    for _ in range(3):
        top_anomalies_from_store(store, 5)
        top_anomalies_from_store(store, 5, corrected=True)
        nearest_neighbors(store, 7, 5)
    assert st.traces == warm_traces, "query path retraced a tile program"
    assert st.misses == warm_misses, "query path missed the program cache"


def test_persistent_cache_dir_follows_env_else_checkout():
    """Entry points leave JAX_COMPILATION_CACHE_DIR to JAX when it is set;
    otherwise the persistent cache goes to the fixed <checkout>/.jax_cache.
    (Only the choice is tested: tests never turn the cache on.)"""
    from pathlib import Path

    from repro.launch.cache import compile_cache_dir

    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}) is None
    path = Path(compile_cache_dir({}))
    assert path.name == ".jax_cache"
    assert (path.parent / "pyproject.toml").exists()
    assert compile_cache_dir({}) == str(path)

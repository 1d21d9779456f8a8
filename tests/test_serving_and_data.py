"""Serving engine + data pipeline integration tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.data import DataConfig, Prefetcher, global_batch_for, host_batch
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.models.common import ArchConfig
from repro.serving import ServeConfig, ServeEngine

TINY = ArchConfig(
    name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=256, remat=False, compute_dtype="float32",
)


def test_serve_engine_greedy_deterministic():
    mesh = make_mesh(1, 1)
    spec = lm.build_spec(TINY)
    params = lm.init_params(spec, jax.random.PRNGKey(0))
    eng = ServeEngine(spec, mesh, params, s_max=24, batch=2,
                      cfg=ServeConfig(max_new_tokens=6))
    prompts = np.random.default_rng(0).integers(0, 256, size=(2, 8)).astype(np.int32)
    a = eng.generate(prompts)
    # rebuild (decode donates its cache) and confirm determinism
    eng2 = ServeEngine(spec, mesh, params, s_max=24, batch=2,
                       cfg=ServeConfig(max_new_tokens=6))
    b = eng2.generate(prompts)
    assert a.shape == (2, 6)
    np.testing.assert_array_equal(a, b)
    assert (a < 256).all()


def test_serve_engine_temperature_sampling():
    mesh = make_mesh(1, 1)
    spec = lm.build_spec(TINY)
    params = lm.init_params(spec, jax.random.PRNGKey(0))
    eng = ServeEngine(spec, mesh, params, s_max=24, batch=2,
                      cfg=ServeConfig(max_new_tokens=8, temperature=1.0, seed=1))
    prompts = np.random.default_rng(0).integers(0, 256, size=(2, 8)).astype(np.int32)
    out = eng.generate(prompts)
    assert out.shape == (2, 8) and (out < 256).all()


def test_serve_sharded_matches_single(mesh22):
    spec = lm.build_spec(TINY)
    params = lm.init_params(spec, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(1).integers(0, 256, size=(4, 8)).astype(np.int32)
    outs = []
    for mesh in (make_mesh(1, 1), mesh22):
        eng = ServeEngine(spec, mesh, params, s_max=16, batch=4,
                          cfg=ServeConfig(max_new_tokens=4))
        outs.append(eng.generate(prompts))
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_host_batch_shapes_and_range():
    cfg = DataConfig(vocab=1000, seq_len=64, global_batch=8)
    b = host_batch(cfg, 0)
    assert b["tokens"].shape == (8, 64) and b["labels"].shape == (8, 64)
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 1000


def test_global_batch_matches_host(mesh22):
    cfg = DataConfig(vocab=512, seq_len=32, global_batch=8)
    ref = host_batch(cfg, 3)
    with mesh22:
        gb = global_batch_for(cfg, 3, mesh22, P("data", None))
    np.testing.assert_array_equal(np.asarray(gb["tokens"]), ref["tokens"])
    np.testing.assert_array_equal(np.asarray(gb["labels"]), ref["labels"])


def test_batches_differ_across_steps():
    cfg = DataConfig(vocab=512, seq_len=32, global_batch=4)
    assert not np.array_equal(host_batch(cfg, 0)["tokens"], host_batch(cfg, 1)["tokens"])


def test_prefetcher_produces_sequence():
    cfg = DataConfig(vocab=128, seq_len=16, global_batch=2)
    pf = Prefetcher(cfg, start_step=0)
    try:
        b0, b1 = pf.next(), pf.next()
        np.testing.assert_array_equal(b0["tokens"], host_batch(cfg, 0)["tokens"])
        np.testing.assert_array_equal(b1["tokens"], host_batch(cfg, 1)["tokens"])
    finally:
        pf.close()


def test_frames_emitted_for_encdec():
    cfg = DataConfig(vocab=128, seq_len=16, global_batch=2, frames_dim=32)
    b = host_batch(cfg, 0)
    assert b["frames"].shape == (2, 16, 32)
    assert np.all(np.isfinite(b["frames"]))

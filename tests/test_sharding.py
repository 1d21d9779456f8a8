"""Distribution invariants: sharded == single-device results, multi-pod
rules, spec sanitization, compressed gradient sync."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.models import common as cm
from repro.models import lm
from repro.models.common import ArchConfig
from repro.training import OptConfig, make_train_step
from repro.training.train_step import (
    compressed_pod_allreduce,
    init_state,
)

TINY = ArchConfig(
    name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=256, remat=False, compute_dtype="float32",
)


def _batch(b=4, s=32):
    key = jax.random.PRNGKey(0)
    tok = jax.random.randint(key, (b, s), 0, 256)
    return {"tokens": tok, "labels": tok}


@pytest.mark.slow
def test_loss_invariant_to_mesh(mesh22):
    """Same params + batch -> same loss on 1x1 and 2x2 meshes."""
    spec = lm.build_spec(TINY)
    batch = _batch()
    ocfg = OptConfig(lr=1e-3)
    losses = {}
    for mesh in (make_mesh(1, 1), mesh22):
        step, *_ = make_train_step(spec, mesh, ocfg, donate=False)
        params, opt = init_state(spec, mesh, ocfg, seed=0)
        with mesh:
            _, _, m = step(params, opt, batch)
        losses[mesh.devices.size] = float(m["loss"])
    assert losses[1] == pytest.approx(losses[4], rel=1e-5)


def test_multipod_rules_train_step(mesh_pod):
    """Train step lowers + runs on a (pod, data, model) mesh."""
    spec = lm.build_spec(TINY)
    ocfg = OptConfig(lr=1e-3)
    step, *_ = make_train_step(spec, mesh_pod, ocfg, donate=False)
    params, opt = init_state(spec, mesh_pod, ocfg)
    with mesh_pod:
        _, _, m = step(params, opt, _batch(b=8))
    assert np.isfinite(float(m["loss"]))


def test_sanitize_spec_drops_nondivisible(mesh22):
    s = cm.sanitize_spec(P("model", "data"), (6, 4), mesh22)  # 6 % 2 == 0 ok
    assert tuple(s) == ("model", "data")
    s = cm.sanitize_spec(P("model", "data"), (5, 4), mesh22)  # 5 % 2 != 0
    assert tuple(s) == (None, "data")
    s = cm.sanitize_spec(P(("data", "model"), None), (6, 4), mesh22)  # 6 % 4
    assert tuple(s) == (None, None)


def test_constrain_safe_without_mesh():
    x = jnp.ones((4, 4))
    out = cm.constrain(x, ("batch", None), dict(cm.DEFAULT_RULES))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_compressed_pod_allreduce(mesh_pod):
    """int8 error-feedback sync: mean over pods within quantization error,
    residual carries the rounding for the next step."""
    grads = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(2, 64)).astype(np.float32))}
    ef = {"w": jnp.zeros((64,), jnp.float32)}

    def f(g, e):
        return compressed_pod_allreduce(g, e, axis="pod")

    g_sharded = {"w": grads["w"]}
    from repro.core.tiles import shard_map

    out, new_ef = jax.jit(
        shard_map(
            f,
            mesh=mesh_pod,
            in_specs=({"w": P("pod", None)}, {"w": P()}),
            out_specs=({"w": P("pod", None)}, {"w": P("pod", None)}),
            check=False,
        )
    )(g_sharded, ef)
    # each pod's synced grad == mean over pods (within int8 error)
    expect = grads["w"].reshape(2, 64).mean(axis=0)
    got = np.asarray(out["w"])
    for podrow in got.reshape(2, 64):
        np.testing.assert_allclose(podrow, expect, atol=0.05)
    # error feedback residual = local grad - dequantized local grad
    assert np.all(np.isfinite(np.asarray(new_ef["w"])))


def test_param_specs_cover_all_leaves():
    spec = lm.build_spec(TINY)
    pspecs = lm.param_specs(spec, cm.DEFAULT_RULES)
    pshape = jax.eval_shape(lambda k: lm.init_params(spec, k), jax.random.PRNGKey(0))
    sl, pl = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P)), jax.tree.leaves(pshape)
    assert len(sl) == len(pl)
    for s, p in zip(sl, pl):
        assert len(tuple(s)) <= p.ndim


def test_seqshard_rules_same_loss(mesh22):
    """The seq-sharded (ring-attention-style) preset computes the SAME loss
    as the baseline rules -- a pure re-sharding, not a math change."""
    from repro.launch.dryrun import seqshard_rules

    spec = lm.build_spec(TINY)
    params = lm.init_params(spec, jax.random.PRNGKey(3))
    batch = _batch(b=4, s=32)
    base = cm.attach_axis_sizes(dict(cm.DEFAULT_RULES), mesh22)
    seqs = cm.attach_axis_sizes(seqshard_rules(mesh22), mesh22)
    with mesh22:
        l0, _ = jax.jit(lambda p, b: lm.loss_fn(spec, p, b, rules=base))(params, batch)
        l1, _ = jax.jit(lambda p, b: lm.loss_fn(spec, p, b, rules=seqs))(params, batch)
    assert float(l0) == pytest.approx(float(l1), rel=1e-5)


def test_fsdp_rules_same_loss(mesh22):
    """The ZeRO-3 full-flat-batch preset is numerically identical too."""
    from repro.launch.dryrun import fsdp_rules

    spec = lm.build_spec(TINY)
    params = lm.init_params(spec, jax.random.PRNGKey(3))
    batch = _batch(b=4, s=32)
    base = cm.attach_axis_sizes(dict(cm.DEFAULT_RULES), mesh22)
    fs = cm.attach_axis_sizes(fsdp_rules(mesh22), mesh22)
    with mesh22:
        l0, _ = jax.jit(lambda p, b: lm.loss_fn(spec, p, b, rules=base))(params, batch)
        l1, _ = jax.jit(lambda p, b: lm.loss_fn(spec, p, b, rules=fs))(params, batch)
    assert float(l0) == pytest.approx(float(l1), rel=1e-5)

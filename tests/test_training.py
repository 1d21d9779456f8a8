"""Training substrate: optimizers, accumulation, checkpoint/restart,
failure injection, elastic re-mesh, straggler watchdog."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_mesh
from repro.launch.train import train_loop
from repro.models import lm
from repro.models.common import ArchConfig
from repro.training import (
    FailureInjector,
    InjectedFailure,
    OptConfig,
    StragglerWatchdog,
    latest_step,
    make_train_step,
)
from repro.training import checkpoint as ckpt
from repro.training.optim import (
    adafactor_init,
    adafactor_update,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    global_norm,
    lr_schedule,
)
from repro.training.train_step import init_state


TINY = ArchConfig(
    name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=256, remat=True,
)


def _mesh1():
    return make_mesh(1, 1)


def _batch(b=4, s=32, seed=0):
    key = jax.random.PRNGKey(seed)
    tok = jax.random.randint(key, (b, s), 0, 256)
    return {"tokens": tok, "labels": tok}


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------


def test_lr_schedule_shape():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in (0, 9, 10, 50, 99)]
    assert lrs[0] < lrs[1] <= lrs[2]  # warmup ascending
    assert lrs[2] >= lrs[3] >= lrs[4]  # cosine descending
    assert lrs[4] >= 0.1 * 0.99  # floor


def test_clip_by_global_norm():
    g = {"a": jnp.ones((4,)) * 10.0, "b": jnp.ones((3,)) * 10.0}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(700), rel=1e-5)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


@pytest.mark.slow
def test_adamw_and_adafactor_reduce_loss():
    mesh = _mesh1()
    spec = lm.build_spec(TINY)
    batch = _batch()
    for name in ("adamw", "adafactor"):
        ocfg = OptConfig(name=name, lr=1e-2, warmup_steps=1, total_steps=50)
        step, *_ = make_train_step(spec, mesh, ocfg)
        params, opt = init_state(spec, mesh, ocfg)
        with mesh:
            first = None
            for _ in range(8):
                params, opt, m = step(params, opt, batch)
                if first is None:
                    first = float(m["loss"])
        assert float(m["loss"]) < first, f"{name} failed to reduce loss"


def test_adafactor_memory_factored():
    """Adafactor second moments are O(rows + cols), not O(rows * cols)."""
    p = {"w": jnp.zeros((128, 64)), "b": jnp.zeros((64,))}
    st = adafactor_init(p)
    assert st["v"]["w"]["vr"].shape == (128,)
    assert st["v"]["w"]["vc"].shape == (64,)
    assert st["v"]["b"]["v"].shape == (64,)


@pytest.mark.slow
def test_grad_accumulation_matches_full_batch():
    mesh = _mesh1()
    cfg = TINY.replace(remat=False, compute_dtype="float32")
    spec = lm.build_spec(cfg)
    ocfg = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    step1, *_ = make_train_step(spec, mesh, ocfg, accum=1, donate=False)
    step4, *_ = make_train_step(spec, mesh, ocfg, accum=4, donate=False)
    params, opt = init_state(spec, mesh, ocfg)
    batch = _batch(b=8, s=16)
    with mesh:
        p1, _, m1 = step1(params, opt, batch)
        p4, _, m4 = step4(params, opt, batch)
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# checkpoint / restart / elastic re-mesh
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_restart_recovers_exactly(tmp_path):
    """Crash at step 4 (after the step-3 checkpoint), restart, finish.

    The RESTORE itself is bit-exact (params round-trip through the atomic
    checkpoint unchanged); the post-restore loss trajectory matches the
    straight-through run to fp32-noise tolerance (CPU threadpool reduction
    ordering is not deterministic under load)."""
    mesh = _mesh1()
    cfg = TINY.replace(compute_dtype="float32")
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")

    # run A: straight through, checkpointing every 3
    pa, _, straight = train_loop(cfg, mesh, steps=6, batch=4, seq=32,
                                 ckpt_dir=d1, ckpt_every=3, log_every=100)
    # run B: crash at step 4 (after ckpt at 3), then resume
    with pytest.raises(InjectedFailure):
        train_loop(cfg, mesh, steps=6, batch=4, seq=32,
                   ckpt_dir=d2, ckpt_every=3, fail_at=4, log_every=100)
    assert latest_step(d2) == 3

    # restore fidelity: the step-3 checkpoints of runs A and B are identical
    import jax as _jax
    from repro.models import lm as _lm
    spec = _lm.build_spec(cfg)
    pshape = _jax.eval_shape(lambda k: _lm.init_params(spec, k), _jax.random.PRNGKey(0))
    from repro.training.optim import make_optimizer
    oshape = _jax.eval_shape(make_optimizer(OptConfig())[0], pshape)
    tpl = {"params": pshape, "opt": oshape}
    sa, _, _ = ckpt.restore(d1, 3, tpl)
    sb, _, _ = ckpt.restore(d2, 3, tpl)
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        # cross-run states agree to fp32 thread-order noise (strict bit
        # round-trip of a single checkpoint is test_checkpoint_atomicity)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=1e-6, atol=1e-7
        )

    _, _, resumed = train_loop(cfg, mesh, steps=6, batch=4, seq=32,
                               ckpt_dir=d2, ckpt_every=3, log_every=100)
    assert len(resumed) == 3
    np.testing.assert_allclose(straight[3:], resumed, rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_elastic_remesh_restore(tmp_path):
    """Checkpoint on a 2x2 mesh, restore onto 1x1 -- loss trajectory equal."""
    cfg = TINY.replace(compute_dtype="float32")
    d = str(tmp_path / "remesh")
    mesh_a = make_mesh(2, 2)
    _, _, la = train_loop(cfg, mesh_a, steps=4, batch=4, seq=32,
                          ckpt_dir=d, ckpt_every=2, log_every=100)
    # resume the remaining steps on a different mesh
    mesh_b = _mesh1()
    _, _, lb = train_loop(cfg, mesh_b, steps=6, batch=4, seq=32,
                          ckpt_dir=d, ckpt_every=100, log_every=100)
    # lb covers steps 4..5 continuing from the step-4 checkpoint of mesh_a
    assert len(lb) == 2 and all(np.isfinite(lb))


def test_checkpoint_atomicity(tmp_path):
    d = str(tmp_path)
    tree = {"x": jnp.arange(10), "y": {"z": jnp.ones((3, 3))}}
    ckpt.save(d, 1, tree)
    # a stale .tmp from a crashed writer must be invisible
    os.makedirs(os.path.join(d, "step_00000002.tmp"), exist_ok=True)
    assert latest_step(d) == 1
    tpl = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    back, _, step = ckpt.restore(d, 1, tpl)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(back["x"]), np.arange(10))


def test_async_checkpointer_surfaces_errors(tmp_path):
    blocker = tmp_path / "file"
    blocker.write_text("not a dir")
    ac = ckpt.AsyncCheckpointer()
    # parent is a FILE -> makedirs inside the worker thread must fail and the
    # error must surface at the next wait()
    ac.save(str(blocker / "x"), 1, {"a": jnp.zeros(1)})
    with pytest.raises(BaseException):
        ac.wait()


# ---------------------------------------------------------------------------
# watchdog / failure injection
# ---------------------------------------------------------------------------


def test_straggler_watchdog_flags_slow_steps():
    dog = StragglerWatchdog(factor=2.0, warmup_steps=2)
    for i in range(5):
        assert not dog.observe(i, 0.1)
    assert dog.observe(5, 0.5)  # 5x EMA
    assert dog.flags and dog.flags[0][0] == 5
    assert not dog.observe(6, 0.1)  # EMA not poisoned by the outlier


def test_failure_injector_fires_once():
    inj = FailureInjector(fail_at_step=3)
    inj.check(2)
    with pytest.raises(InjectedFailure):
        inj.check(3)
    inj.check(3)  # second pass (post-restart) does not re-fire

"""End-to-end fault-tolerant training driver.

Features exercised end-to-end (and tested in tests/test_training.py):
  - jit'd train step with FSDP/TP shardings from the arch's logical axes
  - microbatch gradient accumulation
  - deterministic counter-RNG data pipeline (restart-exact)
  - atomic async checkpointing + restore-on-start (restart loop)
  - failure injection (--fail-at N) to demonstrate recovery
  - straggler watchdog (step-time EMA)
  - elastic re-mesh: restore a checkpoint onto a different mesh shape

Usage (CPU container -- tiny smoke config):
  python -m repro.launch.train --arch granite-3-2b --smoke --steps 20 \
      --ckpt-dir /tmp/ckpt --ckpt-every 5 [--fail-at 12]
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro import configs
from repro.data import DataConfig, host_batch
from repro.launch.mesh import make_mesh
from repro.models import common as cm
from repro.models import lm
from repro.training import (
    AsyncCheckpointer,
    FailureInjector,
    InjectedFailure,
    OptConfig,
    StepTimer,
    StragglerWatchdog,
    latest_step,
    make_train_step,
    restore,
)
from repro.training.optim import make_optimizer
from repro.training.train_step import _named, init_state


def train_loop(
    cfg,
    mesh,
    *,
    steps: int,
    batch: int,
    seq: int,
    accum: int = 1,
    ckpt_dir: str | None = None,
    ckpt_every: int = 0,
    fail_at: int | None = None,
    seed: int = 0,
    log_every: int = 1,
):
    """Returns (params, opt_state, losses).  Restarts from ckpt if present."""
    spec = lm.build_spec(cfg)
    opt_cfg = OptConfig(name=cfg.optimizer, lr=1e-3, warmup_steps=5, total_steps=steps)
    step_fn, pspecs, ospecs, bspec = make_train_step(spec, mesh, opt_cfg, accum=accum)
    dcfg = DataConfig(
        vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed,
        frames_dim=cfg.d_model if cfg.input_mode == "frames" else 0,
    )

    start = 0
    if ckpt_dir and (last := latest_step(ckpt_dir)) is not None:
        pshape = jax.eval_shape(partial(lm.init_params, spec), jax.random.PRNGKey(seed))
        opt_init, _ = make_optimizer(opt_cfg)
        oshape = jax.eval_shape(opt_init, pshape)
        tpl = {"params": pshape, "opt": oshape}
        shardings = {
            "params": _named(mesh, pspecs),
            "opt": _named(mesh, ospecs),
        }
        state, extra, start = restore(ckpt_dir, last, tpl, shardings=shardings)
        params, opt_state = state["params"], state["opt"]
        print(f"[train] restored step {start} from {ckpt_dir}")
    else:
        params, opt_state = init_state(spec, mesh, opt_cfg, seed=seed)

    ckpt = AsyncCheckpointer()
    dog = StragglerWatchdog()
    inj = FailureInjector(fail_at_step=fail_at)
    losses = []

    with mesh:
        for step in range(start, steps):
            inj.check(step)
            b = host_batch(dcfg, step)
            b = {k: jnp.asarray(v) for k, v in b.items()}
            with StepTimer() as t:
                params, opt_state, metrics = step_fn(params, opt_state, b)
                loss = float(metrics["loss"])  # blocks
            losses.append(loss)
            if dog.observe(step, t.dt):
                print(f"[watchdog] straggling step {step}: {t.dt:.3f}s vs EMA {dog.ema:.3f}s")
            if step % log_every == 0:
                print(f"[train] step {step} loss {loss:.4f} ({t.dt*1e3:.0f} ms)")
            if ckpt_dir and ckpt_every and (step + 1) % ckpt_every == 0:
                ckpt.save(ckpt_dir, step + 1, {"params": params, "opt": opt_state},
                          extra={"loss": loss})
    ckpt.wait()
    return params, opt_state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--data", type=int, default=1, help="mesh data-axis size")
    ap.add_argument("--model", type=int, default=1, help="mesh model-axis size")
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    mesh = make_mesh(data=args.data, model=args.model)

    try:
        _, _, losses = train_loop(
            cfg, mesh, steps=args.steps, batch=args.batch, seq=args.seq,
            accum=args.accum, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            fail_at=args.fail_at,
        )
        print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    except InjectedFailure as e:
        print(f"[train] {e}; restart the same command to resume from checkpoint")
        raise SystemExit(42)


if __name__ == "__main__":
    main()

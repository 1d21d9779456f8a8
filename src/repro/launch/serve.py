"""Batched serving driver: prefill a prompt batch, decode N tokens.

Usage (CPU container -- tiny smoke config):
  python -m repro.launch.serve --arch qwen2-1.5b --smoke --batch 4 \
      --prompt-len 32 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.serving import ServeConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    mesh = make_mesh(data=args.data, model=args.model)
    spec = lm.build_spec(cfg)
    params = lm.init_params(spec, jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, size=(args.batch, args.prompt_len)).astype(np.int32)
    frames = None
    if cfg.input_mode == "frames":
        frames = rng.normal(size=(args.batch, args.prompt_len, cfg.d_model)).astype(np.float32)

    eng = ServeEngine(
        spec, mesh, params,
        s_max=args.prompt_len + args.max_new,
        batch=args.batch,
        cfg=ServeConfig(max_new_tokens=args.max_new, temperature=args.temperature),
    )
    t0 = time.perf_counter()
    out = eng.generate(prompts, frames=frames)
    dt = time.perf_counter() - t0
    tput = args.batch * args.max_new / dt
    print(f"[serve] generated {out.shape} in {dt:.2f}s ({tput:.1f} tok/s)")
    print("[serve] first sequence:", out[0].tolist())


if __name__ == "__main__":
    main()

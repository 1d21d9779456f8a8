"""End-to-end LM training driver demo: ~100M-param model, few hundred steps.

Uses the framework's full path -- deterministic data pipeline, jit'd
FSDP/TP train step, checkpointing, watchdog -- on a CPU-sized model.  With
--steps 300 on this container it demonstrably learns the synthetic data's
deterministic next-token structure (loss drops well below ln(vocab)).

    PYTHONPATH=src python examples/train_lm.py --steps 60
"""

import argparse

from repro.launch.mesh import make_mesh
from repro.launch.train import train_loop
from repro.models.common import ArchConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    # ~100M params: a granite-family dense decoder
    cfg = ArchConfig(
        name="demo-100m", family="dense", n_layers=8, d_model=512, n_heads=8,
        n_kv_heads=4, d_ff=2048, vocab=8192, tie_embeddings=True, remat=False,
    )
    mesh = make_mesh(1, 1)
    _, _, losses = train_loop(
        cfg, mesh, steps=args.steps, batch=args.batch, seq=args.seq,
        ckpt_dir=args.ckpt_dir, ckpt_every=max(args.steps // 4, 1), log_every=10,
    )
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f} over {args.steps} steps")


if __name__ == "__main__":
    main()

"""Benchmark harness: one benchmark per paper table/figure.

  bench_accuracy   -- Fig 2a/2b: relative error vs eps_RP, d, q
  bench_scaling    -- Fig 3a/3b: runtime vs n, runtime vs workers (derived)
  bench_blocksize  -- Fig 3c: runtime vs block (tile) size
  bench_matmul     -- section 3.2 / Fig 1: shuffle-free vs naive collective bytes
  bench_sequence   -- sequence engine: chain-operator reuse vs pairwise rebuilds
  roofline         -- per (arch x shape x mesh) roofline terms from the dry-run

Prints ``name,metric,value`` CSV lines.  ``python -m benchmarks.run [--fast]``
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller sizes")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    args = ap.parse_args()

    from benchmarks import (
        bench_accuracy,
        bench_blocksize,
        bench_matmul,
        bench_scaling,
        bench_sequence,
        roofline,
    )

    benches = {
        "accuracy": lambda: bench_accuracy.run(n=256 if args.fast else 512),
        "scaling": lambda: bench_scaling.run(sizes=(96, 128, 192) if args.fast else (128, 256, 512)),
        "blocksize": lambda: bench_blocksize.run(n=256 if args.fast else 512),
        "matmul": lambda: bench_matmul.run(n=512 if args.fast else 1024),
        "sequence": lambda: bench_sequence.run(n=128 if args.fast else 256, t_steps=4),
        "roofline": lambda: roofline.run(),
    }
    chosen = args.only.split(",") if args.only else list(benches)
    t0 = time.time()
    failed = []
    for name in chosen:
        print(f"# === {name} ===", flush=True)
        try:
            benches[name]()
        except Exception as e:  # run the rest, then fail the whole harness
            traceback.print_exc()
            print(f"{name},error,{type(e).__name__}")
            failed.append(name)
    print(f"# total {time.time()-t0:.0f}s")
    if failed:
        raise SystemExit(f"failed benchmarks: {', '.join(failed)}")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json and print its result as the last line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (process start to the window's start) is ``setup_s``.  With
``--trace 0`` the result's metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a traced window.  Each number
the correctness check compared is printed beside its limit as the last lines
on standard error and under ``checks``, the last key of the result.  Exits
non-zero, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# A TPU that fails to initialise must be an error, never a CPU run.
os.environ.setdefault("JAX_PLATFORMS", "tpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import NoChip, run

    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

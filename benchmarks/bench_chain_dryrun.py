"""Paper-technique roofline: the chain product at production scale.

Lowers ChainProduct (Algorithm 2, d levels of distributed n x n GEMMs) on
the 16x16 production mesh for each matmul schedule and reports the
trip-count-corrected per-device FLOPs + collective bytes:

  xla    -- XLA SPMD default (all-gather panels): the Spark BlockMatrix
            "shuffle" analogue == the paper's BASELINE
  summa  -- explicit panels (paper-faithful write-once/read-many: every
            block read exactly where needed, no replication through an
            opaque shuffle)
  cannon -- systolic nearest-neighbor rings (BEYOND-paper: O(n^2/P)
            residency, permute traffic only, overlappable with the GEMM)

This is the experiment behind EXPERIMENTS.md section Perf (CADDeLaG cell).
Run inside the dry-run env (512 host devices):

  XLA_FLAGS=--xla_force_host_platform_device_count=512 \
      PYTHONPATH=src python -m benchmarks.bench_chain_dryrun [--n 65536]
"""

from __future__ import annotations

import argparse
import json
import os


def run(n: int = 65536, d_len: int = 6, out=print):
    import subprocess
    import sys

    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding
from repro.core import make_context, chain_product
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import make_production_mesh

mesh = make_production_mesh()
ctx = make_context(mesh)
res = {{}}
for sched in ("xla", "summa", "cannon"):
    fn = jax.jit(lambda a: chain_product(ctx, a, {d_len}, schedule=sched, fuse_l=True))
    sds = jax.ShapeDtypeStruct(({n}, {n}), jnp.float32,
                               sharding=NamedSharding(mesh, ctx.matrix_spec))
    c = fn.lower(sds).compile()
    a = ha.analyze(c.as_text())
    mem = c.memory_analysis()
    res[sched] = {{
        "dot_flops": a["dot_flops"],
        "coll_bytes": a["collective_total_bytes"],
        "by_type": {{k: v for k, v in a["collective_bytes"].items() if v}},
        "temp_gb": getattr(mem, "temp_size_in_bytes", 0) / 1e9,
    }}
print(json.dumps(res))
"""
    # The child rehearses on virtual host devices: pinned to the CPU, so it
    # never contends for an accelerator the parent process may hold.
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src"},
        timeout=3000,
    )
    if proc.returncode != 0:
        out(f"bench_chain_dryrun,error,{proc.stderr[-300:]}")
        return None
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    peak, ici = 197e12, 50e9
    for sched, r in res.items():
        t_comp = r["dot_flops"] / peak
        t_coll = r["coll_bytes"] / ici
        out(
            f"bench_chain_dryrun,n={n},d={d_len},sched={sched},"
            f"t_comp_ms={t_comp*1e3:.0f},t_coll_ms={t_coll*1e3:.0f},"
            f"temp_gb={r['temp_gb']:.1f},types={r['by_type']}"
        )
    os.makedirs("experiments/perf", exist_ok=True)
    with open(f"experiments/perf/chain_schedules_n{n}.json", "w") as f:
        json.dump(res, f, indent=1)
    return res


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--d", type=int, default=6)
    args = ap.parse_args()
    run(n=args.n, d_len=args.d)

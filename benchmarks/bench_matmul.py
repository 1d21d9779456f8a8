"""Paper section 3.2 / Fig. 1: shuffle-free block matmul vs the naive path.

The paper's contribution: Spark's BlockMatrix.multiply replicates blocks
through the shuffle (O(n^3/p) shuffle bytes); their write-once/read-many
scheme moves O(n^2).  TPU mapping measured here, per schedule, by compiling
C = A @ B on a fake 16-device mesh and counting *collective bytes* in the
post-SPMD HLO (the ICI traffic that the roofline's collective term prices):

  xla    -- XLA SPMD default: all-gathers a full operand panel (the moral
            equivalent of the shuffle replication)
  summa  -- explicit row/column panels under shard_map
  cannon -- systolic nearest-neighbor ring: O(n^2/P) resident, only
            collective-permute traffic, overlappable with the local GEMM

Also measures wall-time on a real 4-device CPU mesh for the same shapes.
"""

from __future__ import annotations

import time

import numpy as np


def run(n: int = 1024, out=print):
    # collective-bytes comparison needs many fake devices -> subprocess
    import json
    import os
    import subprocess
    import sys

    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import Mesh
from repro.core import make_context, matmul
from repro.launch import hlo_analysis as ha

mesh = jax.make_mesh((4, 4), ("data", "model"))
ctx = make_context(mesh)
res = {{}}
for sched in ("xla", "summa", "cannon"):
    f = jax.jit(lambda a, b: matmul(ctx, a, b, schedule=sched))
    sds = jax.ShapeDtypeStruct(({n}, {n}), jnp.float32,
                               sharding=jax.sharding.NamedSharding(mesh, ctx.matrix_spec))
    c = f.lower(sds, sds).compile()
    a = ha.analyze(c.as_text())
    res[sched] = {{"coll_bytes": a["collective_total_bytes"],
                   "by_type": {{k: v for k, v in a["collective_bytes"].items() if v}},
                   "dot_flops": a["dot_flops"]}}
print(json.dumps(res))
"""
    # The child rehearses on virtual host devices: pinned to the CPU, so it
    # never contends for an accelerator the parent process may hold.
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src"},
    )
    if proc.returncode == 0:
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        base = res["xla"]["coll_bytes"]
        for sched, r in res.items():
            ratio = base / max(r["coll_bytes"], 1)
            out(
                f"bench_matmul,sched={sched},coll_bytes={r['coll_bytes']:.3e},"
                f"vs_xla={ratio:.2f}x,types={r['by_type']}"
            )
    else:
        out(f"bench_matmul,subprocess_error,{proc.stderr[-200:]}")

    # wall-time on the real 4-device mesh
    import jax
    import jax.numpy as jnp

    if len(jax.devices()) >= 4:
        from jax.sharding import Mesh

        from repro.core import make_context, matmul

        devs = np.array(jax.devices()[:4]).reshape(2, 2)
        ctx = make_context(Mesh(devs, ("data", "model")))
        rng = np.random.default_rng(0)
        a = ctx.put_matrix(rng.normal(size=(n, n)).astype(np.float32))
        b = ctx.put_matrix(rng.normal(size=(n, n)).astype(np.float32))
        for sched in ("xla", "summa", "cannon"):
            f = jax.jit(lambda x, y, s=sched: matmul(ctx, x, y, schedule=s))
            f(a, b).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(3):
                f(a, b).block_until_ready()
            dt = (time.perf_counter() - t0) / 3
            out(f"bench_matmul,sched={sched},n={n},us_per_call={dt*1e6:.0f}")


if __name__ == "__main__":
    run()

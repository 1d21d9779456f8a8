"""Pallas TPU kernels for the compute hot-spots (interpret mode on CPU).

- ``block_matmul``    -- the paper's per-block GEMM on the MXU (fp32 accum)
- ``edge_projection`` -- fused sqrt(A).Q row-reduce with in-kernel counter RNG
- ``cad_scores``      -- fused commute-distance + |dA| gate + row reduction
- ``flash_attention`` -- online-softmax attention for the LM substrate

Each has a jit'd wrapper in :mod:`repro.kernels.ops` and a pure-jnp oracle in
:mod:`repro.kernels.ref`.
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]

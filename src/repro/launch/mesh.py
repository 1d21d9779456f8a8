"""Production mesh factories (functions, never module-level constants --
importing this module must not touch jax device state).

Single pod:  (16, 16)    = 256 v5e chips, axes ("data", "model")
Multi pod:   (2, 16, 16) = 512 chips,     axes ("pod", "data", "model")

``"data"`` carries the batch (FSDP weight shard inside a pod), ``"model"``
carries tensor-parallel / expert / flash-decode-sequence shards, ``"pod"``
is pure data parallelism across pods (slowest links -> fewest collectives:
one gradient all-reduce per step, optionally int8-compressed).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(data: int = 1, model: int = 1, pod: int = 0) -> Mesh:
    """A ``data`` x ``model`` (x ``pod``) mesh over the first devices.

    ``jax.make_mesh`` orders the devices by the physical topology (a 2x2 v5e
    host gets ring neighbours on each axis); the axes are ``Auto``, as every
    program here shards through ``NamedSharding`` and ``shard_map``.
    """
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    n = int(np.prod(shape))
    return jax.make_mesh(
        shape, axes, devices=jax.devices()[:n],
        axis_types=(AxisType.Auto,) * len(axes),
    )


def mesh_chip_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))

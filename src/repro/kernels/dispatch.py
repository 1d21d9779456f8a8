"""One call site for every Pallas kernel: compiled on TPU, interpreted on CPU.

The choice follows the device the surrounding program runs on -- not
``jax.default_backend()`` -- so one process can run a program on its TPU and
the same program on its CPU device (``chip_smoke.py`` compares the two):

* inside a ``shard_map`` the mesh names its device kind while the kernel is
  traced: a TPU gets the compiled Mosaic kernel, the CPU gets Pallas's TPU
  interpret mode (the one interpreter that is legal under the map's
  varying-type checking);
* outside one, the choice is made as the program is lowered for its
  platform (``lax.platform_dependent``): compiled on TPU, the HLO interpreter
  on CPU.

Both interpreters run the kernel body itself.  Any other device raises.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret_for(device_kind: str):
    """``interpret=`` for a kernel traced inside a map over ``device_kind``."""
    if device_kind == "cpu":
        return pltpu.InterpretParams()
    if device_kind.startswith("TPU"):
        return False
    raise ValueError(f"no Pallas kernel path for device kind {device_kind!r}")


def pallas_call(kernel, *operands, out_shape, **kwargs):
    """``pl.pallas_call(kernel, out_shape=out_shape, **kwargs)(*operands)``.

    The outputs are typed as varying over every mesh axis any operand varies
    over, so the call is legal inside ``jax.shard_map`` with its default
    varying-type checking (outside one, the set is empty).
    """
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    out_shape = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma), out_shape
    )

    def run(interpret):
        return pl.pallas_call(
            kernel, out_shape=out_shape, interpret=interpret, **kwargs
        )

    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return run(_interpret_for(mesh.abstract_device.device_kind))(*operands)
    return lax.platform_dependent(*operands, cpu=run(True), tpu=run(False))

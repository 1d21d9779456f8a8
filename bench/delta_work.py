"""The bytes one delta update of the chain must read, from its shapes.

The incremental update of ``repro.core.delta_chain`` (Khoa & Chawla's
incremental commute time, arXiv:1107.3894, on the squaring chain) multiplies
skinny blocks against n x n float32 operands.  The published recurrence

    dT_l = [T U, U] [V, T V + V (U^T V)]^T
    dP_l = [E, P_{l-1} Ut + E (F^T Ut)] [F + T_l F, Vt]^T

with the sketch of ``dS`` from the new adjacency and the corrected
``P1' = diag(s) P1 diag(s) + E~ F~^T`` reads these distinct operands:

* ``A'``, the new snapshot's adjacency (the sketch of ``S~'``);
* ``T_0 .. T_{d-1}``, the base's squaring levels (``d``);
* ``P_1 .. P_{d-2}``, the base's partial products (``d - 2``);
* ``P1``, the base's preconditioner, which the corrected operator wraps.

Each must come from HBM at least once, so their bytes over the HBM peak
bound one update's time from below, whatever implements it: an
implementation that applies each ``P_l`` as a product of ``(I + T_j)``
factors, or reads an operand more than once, only reads more.
"""

from __future__ import annotations


def update_operands(d: int) -> int:
    """Distinct n x n operands of one update: ``A'``, ``d`` T levels,
    ``d - 2`` P levels and ``P1``."""
    return 1 + d + max(d - 2, 0) + 1


def update_floor_bytes(n: int, d: int, itemsize: int = 4) -> float:
    """Bytes one delta update must read: each distinct operand once."""
    return float(update_operands(d)) * n * n * itemsize

"""Peaks of the chips, and the work a cell's calls need, from their shapes.

The peaks are published numbers (Google Cloud documentation, "TPU v5e"),
keyed by the ``device_kind`` JAX reports.  A device that is not in the table
is an error: a roofline share against a guessed peak is no measurement.

The work counts are of the algorithm, not of what implements it: a float32
product at ``HIGHEST`` makes six bfloat16 passes on the MXU, and is counted
once.  So a share of the bfloat16 peak reads the same whatever runs the GEMM,
and can reach 100% only where one pass does.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them to "
            "bench/roofline.py with their source"
        ) from None


def chain_gemms(d: int) -> int:
    """Dense n x n GEMMs of one full chain build: ``T <- T T`` and
    ``P <- P T + P`` per level past the first, and ``P2 = P1 L``."""
    return 2 * (d - 1) + 1


def transition_matmul_flops(n: int, k: int, d: int, q: int) -> float:
    """Useful matrix-product FLOPs of one full-rebuild transition: the chain's
    GEMMs (``2 n^3`` each), the solve's ``q`` products with an (n, k) block
    (``chi = P1 Y`` and ``q - 1`` Richardson steps, ``2 n^2 k`` each) and the
    two distance expansions of the scoring (``2 n^2 k`` each)."""
    n = float(n)
    return chain_gemms(d) * 2.0 * n**3 + (q + 2) * 2.0 * n * n * k


def query_bytes(n: int, k: int, itemsize: int) -> float:
    """Bytes one whole-artifact query must read: every row of Z once."""
    return float(n) * k * itemsize

"""Streamed-solve roofline model (importable home; benches re-export).

The out-of-core solve is bound by whichever of scratch-disk read, host->device
staging, or MXU FLOPs saturates first.  All three terms come from measured
traffic (the ``stream.*`` byte counters) plus the iteration count, so run
reports and benchmarks can state measured-vs-bound directly.

The compute term needs the device's peak, and only a published one counts:
:data:`PEAKS` is keyed by ``jax.Device.device_kind``, and a device that is
not in it gets no roofline fraction ("not measured") rather than a borrowed
peak.  The disk and H2D bandwidths are assumed tiers, not device peaks.
"""

from __future__ import annotations

# Published peaks of one chip, by device_kind.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM bandwidth.
PEAKS = {"TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9}}
NOT_MEASURED = "not measured"
DISK_BW = 2.0e9  # bytes/s sustained scratch-store read (NVMe-class, assumed)
H2D_BW = 32e9  # bytes/s host->device staging (PCIe gen4 x16-class, assumed)

__all__ = [
    "PEAKS",
    "NOT_MEASURED",
    "DISK_BW",
    "H2D_BW",
    "streamed_solve_flops",
    "streamed_solve_roofline",
]


def streamed_solve_flops(n: int, k: int, iterations: int) -> float:
    """Dense FLOPs of a streamed solve: one (n x n) @ (n x k) mat-vec per
    iteration plus the chi build (P1 @ b), 2nk per MAC row."""
    return 2.0 * n * n * k * (iterations + 1)


def streamed_solve_roofline(
    *,
    bytes_read: float,
    bytes_h2d: float,
    flops: float,
    seconds: float,
    device_kind: str,
    peaks: dict | None = None,
    disk_bw: float = DISK_BW,
    h2d_bw: float = H2D_BW,
) -> dict:
    """Three-term bound for a streamed solve, from measured traffic.

    ``bound_s = max(read/disk_bw, h2d/h2d_bw, flops/peak)`` is the fastest
    the solve could have gone on the modeled hardware; ``roofline_frac =
    bound_s / seconds`` is the fraction of that bound actually achieved.
    ``peaks`` defaults to the published entry for ``device_kind``; a device
    without one gets ``roofline_frac == NOT_MEASURED`` and no bound.
    """
    peaks = PEAKS.get(device_kind) if peaks is None else peaks
    t_disk = bytes_read / disk_bw
    t_h2d = bytes_h2d / h2d_bw
    if peaks is None:
        return {
            "device_kind": device_kind,
            "t_disk_s": t_disk,
            "t_h2d_s": t_h2d,
            "measured_s": seconds,
            "roofline_frac": NOT_MEASURED,
        }
    t_flop = flops / peaks["flops"]
    bound_s, bound = max(
        (t_disk, "disk"), (t_h2d, "h2d"), (t_flop, "compute")
    )
    return {
        "device_kind": device_kind,
        "t_disk_s": t_disk,
        "t_h2d_s": t_h2d,
        "t_compute_s": t_flop,
        "bound": bound,
        "bound_s": bound_s,
        "measured_s": seconds,
        "roofline_frac": bound_s / seconds if seconds > 0 else 0.0,
    }

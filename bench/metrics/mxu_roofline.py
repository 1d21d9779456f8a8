"""Share of the bf16 peak that the window's useful matrix products reach
over chip 0's time in matrix-product ops.

Useful FLOPs come from shapes (``bench.roofline.transition_matmul_flops``,
per chip); the time is the summed device duration of the ops that
``bench.trace_reduce.is_matmul`` names.  A float32 product at ``HIGHEST``
makes six passes, so this reads at most about a sixth of the peak there.
"""

from bench import trace_reduce


def read(rec):
    if rec.peaks is None or not rec.count:
        return None
    lo, hi = rec.trace_window
    ns = trace_reduce.op_time_ns(rec.device_ops(0), trace_reduce.is_matmul, lo, hi)
    if ns <= 0:
        return None
    flops = rec.work["matmul_flops"] / rec.chips
    return 100.0 * flops / rec.peaks["bf16_flops_per_s"] / (ns / 1e9)

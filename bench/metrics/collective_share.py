"""Share of the traced window in which chip 0 runs collective ops and no
other op: the exchange between chips that compute does not hide."""

from bench import trace_reduce


def read(rec):
    ops = rec.device_ops(0)
    if not any(trace_reduce.is_collective(e) for e in ops):
        return None
    lo, hi = rec.trace_window
    return 100.0 * trace_reduce.exposed_collective_ns(ops, lo, hi) / (hi - lo)

"""Share of the traced window in which no op runs on chip 0.

Reads ``device_idle_share.write`` and ``device_idle_share.read``: one
quantity, named by the end-to-end metric it moves in each kind of cell."""

from bench import trace_reduce


def read(rec):
    ops = rec.device_ops(0)
    if not ops:
        return None
    lo, hi = rec.trace_window
    return 100.0 * trace_reduce.idle_share(ops, lo, hi)

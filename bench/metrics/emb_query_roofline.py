"""Share of the HBM roofline that the distance/top-k kernel reaches: the
bytes of Z one query must read over the HBM peak, against the kernel's
summed device time per query on chip 0."""

from bench import trace_reduce


def read(rec):
    if rec.peaks is None or not rec.queries:
        return None
    lo, hi = rec.trace_window
    ns = trace_reduce.op_time_ns(rec.device_ops(0), trace_reduce.is_emb_query, lo, hi)
    if ns <= 0:
        return None
    least_s = rec.work["query_bytes"] / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9 / len(rec.queries))

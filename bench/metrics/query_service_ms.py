"""Median milliseconds from the start of a query's service to its answer:
the read path's own time, with the wait in the queue left out."""

import statistics


def read(rec):
    if not rec.queries:
        return None
    return 1e3 * statistics.median(end - start for _, start, end in rec.queries)

"""The comparison that decides ``correct``, and the arithmetic of the metrics.

Every number compared is printed beside its limit.  The limits are the
configuration's (``limits`` in ``bench/configs/<config>.json``), set from
readings of sound runs and of the control on the chip (see ``PERF.md``).
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def score_gap(got, ref) -> float:
    """``max |got - ref|`` as a share of ``max |ref|``."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-300))


def rank_gap(ids, ref, k: int, *, largest: bool = True) -> float:
    """How far ``ids`` are from a top-``k`` of ``ref``: the reference's own
    values at ``ids``, sorted, against its ``k`` best, as a share of the
    largest of those.  Near-ties cost only their difference; a missing,
    repeated or out-of-range id costs ``inf``."""
    ids = np.asarray(ids).reshape(-1)
    ref = np.asarray(ref, np.float64)
    n = ref.shape[0]
    if ids.size != k or len(set(ids.tolist())) != k or np.any((ids < 0) | (ids >= n)):
        return math.inf
    sign = 1.0 if largest else -1.0
    want = -np.sort(-sign * ref)[:k]
    got = -np.sort(-sign * ref[ids])
    if not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-300))


def answer_gap(scores, top_ids, ref, k: int) -> tuple[float, dict]:
    """A write transition's one compared number, with its two parts: the
    larger of the scores' gap and the top-``k``'s rank gap.  The control
    moves the first; a wrong ranking moves the second."""
    parts = {"score_gap": score_gap(scores, ref), "rank_gap": rank_gap(top_ids, ref, k)}
    return max(parts.values()), parts


def query_gap(ids, vals, ref, k: int, *, largest: bool) -> tuple[float, dict]:
    """A query answer's one compared number, with its two parts: the larger
    of the gap between the answer's scores and the reference's at the
    answered ids, and the ids' rank gap."""
    ids = np.asarray(ids).reshape(-1)
    n = np.asarray(ref).shape[0]
    in_range = bool(np.all((ids >= 0) & (ids < n)))
    parts = {
        "score_gap": score_gap(vals, np.asarray(ref)[ids]) if in_range else math.inf,
        "rank_gap": rank_gap(ids, ref, k, largest=largest),
    }
    return max(parts.values()), parts


def judge(numbers: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict]:
    """``correct`` and the ``{name: {"value", "limit"}}`` of every number."""
    checks = {
        name: {"value": value, "limit": float(limits[name])} for name, value in numbers.items()
    }
    ok = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values()
    )
    return ok, checks


# ---------------------------------------------------------------------------
# end-to-end arithmetic
# ---------------------------------------------------------------------------


def per_item(window_s: float, count: int) -> float:
    """Seconds per completed item: all the window's time over all its work."""
    if count < 1:
        raise ValueError("no item completed in the window")
    return window_s / count


def rate(count: int, seconds: float) -> float:
    """Items completed per second of a window: all its work over all its
    time."""
    if count < 1 or seconds <= 0:
        raise ValueError("no item completed in the window")
    return count / seconds


def latencies_ms(due: list[float], done: list[float]) -> list[float]:
    """Each answered query's latency, from when it was due to its answer."""
    return [(d1 - d0) * 1e3 for d0, d1 in zip(due, done)]


def percentile(values, pct: float) -> float:
    """The ``pct`` percentile by linear interpolation between order
    statistics (numpy's default)."""
    if not len(values):
        raise ValueError("no values")
    return float(np.percentile(np.asarray(values, np.float64), pct))


def spread(values) -> float:
    """Interquartile distance as a share of the median (Python's
    ``statistics.quantiles``, exclusive method)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med

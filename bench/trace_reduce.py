"""From a profiler trace to device busy time, op time and idle attribution.

The JAX profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData``.  A TPU shows one plane per chip
(``/device:TPU:<i>``) whose ``XLA Ops`` line holds one event per device
operation; the host planes hold the harness's ``TraceAnnotation`` spans, on
the same clock.  The arithmetic:

* busy is the union of a device's op intervals within the window, and the
  idle share is ``1 - busy / window``; a loop's own event, which spans its
  body's, is left out;
* an op's time is the sum of its events' durations;
* collective time not overlapped is the part of the union of collective ops
  during which no other op runs;
* an idle gap is attributed to the innermost harness span open during it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all|send|recv"
)


@dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    end: int  # ns

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclass
class Trace:
    devices: dict[int, list[Event]]  # chip id -> its device ops, by start
    spans: list[Event]  # harness spans (host)

    def window(self, name: str = SPAN_PREFIX + "window") -> tuple[int, int]:
        """The [start, end) of the harness span that marks the traced window."""
        for sp in self.spans:
            if sp.name == name:
                return sp.start, sp.end
        raise KeyError(f"no {name!r} span in the trace")


def load(path: str, plane: re.Pattern = DEVICE_PLANE, line: str = OP_LINE) -> Trace:
    """Device ops and harness spans of one ``.xplane.pb`` file.

    ``plane`` matches the planes that hold device ops (its first group, when
    it has one, is the chip id) and ``line`` names their op line; the CPU
    backend, which runs its ops on host threads, is read by passing the host
    plane and its client thread's line.
    """
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: dict[int, list[Event]] = defaultdict(list)
    spans: list[Event] = []
    for pl in data.planes:
        m = plane.match(pl.name)
        for ln in pl.lines:
            if m and re.fullmatch(line, ln.name):
                chip = int(m.group(1)) if m.groups() else 0
                devices[chip] += [
                    Event(e.name, int(e.start_ns), int(e.end_ns))
                    for e in ln.events if opcode(e.name) not in CONTAINER_OPS
                ]
            if pl.name.startswith("/host:"):
                spans += [
                    Event(e.name, int(e.start_ns), int(e.end_ns))
                    for e in ln.events if e.name.startswith(SPAN_PREFIX)
                ]
    return Trace(
        devices={c: sorted(ops, key=lambda e: e.start) for c, ops in devices.items()},
        spans=sorted(spans, key=lambda e: e.start),
    )


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, found {paths}")
    return paths[0]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals, lo: int | None = None, hi: int | None = None) -> list[tuple[int, int]]:
    """Merged, sorted intervals, clipped to [lo, hi) when given."""
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def _spans_of(events):
    return [(e.start, e.end) for e in events]


def busy_ns(ops, lo: int, hi: int) -> int:
    """Time in [lo, hi) during which at least one op runs."""
    return length(union(_spans_of(ops), lo, hi))


def idle_share(ops, lo: int, hi: int) -> float:
    return 1.0 - busy_ns(ops, lo, hi) / max(hi - lo, 1)


def op_time_ns(ops, pred, lo: int | None = None, hi: int | None = None) -> int:
    """Summed durations of the ops ``pred`` selects, clipped to the window."""
    total = 0
    for e in ops:
        if pred(e):
            s = e.start if lo is None else max(e.start, lo)
            t = e.end if hi is None else min(e.end, hi)
            total += max(t - s, 0)
    return total


def subtract(a, b) -> list[tuple[int, int]]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# A TPU op's event name is its HLO instruction: "%name = shape opcode(...)"
# (the CPU backend names an op "opcode.N").  Matrix products run as dot or
# convolution instructions, or as output fusions (``kind=kOutput``: a dot
# with its epilogue fused); a Pallas kernel is a ``tpu_custom_call``.
HLO = re.compile(r"%?(?P<name>[\w.-]+) = (?P<shape>.*?) (?P<op>[a-z][\w-]*)\(")
MATMUL_OPS = {"dot", "dot_general", "convolution"}
# A while loop's (or conditional's, or call's) event spans the events of the
# ops of its body on the same line: only those leaves are device work.
CONTAINER_OPS = {"while", "conditional", "call"}
PALLAS = 'custom_call_target="tpu_custom_call"'


def opcode(name: str) -> str:
    m = HLO.match(name)
    return m["op"] if m else re.sub(r"\.\d+$", "", name)


def is_collective(e: Event) -> bool:
    return bool(COLLECTIVE.match(opcode(e.name)))


def is_matmul(e: Event) -> bool:
    return opcode(e.name) in MATMUL_OPS or "kind=kOutput" in e.name


def is_emb_query(e: Event) -> bool:
    """The read path's only Pallas kernel: ``kernels/emb_query.py``."""
    return PALLAS in e.name


def short_name(name: str) -> str:
    """``opcode[.kind] shape`` of an HLO instruction's text."""
    m = HLO.match(name)
    if not m:
        return name[:120]
    kind = re.search(r"kind=(k\w+)", name)
    return f"{m['op']}{'.' + kind[1] if kind else ''} {m['shape'][:60]}"


def uncovered_ns(target, cover, lo: int, hi: int) -> int:
    """Time of the union of ``target`` ops during which no ``cover`` op runs."""
    return length(subtract(union(_spans_of(target), lo, hi), union(_spans_of(cover), lo, hi)))


def exposed_collective_ns(ops, lo: int, hi: int) -> int:
    coll = [e for e in ops if is_collective(e)]
    rest = [e for e in ops if not is_collective(e)]
    return uncovered_ns(coll, rest, lo, hi)


# ---------------------------------------------------------------------------
# breakdown
# ---------------------------------------------------------------------------


def top_ops(ops, lo: int, hi: int, n: int = 10) -> list[list]:
    """The ``n`` op names with the most summed device time, in seconds."""
    by_name: dict[str, int] = defaultdict(int)
    for e in ops:
        by_name[e.name] += max(min(e.end, hi) - max(e.start, lo), 0)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[short_name(name), ns / 1e9] for name, ns in top if ns > 0]


def idle_by_span(ops, spans, lo: int, hi: int, n: int = 10) -> list[list]:
    """Idle device time in [lo, hi) by the innermost harness span open at
    the time (``(none)`` where none is), the ``n`` largest, in seconds."""
    gaps = subtract([(lo, hi)], union(_spans_of(ops), lo, hi))
    inner = [sp for sp in spans if sp.name != SPAN_PREFIX + "window"]
    cuts = sorted({lo, hi, *(t for sp in inner for t in (sp.start, sp.end) if lo < t < hi)})
    by_name: dict[str, int] = defaultdict(int)
    for s, e in gaps:
        pts = [s] + [c for c in cuts if s < c < e] + [e]
        for a, b in zip(pts, pts[1:]):
            mid = (a + b) / 2
            open_ = [sp for sp in inner if sp.start <= mid < sp.end]
            name = min(open_, key=lambda sp: sp.dur).name if open_ else "(none)"
            by_name[name] += b - a
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]

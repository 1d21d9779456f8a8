"""Thread-aware span tracer: Chrome trace-event export and profiler spans.

Spans measure named intervals on the monotonic clock
(``time.perf_counter_ns``) and export as Chrome trace-event JSON ("X"
complete events plus "M" thread-name metadata), loadable in Perfetto or
chrome://tracing.  One API: ``with span("phase.solve", n=1024):`` opens and
closes a span on one thread; nesting falls out of the event intervals (the
viewers render the stack).  Work that crosses threads is traced as a span on
each side (the panel pipeline's ``pipeline.fetch`` on its prefetch thread,
``pipeline.wait`` and ``pipeline.stage`` on the consumer).

Every span name starts with one of :data:`PREFIXES`, the vocabulary of the
program's layers (``tests/test_obs.py`` checks the names in the sources).

While tracing is enabled each span also enters a
``jax.profiler.TraceAnnotation`` of its name, so under a ``jax.profiler``
trace the program's spans sit on the host thread lines beside the device
ops, on the profiler's clock.  The annotation carries the name alone (span
arguments stay in the Chrome export), so event names in the ``.xplane.pb``
are stable.  Without a running profiler the annotation is a cheap no-op.

Tracing is **disabled by default** and the disabled path is a no-op fast
path: ``span()`` returns a shared null span (no allocation, no clock read,
no lock, no annotation).  Enabling costs two clock reads, one annotation and
one locked list-append per span.

Fencing: device work in jax is dispatched asynchronously, so a span that
only brackets dispatch under-reports the device wall.  When tracing is
enabled with ``enable_tracing(fence=True)``, a span exit on which
``sp.fence(x)`` was called runs ``jax.block_until_ready(x)`` *inside* the
span -- and inside its annotation -- making the recorded duration an honest
device-phase wall.  With tracing disabled (or ``fence=False``) no extra
synchronization is introduced -- timings then measure dispatch plus host
work, and program-level walls stay honest via the existing
block_until_ready at scoring boundaries.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any

import jax

__all__ = [
    "PREFIXES",
    "Tracer",
    "tracer",
    "enable_tracing",
    "disable_tracing",
    "tracing_enabled",
    "span",
]

# The program's span vocabulary: one prefix per layer.
PREFIXES = (
    "sequence.", "phase.", "pipeline.", "query.", "solver.", "tiles.", "oochain.",
    "delta.",
)


def _now_us() -> float:
    return time.perf_counter_ns() / 1000.0


class _NullSpan:
    """Shared no-op span: the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None

    def annotate(self, **args: Any) -> None:
        return None

    def fence(self, value: Any) -> None:
        return None


_NULL_SPAN = _NullSpan()


class _Span:
    """Live same-thread span; records one "X" event on exit."""

    __slots__ = ("_tracer", "name", "args", "t0", "tid", "_fence", "_ann")

    def __init__(self, tracer_: "Tracer", name: str, args: dict[str, Any]):
        self._tracer = tracer_
        self.name = name
        self.args = args
        self.tid = threading.get_ident()
        self.t0 = 0.0
        self._fence = None
        self._ann = None

    def __enter__(self) -> "_Span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = _now_us()
        return self

    def annotate(self, **args: Any) -> None:
        self.args.update(args)

    def fence(self, value: Any) -> None:
        """Register device values to block on at span exit (if fencing on)."""
        self._fence = value

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            if self._fence is not None and self._tracer.fence_enabled:
                _block_until_ready(self._fence)
            self._tracer._record(
                self.name, self.t0, _now_us() - self.t0, self.tid, self.args
            )
        finally:
            self._ann.__exit__(exc_type, exc, tb)
        return None


def _block_until_ready(value: Any) -> None:
    """Wait for the JAX arrays in ``value``; other leaves (store handles,
    host arrays) are already ready.  Device faults raise here."""
    jax.block_until_ready(
        [x for x in jax.tree.leaves(value) if isinstance(x, jax.Array)]
    )


class Tracer:
    """Span recorder; one process-global instance behind :func:`tracer`."""

    def __init__(self) -> None:
        self.enabled = False
        self.fence_enabled = False
        self._lock = threading.Lock()
        self._events: list[dict[str, Any]] = []
        self._thread_names: dict[int, str] = {}

    # -- lifecycle -----------------------------------------------------------

    def enable(self, fence: bool = False) -> "Tracer":
        self.enabled = True
        self.fence_enabled = fence
        return self

    def disable(self) -> None:
        self.enabled = False
        self.fence_enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._thread_names.clear()

    # -- recording -----------------------------------------------------------

    def span(self, name: str, **args: Any):
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def _record(
        self, name: str, t0: float, dur: float, tid: int, args: dict[str, Any]
    ) -> None:
        with self._lock:
            self._note_thread_locked(tid)
            self._events.append(
                {
                    "name": name,
                    "ph": "X",
                    "ts": t0,
                    "dur": max(dur, 0.0),
                    "pid": os.getpid(),
                    "tid": tid,
                    "args": args,
                }
            )

    def _note_thread_locked(self, tid: int) -> None:
        if tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name

    # -- export --------------------------------------------------------------

    def events(self) -> list[dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._events]

    def to_chrome_trace(self) -> dict[str, Any]:
        """Chrome trace-event JSON object (Perfetto / chrome://tracing)."""
        with self._lock:
            pid = os.getpid()
            meta = [
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": tname},
                }
                for tid, tname in sorted(self._thread_names.items())
            ]
            return {
                "traceEvents": meta + [dict(e) for e in self._events],
                "displayTimeUnit": "ms",
                "otherData": {"clock": "perf_counter", "unit": "us"},
            }

    def save(self, path: str) -> None:
        doc = self.to_chrome_trace()
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)


_TRACER = Tracer()


def tracer() -> Tracer:
    return _TRACER


def enable_tracing(fence: bool = False) -> Tracer:
    return _TRACER.enable(fence=fence)


def disable_tracing() -> None:
    _TRACER.disable()


def tracing_enabled() -> bool:
    return _TRACER.enabled


def span(name: str, **args: Any):
    """Open a span on the global tracer (null span when disabled)."""
    if not _TRACER.enabled:
        return _NULL_SPAN
    return _Span(_TRACER, name, args)


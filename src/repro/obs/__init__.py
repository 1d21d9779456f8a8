"""Unified observability: span tracing, metrics registry, structured reports.

Four parts (see the module docstrings for depth):

* :mod:`repro.obs.trace` -- thread-aware span tracer, Chrome trace-event
  export, spans mirrored into the ``jax.profiler`` trace, disabled-by-default
  no-op fast path.
* :mod:`repro.obs.metrics` -- process-wide counters/gauges/series registry
  with atomic snapshot/delta/reset; backs the ``stream_stats()`` and
  ``program_cache_stats()`` facades in :mod:`repro.core.tiles`.
* :mod:`repro.obs.compiles` -- the always-on ``jit.compiles`` counter,
  installed on import.
* :mod:`repro.obs.report` -- versioned RunReport JSON (+ validators) emitted
  by ``caddelag-run --run-report``.

:func:`phase` is the glue the pipeline layers use: one call opens a trace
span (when tracing is on) AND accumulates the always-on
``phase.<name>.seconds`` / ``phase.<name>.calls`` registry counters the
per-transition breakdowns are cut from.  :func:`timed` does the same for the
per-panel spans of the read path, counting only while tracing is enabled.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

from repro.obs import compiles, metrics, trace
from repro.obs.metrics import (
    REGISTRY,
    MetricsRegistry,
    MetricsSnapshot,
    registry,
    scoped,
)
from repro.obs.trace import (
    Tracer,
    disable_tracing,
    enable_tracing,
    span,
    tracer,
    tracing_enabled,
)

__all__ = [
    "metrics",
    "trace",
    "MetricsRegistry",
    "MetricsSnapshot",
    "REGISTRY",
    "registry",
    "scoped",
    "Tracer",
    "tracer",
    "enable_tracing",
    "disable_tracing",
    "tracing_enabled",
    "span",
    "timed",
    "phase",
]

compiles.install()


@contextmanager
def _counted(name: str, args: dict):
    t0 = time.perf_counter()
    sp = trace.span(name, **args)
    sp.__enter__()
    try:
        yield sp
    finally:
        sp.__exit__(None, None, None)
        dt = time.perf_counter() - t0
        REGISTRY.add_named({f"{name}.seconds": dt, f"{name}.calls": 1.0})


def phase(name: str, **args):
    """Time one pipeline phase: a trace span + always-on registry counters.

    The yielded span supports ``fence(x)`` -- with tracing enabled under
    ``enable_tracing(fence=True)``, span exit blocks on ``x`` so both the
    span and the ``phase.<name>.seconds`` counter record an honest device
    wall (the counter is accumulated *after* the span exits, fence included).
    With tracing disabled the span is the shared null span and the counters
    measure dispatch + host work only; program-level walls remain honest via
    the block_until_ready at scoring boundaries.
    """
    return _counted(f"phase.{name}", args)


def timed(name: str, **args):
    """A trace span + ``<name>.seconds`` / ``<name>.calls`` counters, counted
    only while tracing is enabled.

    For spans opened many times a request (one per panel): with tracing
    disabled this returns the shared null span, so the hot path reads no
    clock and touches no registry lock.
    """
    if not trace.tracing_enabled():
        return trace._NULL_SPAN
    return _counted(name, args)

"""Always-on count of JAX compiles in the metrics registry.

One ``jax.monitoring`` listener per process (:func:`install`, idempotent,
called when :mod:`repro.obs` is imported) adds one to ``jit.compiles`` for
every executable built by XLA or loaded from the persistent compile cache
(JAX's ``/jax/core/compile/backend_compile_duration`` event).

JAX calls the listener only when it compiles, so a steady state that reuses
its executables pays nothing; a delta of ``jit.compiles`` over a window says
whether anything in it recompiled.
"""

from __future__ import annotations

import threading

import jax

from repro.obs.metrics import REGISTRY

__all__ = ["COMPILE_EVENT", "install"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_installed = False


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == COMPILE_EVENT:
        REGISTRY.inc("jit.compiles")


def install() -> None:
    """Register the listener once per process."""
    global _installed
    with _lock:
        if _installed:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True

"""Seconds per transition in the program's ingest phase: the
``phase.ingest.seconds`` counter of the window, fenced on the phase's output
in the traced run (``enable_tracing(fence=True)``)."""


def read(rec):
    if not rec.count:
        return None
    value = rec.registry.get("phase.ingest.seconds")
    return None if value is None else value / rec.count

"""Seconds per transition in the program's chain phase: the
``phase.chain.seconds`` counter of the window, fenced on the phase's output
in the traced run (``enable_tracing(fence=True)``)."""


def read(rec):
    if not rec.count:
        return None
    value = rec.registry.get("phase.chain.seconds")
    return None if value is None else value / rec.count

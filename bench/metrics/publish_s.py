"""Seconds per transition in the program's publish phase: the
``phase.publish.seconds`` counter of the window (the summed ``phase.publish``
spans: the host copy of the committed embedding and its write to the
``EmbeddingStore``)."""


def read(rec):
    if not rec.count:
        return None
    value = rec.registry.get("phase.publish.seconds")
    return None if value is None else value / rec.count

"""Mean seconds of one ``delta.update`` span in the window: the incremental
update of the chain operator (sketch, propagation, correction), fenced on
the corrected operator, from the ``delta.update.seconds`` / ``.calls``
counters (counted while tracing is on).  A call whose sketch the drift
monitor rejects counts too."""


def read(rec):
    calls = rec.registry.get("delta.update.calls")
    if not calls:
        return None
    return rec.registry["delta.update.seconds"] / calls

"""Mean milliseconds of one ``query.panel`` span in the window: the host's
put of a panel's degree slice and dispatch of the distance/top-k kernel on
it, from the ``query.panel.seconds`` / ``.calls`` counters."""


def read(rec):
    calls = rec.registry.get("query.panel.calls")
    if not calls:
        return None
    return 1e3 * rec.registry["query.panel.seconds"] / calls

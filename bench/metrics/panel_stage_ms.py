"""Mean milliseconds of one ``pipeline.stage`` span in the window: the read
path's consumer putting one Z panel on the chip (pinned-host copy plus
``device_put``), from the ``pipeline.stage.seconds`` / ``.calls`` counters."""


def read(rec):
    calls = rec.registry.get("pipeline.stage.calls")
    if not calls:
        return None
    return 1e3 * rec.registry["pipeline.stage.seconds"] / calls

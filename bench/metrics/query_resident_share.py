"""Share of the window's queries, in percent, that walked the store's
device-resident copy of the artifact instead of streaming Z from the store:
100 x ``query.resident.hits`` / ``query.calls``.

The record's registry holds only the counters that moved in the window, so a
window of misses leaves no hit key, as does a program that keeps nothing
resident.  The reader tells them apart by the process's registry (the reader
runs in the process that ran the window): a program that keeps artifacts
resident has counted a fill or a hit there, set-up's first query included."""


def read(rec):
    from repro.obs import REGISTRY

    calls = rec.registry.get("query.calls")
    if not calls or all(
        REGISTRY.value(name, None) is None
        for name in ("query.resident.hits", "query.resident.fills")
    ):
        return None
    return 100.0 * rec.registry.get("query.resident.hits", 0.0) / calls

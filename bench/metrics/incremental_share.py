"""Share of the window's transitions, in percent, that a delta update of the
chain served instead of a rebuild: 100 x ``chain.incremental_updates`` /
transitions completed.

The record's registry holds only the counters that moved in the window, so a
window of rebuilds leaves no update key.  A program in incremental mode has
counted its set-up's base build (``chain.full_rebuilds``) in the process's
registry (the reader runs in the process that ran the window); without it
the program has no incremental path to read, and nothing is reported."""


def read(rec):
    from repro.obs import REGISTRY

    if not rec.count or REGISTRY.value("chain.full_rebuilds", None) is None:
        return None
    return 100.0 * rec.registry.get("chain.incremental_updates", 0.0) / rec.count

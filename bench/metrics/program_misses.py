"""Tile-program cache misses per transition in the window
(``program_cache.misses``): each miss builds, traces and lowers a program."""


def read(rec):
    if not rec.count:
        return None
    return rec.registry.get("program_cache.misses", 0.0) / rec.count

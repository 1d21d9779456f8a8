"""Share of the HBM floor that the window's delta updates reach: the time
to read each distinct n x n operand of one update once at the HBM peak
(``bench.delta_work``), times the updates completed
(``chain.incremental_updates``), over the seconds spent in ``delta.update``
spans.  The spans' time includes the calls the drift monitor rejected, so
the share reads no higher than the completed updates' own."""

from bench.delta_work import update_floor_bytes


def read(rec):
    if rec.peaks is None:
        return None
    seconds = rec.registry.get("delta.update.seconds")
    updates = rec.registry.get("chain.incremental_updates")
    if not seconds or not updates:
        return None
    cfg = rec.cell.config
    floor_s = update_floor_bytes(int(cfg["n"]), int(cfg["d"])) / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s * updates / seconds

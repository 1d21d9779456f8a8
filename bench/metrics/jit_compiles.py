"""Executables JAX built or loaded from the compile cache in the window, per
transition or per query: the delta of the program's ``jit.compiles``
counter.

Reads ``jit_compiles.write`` and ``jit_compiles.read``.  The record's
registry holds only the counters that moved in the window, so a window with
no compile leaves no key, as does a program that counts no compiles.  The
reader tells them apart by the process's registry (the reader runs in the
process that ran the window): a program that counts compiles has counted
those of its set-up there, and one that does not has no such counter."""


def read(rec):
    from repro.obs import REGISTRY

    if not rec.count or REGISTRY.value("jit.compiles", None) is None:
        return None
    return rec.registry.get("jit.compiles", 0.0) / rec.count

"""Share of the window's transitions, in percent, whose chain was built in
the factored form (the d - 1 squarings alone, applied as factors by the
solve): 100 x ``chain.factored_builds`` / transitions completed.

The program counts every plain chain build there, 0 for a materialized one,
so its set-up's builds have put the counter in the process's registry (the
reader runs in the process that ran the window).  A program without the
factored form has no such counter, and nothing is reported."""


def read(rec):
    from repro.obs import REGISTRY

    if not rec.count or REGISTRY.value("chain.factored_builds", None) is None:
        return None
    return 100.0 * rec.registry.get("chain.factored_builds", 0.0) / rec.count

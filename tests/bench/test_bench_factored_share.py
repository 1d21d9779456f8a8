"""``factored_share``: the share of the window's transitions whose chain was
built in the factored form.  The entry, the reader on hand-built records,
silent on a program without the counter, and traced tiny write runs on the
CPU on both sides of the program's rule (n / 128 against q)."""

import json

import pytest

from bench import harness
from bench.spec import ROOT, load_benchmark, load_reader, resolve_cell
from bench_tiny import tiny_root

SEED = 2**32 + 77
WRITE = ("climate-128x128.write", "synth-gmm-22528.write-2x2")


def record(count, registry):
    return harness.Record(cell=resolve_cell(WRITE[0]), count=count, window_s=40.0,
                          registry=registry)


def test_entry_names_the_write_cells():
    (entry,) = [m for m in load_benchmark()["per_layer"] if m["name"] == "factored_share"]
    assert tuple(entry["workloads"]) == WRITE
    assert (entry["source"], entry["layer"], entry["moves"], entry["better"], entry["unit"]) == (
        "program_counter", "chain build", "transition_s", "higher", "%")
    for cell in WRITE:
        assert "factored_share" in {m["name"] for m in resolve_cell(cell).per_layer}


def test_share_is_factored_builds_over_transitions(monkeypatch):
    import repro.obs
    from repro.obs.metrics import MetricsRegistry

    read = load_reader(ROOT, "factored_share")
    reg = MetricsRegistry()
    reg.add_named({"chain.factored_builds": 3.0})  # set-up's three builds
    monkeypatch.setattr(repro.obs, "REGISTRY", reg)
    assert read(record(12, {"chain.factored_builds": 12.0})) == 100.0
    assert read(record(12, {"chain.factored_builds": 3.0})) == 25.0
    assert read(record(12, {})) == 0.0  # every build materialized
    assert read(record(0, {})) is None  # no transitions


def test_silent_without_the_program_counter(monkeypatch):
    """A program without the factored form (the parent of this counter)
    reads nothing and raises nothing."""
    import repro.obs
    from repro.obs.metrics import MetricsRegistry

    monkeypatch.setattr(repro.obs, "REGISTRY", MetricsRegistry())
    read = load_reader(ROOT, "factored_share")
    assert read(record(12, {"chain.builds": 12.0, "phase.chain.seconds": 40.0})) is None


@pytest.mark.parametrize("q,share", [(3, 100.0), (10, 0.0)])
def test_traced_tiny_write_cell_reads_the_form(tmp_path, q, share):
    """At n=512, n / 128 = 4: q=3 builds every chain factored, q=10 none;
    both runs stay correct against the reference at the same q."""
    root = tiny_root(tmp_path)
    path = root / "bench/configs/climate-128x128.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "q": q}))
    out = harness.run(WRITE[0], SEED, 0.5, True, root=root, require_chip=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["metrics"]["factored_share"] == {"value": share, "unit": "%"}

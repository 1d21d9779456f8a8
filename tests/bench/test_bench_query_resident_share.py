"""``query_resident_share``: the share of the window's queries that walked
the store's device-resident copy of the artifact.  The reader on hand-built
records, silent on a program without the counters, and a traced tiny read
run on the CPU with the resident copy on, where every query of the window is
a hit and the read faults still make the run incorrect."""

import pytest

from bench import faults, harness
from bench.spec import ROOT, load_benchmark, load_reader, resolve_cell
from bench_tiny import tiny_root

SEED = 2**31 + 29
READ = "climate-360x720.read"


def record(count, registry):
    return harness.Record(cell=resolve_cell(READ), count=count, window_s=40.0,
                          registry=registry)


@pytest.fixture
def resident(monkeypatch):
    """The CPU reports no memory stats, so the store keeps nothing there
    unless given free bytes to count; give it 1 GiB, as a chip would."""
    import repro.store.embstore as embstore

    monkeypatch.setattr(embstore, "NO_STATS_FREE_BYTES", 2**30)


@pytest.fixture
def fresh_programs():
    from repro.core.tiles import clear_program_cache

    clear_program_cache()
    yield
    clear_program_cache()


def test_entry_names_the_read_cell():
    (entry,) = [m for m in load_benchmark()["per_layer"] if m["name"] == "query_resident_share"]
    assert entry["workloads"] == [READ]
    assert (entry["source"], entry["layer"], entry["moves"], entry["better"], entry["unit"]) == (
        "program_counter", "read path", "queries_per_s", "higher", "%")
    assert "query_resident_share" in {m["name"] for m in resolve_cell(READ).per_layer}


def test_share_is_hits_over_queries(monkeypatch):
    import repro.obs
    from repro.obs.metrics import MetricsRegistry

    read = load_reader(ROOT, "query_resident_share")
    reg = MetricsRegistry()
    reg.inc("query.resident.fills")  # set-up's first query kept the artifact
    monkeypatch.setattr(repro.obs, "REGISTRY", reg)
    assert read(record(40, {"query.calls": 40.0, "query.resident.hits": 30.0})) == 75.0
    assert read(record(40, {"query.calls": 40.0})) == 0.0  # every query a miss
    assert read(record(0, {})) is None  # no queries


def test_silent_without_the_program_counters(monkeypatch):
    """A program that keeps nothing resident (the parent of the resident
    copy) reads nothing and raises nothing."""
    import repro.obs
    from repro.obs.metrics import MetricsRegistry

    monkeypatch.setattr(repro.obs, "REGISTRY", MetricsRegistry())
    read = load_reader(ROOT, "query_resident_share")
    assert read(record(40, {"query.calls": 40.0, "pipeline.stage.calls": 120.0})) is None


def test_traced_read_cell_walks_the_resident_copy(tmp_path, resident):
    """Set-up's first query keeps the artifact on the device, so every query
    of the window is a hit: no panel is staged in it, nothing compiles."""
    out = harness.run(READ, SEED, 1.0, True, root=tiny_root(tmp_path), require_chip=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    m = out["metrics"]
    assert m["query_resident_share"] == {"value": 100.0, "unit": "%"}
    assert m["panel_dispatch_ms"]["value"] > 0 and "panel_stage_ms" not in m
    assert m["jit_compiles.read"] == {"value": 0.0, "unit": "count"}


@pytest.mark.parametrize("fault", sorted(faults.READ))
def test_read_fault_on_the_resident_copy_is_not_correct(tmp_path, fault, monkeypatch,
                                                        resident, fresh_programs):
    faults.READ[fault](monkeypatch.setattr)
    out = harness.run(READ, SEED, 0.3, False, root=tiny_root(tmp_path), require_chip=False)
    assert not out["correct"], out["checks"]

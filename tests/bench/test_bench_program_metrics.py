"""The per-layer metrics read from the program's own spans and counters:
each reader on hand-built records, silent on a program without the
counters, and reported by a traced run of a tiny cell on the CPU."""

import json

import pytest

from bench import harness
from bench.spec import ROOT, load_benchmark, load_reader, resolve_cell
from bench_tiny import tiny_root

SEED = 2**31 + 29
WRITE = ("climate-128x128.write", "synth-gmm-22528.write-2x2")
READ = ("climate-360x720.read",)
PROGRAM_METRICS = {
    "publish_s": WRITE,
    "jit_compiles.write": WRITE,
    "jit_compiles.read": READ,
    "panel_stage_ms": READ,
    "panel_dispatch_ms": READ,
}


def record(count, registry, cell="climate-128x128.write"):
    return harness.Record(cell=resolve_cell(cell), count=count, window_s=40.0,
                          registry=registry)


@pytest.mark.parametrize("name", sorted(PROGRAM_METRICS))
def test_program_metric_entries_name_their_cells(name):
    (entry,) = [m for m in load_benchmark()["per_layer"] if m["name"] == name]
    assert tuple(entry["workloads"]) == PROGRAM_METRICS[name]
    assert entry["source"] in ("program_span", "program_counter")
    for cell in entry["workloads"]:
        assert name in {m["name"] for m in resolve_cell(cell).per_layer}


def test_publish_s_is_publish_seconds_per_transition():
    read = load_reader(ROOT, "publish_s")
    assert read(record(10, {"phase.publish.seconds": 0.5, "phase.publish.calls": 10.0})) == 0.05
    assert read(record(0, {"phase.publish.seconds": 0.5})) is None


@pytest.mark.parametrize("name,cell", [("jit_compiles.write", WRITE[0]), ("jit_compiles.read", READ[0])])
def test_jit_compiles_per_item_counts_a_quiet_window_as_zero(name, cell, monkeypatch):
    import repro.obs
    from repro.obs.metrics import MetricsRegistry

    read = load_reader(ROOT, name)
    reg = MetricsRegistry()
    reg.inc("jit.compiles", 40.0)  # set-up compiled: the program counts compiles
    monkeypatch.setattr(repro.obs, "REGISTRY", reg)
    assert read(record(12, {"jit.compiles": 12.0}, cell)) == 1.0
    assert read(record(160, {}, cell)) == 0.0
    assert read(record(0, {}, cell)) is None


def test_panel_means_are_span_seconds_over_calls():
    stage = load_reader(ROOT, "panel_stage_ms")
    dispatch = load_reader(ROOT, "panel_dispatch_ms")
    reg = {"pipeline.stage.seconds": 0.27, "pipeline.stage.calls": 54.0,
           "query.panel.seconds": 0.108, "query.panel.calls": 54.0}
    assert stage(record(2, reg, READ[0])) == pytest.approx(5.0)
    assert dispatch(record(2, reg, READ[0])) == pytest.approx(2.0)


@pytest.mark.parametrize("name", sorted(PROGRAM_METRICS))
def test_program_metrics_are_silent_without_the_program_counters(name, monkeypatch):
    """A program without these spans and counters (the parent of this
    instrumentation) reads nothing and raises nothing."""
    import repro.obs
    from repro.obs.metrics import MetricsRegistry

    monkeypatch.setattr(repro.obs, "REGISTRY", MetricsRegistry())
    rec = record(12, {"phase.chain.seconds": 40.0, "pipeline.consumer_wait_seconds": 1.0,
                      "program_cache.misses": 12.0}, PROGRAM_METRICS[name][0])
    assert load_reader(ROOT, name)(rec) is None


def test_traced_read_cell_reports_the_panel_metrics(tmp_path):
    root = tiny_root(tmp_path)
    out = harness.run("climate-360x720.read", SEED, 1.0, True, root=root, require_chip=False)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["panel_stage_ms"]["value"] > 0 and m["panel_dispatch_ms"]["value"] > 0
    assert m["jit_compiles.read"] == {"value": 0.0, "unit": "count"}  # warmed in set-up
    assert m["panel_stage_ms"]["unit"] == m["panel_dispatch_ms"]["unit"] == "ms"


def test_traced_write_cell_reports_publish_and_compiles(tmp_path):
    root = tiny_root(tmp_path)
    path = root / "bench/configs/climate-128x128.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "n_lat": 8, "n_lon": 16, "n": 128}))
    out = harness.run("climate-128x128.write", SEED, 0.5, True, root=root, require_chip=False)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["publish_s"]["value"] > 0
    # each transition's graph build compiles its new closure once
    assert m["program_misses"]["value"] == 1.0
    assert m["jit_compiles.write"] == {"value": 1.0, "unit": "count"}

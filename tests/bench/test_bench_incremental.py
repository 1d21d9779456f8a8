"""The drifting climate cell, ``climate-128x128.drift``, on the CPU: a tiny
copy runs through the harness with ``correct`` true and its three per-layer
metrics read and in range; the control fails its limit; the delta update's
roofline share stays at or under 100% on hand-built records.

The tiny copy is the cell's configuration at 16 x 32 (n=512).  Its limit is
set from CPU readings by the rule of the chip's limits: at this size and
drift 0.01 the exact program (both incremental flags off) reads up to 3.8e-4
against the reference and the incremental program up to 5.6e-4 (score
differences shrink with the drift, the float32 rounding does not), while the
control reads from 3.0e-3; 1.5e-3 lies between them with room on both
sides."""

import dataclasses
import json

import numpy as np
import pytest

from bench import check, harness, reference
from bench import traffic as tf
from bench.delta_work import update_floor_bytes, update_operands
from bench.roofline import PEAKS
from bench.spec import ROOT, load_benchmark, load_reader, resolve_cell
from bench_tiny import tiny_root
from repro.core.embedding import CommuteConfig

CELL = "climate-128x128.drift"
CONFIG = "climate-128x128-incremental"
NEW_METRICS = ("incremental_share", "delta_update_s", "delta_update_roofline")
TINY = {"n_lat": 16, "n_lon": 32, "n": 512, "limits": {"write": {"answer_gap": 1.5e-3}}}
SEED = 2**32 + 99


@pytest.fixture
def root(tmp_path):
    root = tiny_root(tmp_path)
    path = root / "bench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg.update(TINY)
    path.write_text(json.dumps(cfg))
    return root


def test_cell_entries():
    """One configuration, one cell, and the three new metrics, each moving
    ``transition_s`` in this cell alone; the cell reports neither
    ``mxu_roofline`` nor ``collective_share`` nor a read metric.  The
    program runs on one chip (a 1x1 mesh)."""
    bench = load_benchmark()
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "climate-drift", 1)
    assert resolve_cell(CELL).config["mesh"] == [1, 1]
    assert resolve_cell(CELL).config["assumed"]["delta_budget"] == CommuteConfig.delta_budget
    for name in NEW_METRICS:
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "transition_s"
    c = resolve_cell(CELL)
    assert [m["name"] for m in c.end_to_end] == ["setup_s", "transition_s"]
    reported = {m["name"] for m in c.per_layer}
    assert set(NEW_METRICS) <= reported
    assert not reported & {"mxu_roofline", "collective_share", "query_service_ms",
                           "device_idle_share.read", "emb_query_roofline"}
    cfg, base = c.config, resolve_cell("climate-128x128.write").config
    assert cfg["warm_start"] and cfg["incremental_chain"]
    same = ("n_lat", "n_lon", "n", "eps_rp", "d", "q", "solver", "schedule", "reduced")
    assert {k: cfg[k] for k in same} == {k: base[k] for k in same}
    assert c.traffic["drift"] == 0.01 and c.traffic["event"]["strength"] == 0.0


def test_tiny_cell_is_correct_and_reports_its_metrics(root):
    out = harness.run(CELL, SEED, 0.5, False, root=root, require_chip=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"setup_s", "transition_s"}

    out = harness.run(CELL, SEED + 1, 0.5, True, root=root, require_chip=False)
    assert out["correct"], out["checks"]
    m = {name: v["value"] for name, v in out["metrics"].items()}
    assert 0.0 < m["incremental_share"] <= 100.0
    assert m["delta_update_s"] > 0.0
    assert m["chain_s"] > 0.0 and m["solve_s"] > 0.0
    # the CPU has no published peak: the share is left out, not guessed
    assert "delta_update_roofline" not in m
    assert "mxu_roofline" not in m and "collective_share" not in m


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**33 + 7])
def test_control_is_not_correct(root, seed):
    """The reference at three passes against the reference at six fails the
    tiny copy's limit."""
    import jax

    c = resolve_cell(CELL, root)
    snaps = tf.snapshots(c.traffic, c.config, seed)
    mesh = harness.mesh_ctx(c.config, jax.devices())
    sharding = mesh.sharding(mesh.matrix_spec)
    t2 = harness.SETUP_SNAPSHOTS + 1
    ref = reference.transition_scores(snaps, t2, c.config, sharding)
    ctl = reference.transition_scores(snaps, t2, c.config, sharding, passes=3)
    top_k = int(c.config["top_k"])
    gap, _ = check.answer_gap(ctl, np.argsort(-ctl, kind="stable")[:top_k], ref, top_k)
    assert gap > c.config["limits"]["write"]["answer_gap"], gap


def record(registry, count=10, peaks=None):
    return harness.Record(cell=resolve_cell(CELL), count=count, window_s=40.0,
                          registry=registry, peaks=peaks)


def test_floor_counts_the_published_operands():
    """A', d T levels, d - 2 P levels and P1: 12 float32 n x n operands at
    d=6, 12.9 GB at n=16384."""
    assert update_operands(6) == 12
    assert update_floor_bytes(16384, 6) == 12 * 16384**2 * 4


def test_readers_on_hand_built_records(monkeypatch):
    from repro.obs import REGISTRY

    monkeypatch.setattr(REGISTRY, "value", lambda name, default=0.0: 1.0)
    share = load_reader(ROOT, "incremental_share")
    assert share(record({"chain.incremental_updates": 9.0})) == 90.0
    assert share(record({})) == 0.0  # a window of rebuilds
    mean = load_reader(ROOT, "delta_update_s")
    assert mean(record({"delta.update.seconds": 1.5, "delta.update.calls": 10.0})) == 0.15
    assert mean(record({})) is None

    roof = load_reader(ROOT, "delta_update_roofline")
    peaks = PEAKS["TPU v5 lite"]
    floor_s = update_floor_bytes(16384, 6) / peaks["hbm_bytes_per_s"]
    reg = {"delta.update.seconds": 10 * 2 * floor_s, "delta.update.calls": 10.0,
           "chain.incremental_updates": 10.0}
    assert roof(record(reg, peaks=peaks)) == pytest.approx(50.0)
    assert roof(record(reg)) is None  # no peaks: no share
    assert roof(record({**reg, "chain.incremental_updates": 0.0}, peaks=peaks)) is None


def test_silent_without_the_program_counters(monkeypatch):
    """A program with no incremental path counted no base build: the share
    reads nothing, and the span metrics have nothing to read."""
    from repro.obs import REGISTRY

    monkeypatch.setattr(REGISTRY, "value", lambda name, default=0.0: default)
    for name in NEW_METRICS:
        assert load_reader(ROOT, name)(record({}, peaks=PEAKS["TPU v5 lite"])) is None


def test_roofline_stays_under_100_at_the_programs_own_bytes(ctx1):
    """The program's passes of one update read at least the floor's bytes
    (``chain.delta_gemm_bytes`` counts them), so an update timed at exactly
    those bytes over the peak -- faster than any chip runs it -- reads at
    most 100%."""
    from repro.core import CommuteConfig, build_base_chain, try_delta_update
    from repro.obs import REGISTRY

    c = resolve_cell(CELL)
    geom = {**c.config, "n_lat": 8, "n_lon": 8, "n": 64}
    snaps = tf.snapshots(c.traffic, geom, SEED)
    cfg = CommuteConfig(eps_rp=1e-3, d=int(geom["d"]), q=10, schedule="xla",
                        incremental_chain=True, warm_start=True)
    base = build_base_chain(ctx1, snaps.adjacency(ctx1, 0), cfg)
    m0 = REGISTRY.snapshot()
    assert try_delta_update(ctx1, base, snaps.adjacency(ctx1, 1), cfg) is not None
    moved = REGISTRY.delta(m0)["chain.delta_gemm_bytes"]
    base.release()
    assert moved >= update_floor_bytes(64, cfg.d)

    peaks = PEAKS["TPU v5 lite"]
    reg = {"delta.update.seconds": moved / peaks["hbm_bytes_per_s"], "delta.update.calls": 1.0,
           "chain.incremental_updates": 1.0}
    rec = harness.Record(cell=dataclasses.replace(c, config=geom), count=1, window_s=1.0,
                         registry=reg, peaks=peaks)
    assert 0.0 < load_reader(ROOT, "delta_update_roofline")(rec) <= 100.0

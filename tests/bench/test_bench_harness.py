"""The benchmark's harness on the CPU: BENCHMARK.json's keys and limits,
the traffic generators, the metric arithmetic, and a cell added by files."""

import json
import math
import re
import statistics
import time

import jax
import numpy as np
import pytest

from bench import check, harness
from bench import traffic as tf
from bench.spec import NAME_RE, ROOT, UNIT_RE, load_benchmark, load_reader, reader_path, resolve_cell
from bench_tiny import tiny_root

BENCH = load_benchmark()
SEED = 2**31 + 17  # seeds run past 32 signed bits


# ---------------------------------------------------------------------------
# BENCHMARK.json resolves, by name, to files that exist
# ---------------------------------------------------------------------------


def test_top_level_keys_and_command():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert BENCH["command"] == ["python3", "bench/run_cell.py"]
    assert (ROOT / BENCH["command"][1]).is_file()
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(workload):
    cell = resolve_cell(workload)
    entry = next(w for w in BENCH["workloads"] if w["name"] == workload)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    assert cell.kind in harness.LOOPS
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(load_reader(ROOT, m["name"]))
    assert cell.config["limits"][cell.kind], "the configuration holds the check's limits"


def test_split_metric_is_read_by_its_base_reader(tmp_path):
    """``<base>.<suffix>`` falls back to ``<base>.py``; an exact file wins."""
    assert reader_path(ROOT, "device_idle_share.read").name == "device_idle_share.py"
    assert reader_path(ROOT, "device_idle_share.write") == reader_path(ROOT, "device_idle_share.read")
    root = tiny_root(tmp_path)
    (root / "bench/metrics/device_idle_share.read.py").write_text("def read(rec):\n    return 1.0\n")
    assert load_reader(root, "device_idle_share.read")(None) == 1.0
    assert reader_path(root, "graph_s").name == "graph_s.py"


def test_configs_metrics_names_and_units():
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert c["file"] not in files
        files.add(c["file"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert all(NAME_RE.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    names = [x["name"] for x in BENCH["configs"] + BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    assert all(NAME_RE.match(n) for n in names), names
    assert len(set(m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"])) == len(
        BENCH["end_to_end"] + BENCH["per_layer"]
    )
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        for w in m.get("workloads", []):
            cell = resolve_cell(w)
            assert m["moves"] in {x["name"] for x in cell.end_to_end}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT_RE.match(m["unit"]) and m["better"] in ("lower", "higher")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    assert len(json.dumps(BENCH).encode()) <= 64 * 1024


# ---------------------------------------------------------------------------
# traffic: deterministic per seed, different across seeds
# ---------------------------------------------------------------------------

CLIMATE = json.loads((ROOT / "bench/traffic/climate-monthly.json").read_text())
GMM = json.loads((ROOT / "bench/traffic/gmm-drift.json").read_text())
QUERIES = json.loads((ROOT / "bench/traffic/query-mix.json").read_text())


@pytest.mark.parametrize("kind", ["climate", "gmm"])
def test_snapshot_features_are_seeded(kind):
    traffic, cfg = (CLIMATE, {"n_lat": 8, "n_lon": 8}) if kind == "climate" else (GMM, {"n": 64})
    a = tf.snapshots(traffic, cfg, SEED)
    b = tf.snapshots(traffic, cfg, SEED)
    c = tf.snapshots(traffic, cfg, SEED + 1)
    for t in (0, 3, 2):  # out of order: regenerated from the start
        np.testing.assert_array_equal(np.asarray(a.features(t)), np.asarray(b.features(t)))
        assert not np.allclose(np.asarray(a.features(t)), np.asarray(c.features(t)))
    assert not np.allclose(np.asarray(a.features(1)), np.asarray(a.features(2)))


def test_climate_event_switches_on_one_snapshot_in_four():
    s = tf.snapshots(CLIMATE, {"n_lat": 16, "n_lon": 16}, SEED)
    on = [s.event_on(t) for t in range(8)]
    assert sum(on) == 2
    carried = [len(s.truth(t)) > 0 for t in range(1, 9)]
    assert sum(carried) == 4  # half the transitions carry the event
    assert len(s.event_nodes) == max(1, int(0.02 * 256))


def test_gmm_injection_is_symmetric_sparse_and_seeded():
    n = 256
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    s = tf.snapshots(GMM, {"n": n}, SEED)
    r = np.asarray(s.injection(1, sharding))
    np.testing.assert_array_equal(r, r.T)
    assert np.all(np.diag(r) == 0) and np.all((r >= 0) & (r < 1))
    density = np.count_nonzero(r) / (n * (n - 1))
    assert 0.035 < density < 0.065
    np.testing.assert_array_equal(r, np.asarray(tf.snapshots(GMM, {"n": n}, SEED).injection(1, sharding)))
    assert not np.array_equal(r, np.asarray(s.injection(2, sharding)))
    assert not np.array_equal(r, np.asarray(tf.snapshots(GMM, {"n": n}, SEED + 1).injection(1, sharding)))


def test_zipf_ranks_follow_the_power_law():
    rng = np.random.default_rng(0)
    ranks = tf.zipf_ranks(rng, 1000, 0.99, 200_000)
    counts = np.bincount(ranks, minlength=1000)
    assert counts.argmax() == 0 and ranks.min() >= 0 and ranks.max() < 1000
    assert counts[0] / counts[1] == pytest.approx(2**0.99, rel=0.05)
    assert counts[0] / counts[9] == pytest.approx(10**0.99, rel=0.1)


def test_poisson_gaps_are_the_same_set_in_a_seeded_order():
    a = tf.poisson_gaps(np.random.default_rng(1), 5.0, 400)
    b = tf.poisson_gaps(np.random.default_rng(2), 5.0, 400)
    np.testing.assert_allclose(np.sort(a), np.sort(b))
    assert not np.allclose(a, b)
    assert a.mean() == pytest.approx(1 / 5.0, rel=0.05)
    assert a.std() / a.mean() == pytest.approx(1.0, rel=0.1)  # exponential: CV 1


def test_query_schedule_keeps_rate_shares_and_zipf_nodes():
    traffic = {**QUERIES, "rate_per_s": 20.0}
    sched = tf.query_schedule(traffic, 512, SEED, 30.0)
    assert len(sched) == 600
    due = np.array([q.due_s for q in sched])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert due[-1] == pytest.approx(30.0, rel=0.05)
    kinds = [q.kind for q in sched]
    assert kinds.count("top_anomalies") == 60
    nodes = [q.node for q in sched if q.kind == "nearest_neighbors"]
    assert all(0 <= x < 512 for x in nodes)
    assert nodes.count(statistics.mode(nodes)) > 40  # the Zipf head: ~1/7 of 540
    assert [q.node for q in tf.query_schedule(traffic, 512, SEED, 30.0)] == [q.node for q in sched]
    other = tf.query_schedule(traffic, 512, SEED + 1, 30.0)
    assert [q.kind for q in other] != kinds
    assert [q.due_s for q in other] == list(due)  # the same load at the same times


# ---------------------------------------------------------------------------
# metric arithmetic
# ---------------------------------------------------------------------------


def test_throughput_is_window_over_count():
    assert check.per_item(10.0, 4) == 2.5
    with pytest.raises(ValueError):
        check.per_item(10.0, 0)


def test_percentiles_and_spread():
    assert check.percentile(list(range(101)), 95) == 95.0
    assert check.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx((4.5 - 1.5) / 3.0)


def test_gaps():
    ref = np.array([5.0, 4.0, 3.0, 2.0, 1.0])
    assert check.score_gap(ref * (1 + 1e-6), ref) == pytest.approx(1e-6)
    assert check.rank_gap([0, 1], ref, 2) == 0.0
    assert check.rank_gap([0, 2], ref, 2) == pytest.approx(0.2)
    assert check.rank_gap([0, 0], ref, 2) == math.inf
    assert check.rank_gap([4, 3], ref, 2, largest=False) == 0.0
    gap, parts = check.answer_gap(ref * (1 + 1e-6), [0, 2], ref, 2)
    assert gap == pytest.approx(0.2) and parts["score_gap"] == pytest.approx(1e-6)
    gap, parts = check.query_gap([0, 1], ref[[0, 1]], ref, 2, largest=True)
    assert gap == 0.0 and parts == {"score_gap": 0.0, "rank_gap": 0.0}
    assert check.query_gap([0, 9], ref[[0, 1]], ref, 2, largest=True)[0] == math.inf
    ok, checks = check.judge({"a": 1e-7, "b": 0.0}, {"a": 1e-6, "b": 0.0})
    assert ok and checks["a"] == {"value": 1e-7, "limit": 1e-6}
    assert not check.judge({"a": math.inf}, {"a": 1.0})[0]


def test_latency_counts_the_wait_a_stall_imposes(tmp_path, monkeypatch, capsys):
    """Latency runs from each query's due time: one stalled query delays the
    queries queued behind it, so the knee sweep's p95 rises far more than
    its median; timed from the start of service, it would not."""
    from bench import calibrate

    root = tiny_root(tmp_path)
    served = []

    def fake_serve(handle, q):
        served.append(q)
        time.sleep(0.3 if len(served) == 20 else 0.002)
        return type("R", (), {"idx": np.arange(q.k), "val": np.zeros(q.k)})()

    monkeypatch.setattr(harness, "serve", fake_serve)
    calibrate.knee(resolve_cell("climate-360x720.read", root), [40.0], 1.0, SEED)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["queries"] == 40 and line["failed"] == 0
    assert line["p50_ms"] < 60.0 and line["p95_ms"] > 100.0
    assert line["service_p50_ms"] < 60.0


def test_read_throughput_counts_answers_within_the_window(tmp_path, monkeypatch):
    """Offered above capacity, the read cell reports the answers completed
    within the window per second of it; the queries still queued at its
    close are answered after it and checked, but not counted."""
    root = tiny_root(tmp_path)
    path = root / "bench/traffic/query-mix.json"
    path.write_text(json.dumps({**QUERIES, "rate_per_s": 40.0}))
    served = []
    orig = harness.serve

    def slow_serve(handle, q):
        served.append(q)
        time.sleep(0.05)  # capacity 20 queries/s, half the offered rate
        return orig(handle, q)

    monkeypatch.setattr(harness, "serve", slow_serve)
    out = harness.run("climate-360x720.read", SEED, 1.0, False, root=root, require_chip=False)
    assert out["attempted"] == 40 and out["failed"] == 0
    assert len(served) == 2 + 40  # one warm-up of each kind, then every query due
    assert out["correct"], out["checks"]
    rate = out["metrics"]["queries_per_s"]["value"]
    assert 10.0 <= rate <= 20.0, rate


# ---------------------------------------------------------------------------
# a cell, a configuration, a traffic mix and a metric added by files alone
# ---------------------------------------------------------------------------


def test_a_dummy_cell_and_metric_are_added_by_files(tmp_path):
    root = tiny_root(tmp_path)
    cfg = json.loads((root / "bench/configs/climate-128x128.json").read_text())
    cfg.update(n_lat=8, n_lon=16, n=128)
    (root / "bench/configs/dummy-grid.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/dummy-mix.json").write_text(json.dumps({**CLIMATE, "drift": 0.3}))
    (root / "bench/metrics/dummy_transitions.py").write_text(
        "def read(rec):\n    return float(rec.count)\n"
    )
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "dummy-grid", "source": "test", "file": "bench/configs/dummy-grid.json",
                           "reduced": []})
    doc["workloads"].append({"name": "dummy-grid.write", "config": "dummy-grid", "traffic": "dummy-mix",
                             "chips": 1, "why": "test"})
    next(m for m in doc["end_to_end"] if m["name"] == "transition_s")["workloads"].append("dummy-grid.write")
    doc["per_layer"].append({"name": "dummy_transitions", "unit": "count", "better": "higher",
                             "source": "host_clock", "layer": "test", "moves": "transition_s",
                             "workloads": ["dummy-grid.write"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    out = harness.run("dummy-grid.write", SEED, 0.5, True, root=root, require_chip=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["dummy_transitions"]["value"] >= 1
    assert set(out["metrics"]) == {"dummy_transitions"}
    assert list(out)[-1] == "checks"
    out = harness.run("dummy-grid.write", SEED, 0.5, False, root=root, require_chip=False)
    assert set(out["metrics"]) == {"setup_s", "transition_s"}
    assert out["device"]["platform"] == "cpu"


def test_no_chip_is_an_error():
    with pytest.raises(harness.NoChip):
        harness.run("climate-128x128.write", SEED, 1.0, False)

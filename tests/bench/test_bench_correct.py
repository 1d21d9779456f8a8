"""``correct`` on the CPU: sound runs pass; the control and every fault a
cell can have fail.

The harness runs as on the chip, its look for a chip skipped, at a size the
CPU holds.  Each fault breaks the timed path underneath, where the answer is
produced; the comparison with the reference must then read ``correct``
false.  The control is the reference computed with three-pass bfloat16
products (what ``Precision.HIGH`` makes) in the program's place: its
numbers must exceed the configuration's limits.
"""

import numpy as np
import pytest

from bench import check, faults, harness, reference
from bench import traffic as tf
from bench.spec import resolve_cell
from bench_tiny import tiny_root

SEED = 2**32 + 99
WRITE = ["climate-128x128.write", "synth-gmm-22528.write-2x2"]
READ = ["climate-360x720.read"]


@pytest.fixture
def root(tmp_path):
    return tiny_root(tmp_path)


@pytest.fixture
def fresh_programs():
    """Programs built under a fault must not serve later runs, nor may
    programs built before it hide the fault."""
    from repro.core.tiles import clear_program_cache

    clear_program_cache()
    yield
    clear_program_cache()


def run(root, cell, seconds=0.3):
    return harness.run(cell, SEED, seconds, False, root=root, require_chip=False)


@pytest.mark.parametrize("cell", WRITE + READ)
def test_sound_run_is_correct(root, cell):
    out = run(root, cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


# ---------------------------------------------------------------------------
# faults
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fault", sorted(faults.WRITE))
@pytest.mark.parametrize("cell", WRITE)
def test_write_fault_is_not_correct(root, cell, fault, monkeypatch, fresh_programs):
    faults.WRITE[fault](monkeypatch.setattr)
    out = run(root, cell)
    assert not out["correct"], out["checks"]


def test_exchange_left_out_is_not_correct(root, monkeypatch, fresh_programs):
    faults.exchange_left_out(monkeypatch.setattr)
    out = run(root, "synth-gmm-22528.write-2x2")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(faults.READ))
def test_read_fault_is_not_correct(root, fault, monkeypatch, fresh_programs):
    faults.READ[fault](monkeypatch.setattr)
    out = run(root, READ[0])
    assert not out["correct"], out["checks"]


def test_planted_fault_is_taken_out_again(root, fresh_programs):
    """The chip's fault readings plant a fault for one run only."""
    import repro.kernels.emb_query as eq

    orig = eq.panel_topk_update
    with faults.planted(faults.half_batch_read):
        assert eq.panel_topk_update is not orig
        assert not run(root, READ[0])["correct"]
    assert eq.panel_topk_update is orig
    assert run(root, READ[0])["correct"]


# ---------------------------------------------------------------------------
# the control
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**33 + 7])
@pytest.mark.parametrize("cell", WRITE)
def test_write_control_is_not_correct(root, cell, seed):
    """The reference at three passes, against the reference at six, at the
    tiny size: it fails the configuration's limit on the score gap."""
    import jax

    c = resolve_cell(cell, root)
    snaps = tf.snapshots(c.traffic, c.config, seed)
    mesh = harness.mesh_ctx(c.config, jax.devices())
    sharding = mesh.sharding(mesh.matrix_spec)
    t2 = harness.SETUP_SNAPSHOTS + 1
    ref = reference.transition_scores(snaps, t2, c.config, sharding)
    ctl = reference.transition_scores(snaps, t2, c.config, sharding, passes=3)
    top_k = int(c.config["top_k"])
    gap, _ = check.answer_gap(ctl, np.argsort(-ctl, kind="stable")[:top_k], ref, top_k)
    assert gap > c.config["limits"]["write"]["answer_gap"], gap


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**33 + 7])
def test_read_control_is_not_correct(root, seed):
    c = resolve_cell(READ[0], root)
    n = int(c.config["n"])
    k = reference.k_rp(n, float(c.config["eps_rp"]))
    z, vol, _ = tf.embedding_artifact(c.traffic, n, k, seed)
    gaps = []
    for q in tf.query_schedule(c.traffic, n, seed, 4.0):
        ref = reference.query_answer(z, vol, q.kind, q.node, q.k)
        ctl = reference.query_answer(z, vol, q.kind, q.node, q.k, passes=3)
        largest = q.kind == "top_anomalies"
        order = np.argsort(-ctl if largest else ctl, kind="stable")[: q.k]
        gaps.append(check.query_gap(order, ctl[order], ref, q.k, largest=largest)[0])
    assert max(gaps) > c.config["limits"]["read"]["query_gap"], max(gaps)

#!/usr/bin/env python3
"""Smoke run of CADDeLaG's main paths on TPU chips.

    python chip_smoke.py               # one chip: phases a-d
    python chip_smoke.py --four-chips  # four chips: phase a, 2x2 mesh vs 1x1

Phases on one chip:

  a. Write path, resident: a 128x128 gridded-climate sequence (n=16384, T=3,
     d=6, q=10, eps_RP=1e-3, so k_RP=17) through ``SequenceDetector``,
     publishing every snapshot's embedding to an ``EmbeddingStore``.
  b. Read path: ``caddelag-query`` top-20 and ``--neighbors`` over phase a's
     artifact, checked against the top-k computed from the in-memory Z.
  c. Out-of-core kernel path: ``caddelag-run`` on a GMM sequence (n=8192,
     T=2) with ``--oocore-chain --use-gemm-kernel --tile-codec bf16`` and a
     host-RAM scratch, checked against the resident run of the same sequence.
  d. Reference: the phase-a pipeline at n=2048 on the TPU against the same
     pipeline on this process's CPU device, and an embedding against the
     exact eigendecomposition oracle.

``--four-chips`` runs phase a alone on a 2x2 ``data`` x ``model`` mesh under
the ``cannon`` and ``summa`` schedules and compares each with the same run on
a 1x1 mesh over the first device.

Each phase prints its facts on lines of their own and a PASS or FAIL gate.
Seconds are from one smoke run each, not a benchmark.  The last line of
standard output is one JSON object naming the device, printed only when
every phase passed; the script exits non-zero when JAX finds no TPU or any
phase fails.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

# A TPU that fails to initialise must be an error, never a silent CPU run.
os.environ.setdefault("JAX_PLATFORMS", "tpu,cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import argparse  # noqa: E402
import contextlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

# Phase sizes.  a: the paper's climate setting (eps_RP, d, q) on a 128x128
# grid; c: out-of-core at half that n; d: small enough for the CPU reference
# and the O(n^3) float64 oracle.
CLIMATE_GRID = (128, 128)
T_STEPS = 3
D, Q, EPS = 6, 10, 1e-3
TOP_K = 20
OOCORE_N, OOCORE_T = 8192, 2
REF_GRID = (32, 64)

# Gates.
# Phase a's top-20 inside the climate event region, per transition, as a
# share of min(20, region size): at least what the same generator and
# settings give on the CPU at small n (1.0 at 16x16 and 64x64, 17/20 and
# 18/20 at 32x32).
EVENT_OVERLAP_MIN = 0.85
# TPU vs CPU (phase d) and 2x2 vs 1x1 (--four-chips): scores agree to
# anomaly-ranking grade -- max |difference| within this share of the top
# score -- and the top-20 is the reference's top-20 up to ties at that grain.
SCORE_RTOL = 1e-2
# Out-of-core bf16 scratch vs resident (phase c): the bf16-scratch contract
# of README.md, anomaly-ranking grade -- each of the d=6 levels rounds its
# working matrix once to bf16 (2^-8), so 6 * 2^-8 = 2.3e-2.
BF16_SCRATCH_RTOL = 2.3e-2
# Read path (phase b): the kernel's scores vs float64 numpy on the same Z.
QUERY_RTOL = 1e-3
# Embedding vs the exact oracle (phase d), as tests/test_core_math.py.
ORACLE_MEDIAN_REL_MAX = 0.25


class GateFailed(Exception):
    pass


def gate(cond: bool, what: str) -> None:
    if not cond:
        raise GateFailed(what)


def say(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", flush=True)


def device_memory(tag: str, device) -> None:
    stats = device.memory_stats() or {}
    say(tag, f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')} "
             f"(process so far, {device.device_kind} {device.id})")


def same_topk(ids, ref_scores, k: int, rtol: float, *, largest: bool = True) -> bool:
    """``ids`` are a top-k of ``ref_scores`` up to near-ties: the reference
    scores at ``ids``, sorted, match the reference's own top-k values within
    ``rtol`` of the best score."""
    ref = np.asarray(ref_scores, np.float64)
    sign = 1.0 if largest else -1.0
    want = np.sort(sign * ref)[::-1][:k]
    got = np.sort(sign * ref[np.asarray(ids)])[::-1]
    return len(set(np.asarray(ids).tolist())) == k and bool(
        np.all(np.abs(got - want) <= rtol * np.abs(want[0]))
    )


def score_gap(a, b) -> float:
    """max |a - b| as a share of max |b|."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def solve_facts(reports) -> tuple[str, bool]:
    reps = [r for r in reports if r is not None]
    its = "+".join(str(r.iterations) for r in reps)
    res = max(r.residual for r in reps)
    ok = all(r.converged and np.isfinite(r.residual) for r in reps)
    return f"solver iterations {its}, max residual {res!r}, converged {ok}", ok


# ---------------------------------------------------------------------------
# phase a: write path, resident
# ---------------------------------------------------------------------------


class Published:
    """The detector's embedding store, recording the last in-memory (Z, vol)
    each push hands to it (publishing also waits for the push's device work)."""

    def __init__(self, store):
        self.store = store
        self.z = self.vol = None

    def put_embedding(self, emb_id, z, vol, deg):
        self.z, self.vol = np.asarray(z), float(vol)
        return self.store.put_embedding(emb_id, z, vol, deg)


def run_climate(ctx, grid, t_steps, *, store_dir=None, schedule="cannon", tag="a"):
    """The climate sequence through SequenceDetector, publishing to an
    EmbeddingStore at ``store_dir`` (host RAM when None); returns
    per-transition scores and top-k, the truth, the last (Z, vol), facts."""
    from repro.core import CommuteConfig, SequenceDetector
    from repro.graphs import climate_snapshot_sequence
    from repro.store import EmbeddingStore

    lat, lon = grid
    n = lat * lon
    cfg = CommuteConfig(eps_rp=EPS, d=D, q=Q, schedule=schedule)
    k = cfg.k_rp(n)
    say(tag, f"n={n} ({lat}x{lon} grid) T={t_steps} d={D} q={Q} eps_RP={EPS} "
             f"k_RP={k} schedule={schedule} mesh={dict(ctx.mesh.shape)}")
    store = Published(EmbeddingStore.create(
        store_dir, n=n, k=k, seed=cfg.seed, meta={"dataset": "climate", "n": n}
    ))
    seq = climate_snapshot_sequence(ctx, lat, lon, t_steps, sigma=1.0)
    det = SequenceDetector(ctx, cfg, top_k=TOP_K, emb_store=store)
    out = {"scores": [], "top_idx": [], "solve_ok": True, "truth": seq.truth}
    for t, a in enumerate(seq.snapshots()):
        t0 = time.perf_counter()
        res = det.push(a)
        dt = time.perf_counter() - t0
        if res is None:
            say(tag, f"push 0 (embedding only, compiles included): {dt!r} s "
                     "-- one smoke run, not a benchmark")
            continue
        kind = "first transition, compiles included" if t == 1 else "steady transition"
        facts, ok = solve_facts(res.solve_reports)
        out["solve_ok"] &= ok
        say(tag, f"transition {t - 1}->{t} ({kind}): {dt!r} s -- one smoke run, "
                 f"not a benchmark; {facts}")
        out["scores"].append(np.asarray(res.scores))
        out["top_idx"].append(np.asarray(res.top_idx))
    det.finalize()
    out["z"], out["vol"] = store.z, store.vol
    return out


def phase_a(ctx, store_dir):
    out = run_climate(ctx, CLIMATE_GRID, T_STEPS, store_dir=store_dir)
    gate(out["solve_ok"], "every solve converged with a finite residual")
    gate(all(np.all(np.isfinite(s)) for s in out["scores"]), "all scores finite")
    for t, (ids, truth) in enumerate(zip(out["top_idx"], out["truth"])):
        event = set(np.asarray(truth).tolist())
        inside = len(event & set(ids.tolist()))
        share = inside / min(TOP_K, len(event))
        say("a", f"transition {t}->{t + 1}: top-{TOP_K} truth overlap {inside} "
                 f"(event region {len(event)} nodes, share {share!r}, "
                 f"gate >= {EVENT_OVERLAP_MIN})")
        gate(share >= EVENT_OVERLAP_MIN, "top-20 truth overlap")
    return out


# ---------------------------------------------------------------------------
# phase b: read path
# ---------------------------------------------------------------------------


def run_query_cli(argv) -> list[tuple[int, float]]:
    """``caddelag-query`` in process; returns its printed (node, score) rows."""
    from repro.core.query import main as query_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = query_main(argv)
    text = buf.getvalue()
    for line in text.splitlines():
        say("b", line)
    gate(rc == 0, f"caddelag-query {' '.join(argv)} exited {rc}")
    return [(int(m.group(1)), float(m.group(2)))
            for m in re.finditer(r"node (\d+)\s+score (\S+)", text)]


def phase_b(store_dir, a_out):
    z = a_out["z"].astype(np.float64)
    vol = a_out["vol"]
    n = z.shape[0]
    zbar = z.astype(np.float32).mean(axis=0, dtype=np.float64).astype(np.float32)
    ref = vol * ((z - zbar.astype(np.float64)) ** 2).sum(axis=1)
    t0 = time.perf_counter()
    rows = run_query_cli(["--store", store_dir, "--top-k", str(TOP_K)])
    say("b", f"top-{TOP_K} query: {time.perf_counter() - t0!r} s (compile included) "
             "-- one smoke run, not a benchmark")
    ids = np.array([i for i, _ in rows])
    vals = np.array([v for _, v in rows])
    gate(len(ids) == TOP_K, f"{TOP_K} rows printed")
    gap = score_gap(vals, ref[ids])
    say("b", f"top-{TOP_K} vs the in-memory Z: score gap {gap!r} (gate <= {QUERY_RTOL})")
    gate(same_topk(ids, ref, TOP_K, QUERY_RTOL), "top-20 equals the in-memory top-20")
    gate(gap <= QUERY_RTOL, "top-20 scores match")
    for node in (int(a_out["top_idx"][-1][0]), 0, n // 2):
        t0 = time.perf_counter()
        rows = run_query_cli(["--store", store_dir, "--top-k", str(TOP_K),
                              "--neighbors", str(node)])
        dt = time.perf_counter() - t0
        ids = np.array([i for i, _ in rows])
        vals = np.array([v for _, v in rows])
        d = vol * ((z - z[node]) ** 2).sum(axis=1)
        d[node] = np.inf
        gate(len(ids) == TOP_K and node not in ids, f"neighbors of {node}: self excluded")
        gap = score_gap(vals, d[ids])
        say("b", f"--neighbors {node}: {dt!r} s; vs the in-memory Z: score gap {gap!r} "
                 f"(gate <= {QUERY_RTOL})")
        gate(same_topk(ids, d, TOP_K, QUERY_RTOL, largest=False),
             f"neighbors of {node} equal the in-memory nearest {TOP_K}")
        gate(gap <= QUERY_RTOL, f"neighbors of {node}: scores match")


# ---------------------------------------------------------------------------
# phase c: out-of-core kernel path
# ---------------------------------------------------------------------------


def run_cli(args, report_path, tag="c"):
    from repro.launch.caddelag_run import main as run_main

    t0 = time.perf_counter()
    run_main(args + ["--run-report", report_path])
    say(tag, f"caddelag-run {' '.join(args)}: {time.perf_counter() - t0!r} s "
             "(compile included) -- one smoke run, not a benchmark")
    with open(report_path) as f:
        return json.load(f)


def phase_c(work):
    base = ["--dataset", "gmm", "--n", str(OOCORE_N), "--t-steps", str(OOCORE_T),
            "--d", str(D), "--q", str(Q), "--eps", str(EPS), "--top-k", str(TOP_K)]
    oo = run_cli(base + ["--oocore-chain", "--use-gemm-kernel", "--tile-codec", "bf16"],
                 os.path.join(work, "oocore.json"))
    res = run_cli(base, os.path.join(work, "resident.json"))
    for name, doc in (("oocore", oo), ("resident", res)):
        for tr in doc["transitions"]:
            for s in tr["solves"]:
                say("c", f"{name} transition {tr['index']}: solver iterations "
                         f"{s['iterations']}, residual {s['residual']!r}, "
                         f"converged {s['converged']}, streamed {s['streamed']}")
                gate(s["converged"] and np.isfinite(s["residual"]),
                     f"{name} solve converged")
    gate(all(s["streamed"] for tr in oo["transitions"] for s in tr["solves"]),
         "the out-of-core solves streamed")
    tot = oo["totals"]["bytes"]
    say("c", f"oocore H2D {tot['bytes_h2d']} bytes, saved by on-device bf16 decode "
             f"{tot['bytes_h2d_saved']} bytes, peak panel residency "
             f"{oo['totals']['peak_live_bytes']} bytes")
    gate(tot["bytes_h2d_saved"] > 0, "bf16 panels decoded on device")
    for t_oo, t_res in zip(oo["transitions"], res["transitions"]):
        gap = score_gap(t_oo["top_val"], t_res["top_val"])
        same = set(t_oo["top_idx"]) == set(t_res["top_idx"])
        say("c", f"transition {t_oo['index']}: top-{TOP_K} same set {same}, "
                 f"top-{TOP_K} score gap {gap!r} (gate <= {BF16_SCRATCH_RTOL})")
        gate(same, "same top-20 as the resident run")
        gate(gap <= BF16_SCRATCH_RTOL, "scores within the bf16-scratch contract")


# ---------------------------------------------------------------------------
# phase d: reference
# ---------------------------------------------------------------------------


def one_device_ctx(device):
    from jax.sharding import Mesh

    from repro.core import make_context

    return make_context(Mesh(np.array([device]).reshape(1, 1), ("data", "model")))


def oracle_error(ctx, grid) -> float:
    """Median relative error of commute distances from the embedding vs the
    exact eigendecomposition, on snapshot 0 (settings of the oracle test)."""
    import jax.numpy as jnp

    from repro.core import CommuteConfig
    from repro.core.embedding import (
        commute_distance_block,
        commute_time_embedding,
        exact_commute_distances,
    )
    from repro.graphs import climate_snapshot_sequence

    a = next(climate_snapshot_sequence(ctx, *grid, 2, sigma=1.0).snapshots())
    cfg = CommuteConfig(eps_rp=EPS, d=8, q=12, schedule="xla", k_override=64)
    emb = commute_time_embedding(ctx, a, cfg)
    n = a.shape[0]
    idx = jnp.arange(n)
    approx = np.asarray(commute_distance_block(emb, idx, idx), np.float64)
    exact = np.asarray(exact_commute_distances(np.asarray(a)), np.float64)
    mask = ~np.eye(n, dtype=bool)
    return float(np.median(np.abs(approx - exact)[mask] / np.maximum(exact[mask], 1e-9)))


def phase_d():
    tpu = jax.devices()[0]
    cpu = jax.devices("cpu")[0]
    runs = {}
    for role, dev in (("tpu", tpu), ("cpu", cpu)):
        with jax.default_device(dev):
            runs[role] = run_climate(one_device_ctx(dev), REF_GRID, 2, tag=f"d/{role}")
    gate(runs["tpu"]["solve_ok"] and runs["cpu"]["solve_ok"], "solves converged")
    s_tpu, s_cpu = runs["tpu"]["scores"][0], runs["cpu"]["scores"][0]
    gap = score_gap(s_tpu, s_cpu)
    top_ok = same_topk(runs["tpu"]["top_idx"][0], s_cpu, TOP_K, SCORE_RTOL)
    say("d", f"TPU vs CPU scores: gap {gap!r} (gate <= {SCORE_RTOL}), "
             f"top-{TOP_K} matches {top_ok}")
    for role, dev in (("tpu", tpu), ("cpu", cpu)):
        with jax.default_device(dev):
            err = oracle_error(one_device_ctx(dev), REF_GRID)
        say("d", f"{role}: median relative error vs exact commute distances "
                 f"{err!r} (gate < {ORACLE_MEDIAN_REL_MAX})")
        gate(err < ORACLE_MEDIAN_REL_MAX, f"{role} embedding vs oracle")
    gate(gap <= SCORE_RTOL, "TPU scores agree with the CPU")
    gate(top_ok, "TPU top-20 agrees with the CPU")


# ---------------------------------------------------------------------------
# four chips: phase a on a 2x2 mesh vs 1x1
# ---------------------------------------------------------------------------


def four_chips():
    from repro.core import make_context
    from repro.launch.mesh import make_mesh

    gate(len(jax.devices()) == 4, f"four devices, found {len(jax.devices())}")
    ref = run_climate(make_context(make_mesh(1, 1)), CLIMATE_GRID, T_STEPS, tag="4/1x1")
    gate(ref["solve_ok"], "1x1 solves converged")
    for schedule in ("cannon", "summa"):
        got = run_climate(make_context(make_mesh(2, 2)), CLIMATE_GRID, T_STEPS,
                          schedule=schedule, tag=f"4/2x2-{schedule}")
        gate(got["solve_ok"], f"2x2 {schedule} solves converged")
        for t, (s, r, ids) in enumerate(zip(got["scores"], ref["scores"], got["top_idx"])):
            gap = score_gap(s, r)
            top_ok = same_topk(ids, r, TOP_K, SCORE_RTOL)
            say("4", f"{schedule} transition {t}->{t + 1}: 2x2 vs 1x1 score gap "
                     f"{gap!r} (gate <= {SCORE_RTOL}), top-{TOP_K} matches {top_ok}")
            gate(gap <= SCORE_RTOL and top_ok, f"2x2 {schedule} agrees with 1x1")


# ---------------------------------------------------------------------------


def run_phase(tag, fn, *args) -> bool:
    t0 = time.perf_counter()
    try:
        fn(*args)
    except Exception:  # report every phase, then fail the run
        traceback.print_exc()
        say(tag, f"FAIL after {time.perf_counter() - t0!r} s")
        return False
    for dev in jax.devices():
        device_memory(tag, dev)
    say(tag, f"PASS in {time.perf_counter() - t0!r} s")
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="phase a on a 2x2 mesh (cannon, summa) vs a 1x1 mesh")
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is {jax.default_backend()!r})",
              file=sys.stderr)
        return 2
    from repro.core import make_context
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh

    enable_compile_cache()
    dev = jax.devices()[0]
    say("smoke", f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
                 f"jax {jax.__version__}")
    if args.four_chips:
        ok = run_phase("4", four_chips)
    else:
        ctx = make_context(make_mesh(1, 1))
        with tempfile.TemporaryDirectory(prefix="caddelag-smoke-") as work:
            store_dir = os.path.join(work, "emb")
            a_out = {}
            ok_a = run_phase("a", lambda: a_out.update(phase_a(ctx, store_dir)))
            ok_b = ok_a and run_phase("b", phase_b, store_dir, a_out)
            if not ok_a:
                say("b", "FAIL: skipped, phase a produced no artifact")
            a_out.clear()
            ok_c = run_phase("c", phase_c, work)
        ok_d = run_phase("d", phase_d)
        ok = ok_a and ok_b and ok_c and ok_d
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

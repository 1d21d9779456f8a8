"""The trace reduction of the benchmark: interval arithmetic on hand-built
events with known overlaps, and loading a trace recorded here on the CPU."""

import re

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event


def ev(name, start, end):
    return Event(name, start, end)


# Two ops overlap on [20, 30); a collective runs [50, 80), half of it under
# compute ([50, 65) by "dot.2"); the window is [0, 100).
OPS = [
    ev("fusion.1", 10, 30),
    ev("dot.2", 20, 65),
    ev("collective-permute-done.3", 50, 80),
    ev("copy.4", 90, 95),
]


def test_union_merges_overlaps_and_clips():
    assert tr.union([(10, 30), (20, 65), (50, 80), (90, 95)]) == [(10, 80), (90, 95)]
    assert tr.union([(10, 30), (90, 120)], 15, 100) == [(15, 30), (90, 100)]
    assert tr.union([(5, 5), (7, 6)]) == []


def test_busy_and_idle_share():
    assert tr.busy_ns(OPS, 0, 100) == 75
    assert tr.idle_share(OPS, 0, 100) == pytest.approx(0.25)
    assert tr.busy_ns(OPS, 60, 92) == 22  # [60, 80) and [90, 92)


def test_op_time_sums_durations_within_the_window():
    assert tr.op_time_ns(OPS, lambda e: True) == 20 + 45 + 30 + 5
    assert tr.op_time_ns(OPS, tr.is_matmul, 0, 100) == 45
    assert tr.op_time_ns(OPS, tr.is_matmul, 40, 100) == 25


def test_subtract_and_exposed_collective_time():
    assert tr.subtract([(0, 100)], [(10, 20), (30, 40)]) == [(0, 10), (20, 30), (40, 100)]
    assert tr.subtract([(0, 10), (20, 30)], [(5, 25)]) == [(0, 5), (25, 30)]
    assert tr.exposed_collective_ns(OPS, 0, 100) == 15  # [65, 80)
    assert tr.exposed_collective_ns([e for e in OPS if not tr.is_collective(e)], 0, 100) == 0


def test_top_ops_and_idle_attribution():
    top = tr.top_ops(OPS + [ev("dot.2", 96, 99)], 0, 100, n=2)
    assert top == [["dot.2", pytest.approx(48e-9)], ["collective-permute-done.3", pytest.approx(30e-9)]]
    spans = [ev("bench.window", 0, 100), ev("bench.push", 0, 85), ev("bench.wait", 80, 85)]
    idle = dict((name, s) for name, s in tr.idle_by_span(OPS, spans, 0, 100))
    # gaps: [0, 10) and [80, 90) and [95, 100); [80, 85) is inside the wait
    assert idle == pytest.approx({"bench.push": 10e-9, "bench.wait": 5e-9, "(none)": 10e-9})


GEMM = ("%fusion = f32[64,64]{1,0:T(8,128)} fusion(f32[64,64]{1,0:T(8,128)} "
        "%collective-permute-done, f32[64,64]{1,0:T(8,128)} %y), kind=kOutput, calls=%f")
PERMUTE = ("%collective-permute-start.1 = (f32[64,64]{1,0:T(8,128)}, u32[]{:S(2)}) "
           "collective-permute-start(f32[64,64]{1,0:T(8,128)} %b), channel_id=1")
KERNEL = ('%branch_0_fun.1 = (f32[1,20]{1,0:T(1,128)}) custom-call(f32[1,17]{1,0} %q), '
          'custom_call_target="tpu_custom_call"')


def test_categories_follow_the_hlo_opcode():
    assert tr.is_matmul(ev(GEMM, 0, 1)) and not tr.is_collective(ev(GEMM, 0, 1))
    assert tr.is_collective(ev(PERMUTE, 0, 1)) and not tr.is_matmul(ev(PERMUTE, 0, 1))
    assert tr.is_emb_query(ev(KERNEL, 0, 1)) and not tr.is_matmul(ev(KERNEL, 0, 1))
    assert tr.is_matmul(ev("dot_general.3", 0, 1))
    assert not tr.is_matmul(ev("%add.1 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)", 0, 1))
    assert tr.is_collective(ev("all-reduce.1", 0, 1))
    assert tr.short_name(GEMM) == "fusion.kOutput f32[64,64]{1,0:T(8,128)}"
    assert tr.short_name(PERMUTE).startswith("collective-permute-start (f32[64,64]")


def test_loads_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.push"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()

    trace = tr.load(tr.find_xplane(str(tmp_path)), plane=re.compile(r"/host:CPU$"), line="tf_XLA.*")
    assert [s.name for s in trace.spans].count("bench.push") == 3
    lo, hi = trace.window()
    ops = [e for e in trace.devices[0] if not e.name.startswith(("ThreadpoolListener", "end:"))]
    assert ops, "the CPU backend's XLA ops run on its client threads"
    inside = [e for e in ops if lo <= e.start and e.end <= hi]
    assert inside, "ops run inside the annotated window, on the same clock"
    assert 0 < tr.busy_ns(inside, lo, hi) <= hi - lo
    assert 0.0 <= tr.idle_share(inside, lo, hi) < 1.0
    assert any(tr.is_matmul(e) for e in inside)
    # Every idle instant is put down to some span of the window.
    idle = tr.idle_by_span(inside, trace.spans, lo, hi)
    assert sum(s for _, s in idle) == pytest.approx((hi - lo - tr.busy_ns(inside, lo, hi)) / 1e9)

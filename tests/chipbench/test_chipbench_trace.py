"""The trace reduction: union-of-intervals and gap arithmetic on known
intervals, and on a trace this test records itself from the CPU
profiler with annotations at host-clock times it knows."""

import time

import pytest
from chipbench_tiny import ROOT  # noqa: F401

from chipbench import trace


def test_union_and_gaps_known_intervals():
    iv = [(10, 20), (15, 30), (40, 50), (50, 55), (60, 60), (70, 80)]
    u = trace.union(iv)
    assert u == [(10, 30), (40, 55), (70, 80)]
    assert trace.length(u) == 45
    assert trace.gaps(u, 0, 100) == [(0, 10), (30, 40), (55, 70), (80, 100)]
    assert trace.gaps(u, 12, 75) == [(30, 40), (55, 70)]
    assert trace.length(trace.clip(u, 12, 75)) == 18 + 15 + 5
    assert trace.sum_by_name([("a", 0, 10), ("a", 5, 20), ("b", 90, 120)],
                             0, 100) == {"a": 25, "b": 10}


def test_ops_by_program_sums_each_programs_custom_calls():
    """Custom calls go to the program whose event holds their start, are
    clipped to the window, and ops of another kind count nothing."""
    mods = [("jit__fedagg_fold_call", 0, 100), ("jit_gather_impl", 100, 150),
            ("jit__fedagg_fold_call", 200, 300)]
    ops = [("%custom-call.1 = f32[8] custom-call(...)", 10, 40),
           ("%fusion.2 = f32[8] fusion(...)", 40, 90),
           ("%custom-call.3 = f32[8] custom-call(...)", 210, 260),
           ("%custom-call.4 = f32[8] custom-call(...)", 270, 320)]
    assert trace.ops_by_program(ops, mods, "custom-call", 0, 1000) == {
        "jit__fedagg_fold_call": 30 + 50 + 30}
    assert trace.ops_by_program(ops, mods, "custom-call", 20, 250) == {
        "jit__fedagg_fold_call": 20 + 40}
    assert trace.ops_by_program(ops[1:2], mods, "custom-call", 0, 1000) == {}


def test_label_gaps_by_innermost_open_span():
    spans = [("round", 0, 100), ("select", 10, 30), ("eval", 60, 90)]
    got = trace.label_gaps([(12, 20), (40, 55), (65, 95)], spans, top=3)
    assert got == [["eval", 30e-9], ["round", 15e-9], ["select", 8e-9]]


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    t_mark = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.clock"):
        pass
    known = []
    for i, nap in enumerate((0.02, 0.01, 0.03)):
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"work.{i}"):
            time.sleep(nap)
            f(x).block_until_ready()
        known.append((a, time.perf_counter()))
        time.sleep(0.015)
    jax.profiler.stop_trace()
    pd = trace.load(trace.find_xplane(str(tmp_path)))
    mark, _ = trace.find_host_event(pd, "chipbench.clock")
    spans = [trace.find_host_event(pd, f"work.{i}") for i in range(3)]
    to_ns = lambda t: mark + (t - t_mark) * 1e9
    for (s, e), (a, b) in zip(spans, known):
        assert abs(s - to_ns(a)) < 2e6 and abs(e - to_ns(b)) < 2e6
    t0, t1 = spans[0][0], spans[-1][1]
    u = trace.union(spans)
    g = trace.gaps(u, t0, t1)
    assert len(g) == 2
    assert trace.length(u) + trace.length(g) == t1 - t0
    for gap in g:
        assert 0.012e9 < gap[1] - gap[0] < 0.05e9
    assert trace.device_planes(pd, prefix="/device:TPU:") == []
    with pytest.raises(KeyError):
        trace.find_host_event(pd, "no.such.event")

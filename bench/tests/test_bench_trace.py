"""The trace reduction: on events made by hand, and on a trace recorded
on the CPU during the test."""
import time

import jax
import pytest
import jax.numpy as jnp

from bench import trace
from bench.trace import Event


def ev(name, start, dur):
    return Event(name, float(start), float(dur))


def test_union_gaps_and_names():
    evs = [ev("a", 0, 10), ev("b", 5, 10), ev("a", 30, 5), ev("c", 50, 0)]
    assert trace.intervals(evs) == [[0, 15], [30, 35], [50, 50]]
    assert trace.busy_s(evs) == pytest.approx(20e-9)
    assert trace.gaps(evs) == [(15, 30), (35, 50)]
    assert trace.seconds_by_name(evs) == pytest.approx(
        {"a": 15e-9, "b": 10e-9, "c": 0.0})
    assert trace.top({"x": 1.0, "y": 3.0, "z": 2.0}, 2) == [["y", 3.0], ["z", 2.0]]


def test_gaps_take_the_host_span_that_overlaps_most():
    spans = [ev("feed", 10, 12), ev("sync", 22, 30)]
    got = trace.label_gaps([(15, 30), (60, 70)], spans)
    assert got == pytest.approx({"sync": 15e-9, "unattributed": 10e-9})


def test_short_names_of_tpu_ops():
    e = Event("%fused_stats.1 = (s32[8,1]) custom-call(bf16[8,9] %G.1)", 0, 1)
    assert e.short == "fused_stats.1"
    assert Event("dot_general.1", 0, 1).short == "dot_general.1"


def test_matching_reads_names_and_statistics():
    evs = [Event("custom-call.3", 0, 1, (("long_name", "_fused_stats_kernel"),)),
           Event("fusion.1", 1, 1)]
    assert trace.matching(evs, r"fused_stats") == [evs[0]]


def test_summary_of_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        t0 = time.perf_counter()
        for _ in range(5):
            with jax.profiler.TraceAnnotation("step"):
                y = f(x)
            with jax.profiler.TraceAnnotation("sync"):
                y.block_until_ready()
        window = time.perf_counter() - t0
    prof = trace.load(tmp_path)
    s = trace.summarize(prof, window, span_names=("step", "sync"),
                        plane_prefix=trace.HOST_PLANE, line_name=None)
    assert 0 < s.busy_s <= window
    assert s.window_s == window
    assert len(s.device_ops) <= 10 and all(v > 0 for _k, v in s.device_ops)
    dots = trace.matching(s.all_ops(), r"dot")
    assert len(dots) >= 5
    assert len(trace.host_spans(prof, ("step", "sync"))) == 10
    assert {k for k, _v in s.idle_gaps} <= {"step", "sync", "unattributed"}


def test_collectives_are_read_by_their_own_name():
    """The collectives' device time per step, averaged over the chips; an
    op that only takes a collective's result as its operand is not one."""
    from bench import common
    reader = common.layer_metric("collective_ms.train")
    chip0 = [Event("%psum.91 = f32[8]{0:T(128)} all-reduce(f32[8] %x), "
                   "replica_groups={{0,1,2,3}}", 0, 2e6),
             Event("%all-gather-start.1 = (f32[8]{0:T(8,128)}, f32[2]) "
                   "all-gather-start(f32[2] %y)", 3e6, 1e6),
             Event("%fusion.2 = f32[8] fusion(f32[8] %all-reduce.3)", 5e6, 5e6)]
    chip1 = [Event("%all-reduce.1 = (f32[8], f32[2]) all-reduce(f32[8] %x, "
                   "f32[2] %z)", 0, 4e6)]
    s = trace.Summary({"/device:TPU:0": chip0, "/device:TPU:1": chip1},
                      0.0, 1.0, [], [])
    assert reader.read({"window": {"steps": 2}, "trace": s}) == \
        pytest.approx((3.0 + 4.0) / 2 / 2)
    one_chip = trace.Summary({"/device:TPU:0": chip0[2:]}, 0.0, 1.0, [], [])
    assert reader.read({"window": {"steps": 2}, "trace": one_chip}) is None
    assert reader.read({"window": {"rounds": 2}, "trace": s}) is None

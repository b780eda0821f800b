"""The trace reducer on a profiler trace recorded on a v5e chip (two
small jitted programs run three times each, trimmed to the device plane
and the clock marker; ``data/tpu_trace.pbtxt``)."""
import os

import pytest
from jax.profiler import ProfileData

import _bench_path  # noqa: F401
from bench import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
# perf_counter_ns readings taken around the marker annotation on the chip
M0, M1 = 31814308280, 31814347490


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "tpu_trace.pbtxt")) as f:
        return ProfileData.from_text_proto(f.read())


def test_clock_offset_from_the_marker(recorded):
    off = xtrace.clock_offset_ns(recorded, M0, M1)
    # marker at 40155171 ns for 2229 ns of profile time
    assert off == (M0 + M1) / 2 - (40155171 + 40155171 + 2229) / 2


def test_busy_union_idle_gaps_and_program_time(recorded):
    off = xtrace.clock_offset_ns(recorded, M0, M1)
    dt = xtrace.device_trace(recorded, off)
    mods = dt.modules["/device:TPU:0"]
    assert [xtrace.program_name(m[0]) for m in mods] == ["jit__lambda"] * 6
    lo, hi = mods[0][1], mods[-1][2]
    # ops of each run, back to back with 1 ns between them, summed
    busy = (14 + 3344 + 13154) + 2746 + (13 + 3303 + 13211) + 2741 \
        + (13 + 3230 + 13152) + 2834
    assert xtrace.busy_ns(dt, lo, hi) == pytest.approx(busy)
    assert sum(m[2] - m[1] for m in mods) == pytest.approx(
        16518 + 2748 + 16534 + 2744 + 16402 + 2837)
    covered, gaps = xtrace.union([(a, b) for _, a, b in
                                  dt.ops["/device:TPU:0"]], lo, hi)
    assert covered == pytest.approx(busy)
    longest = max(gaps, key=lambda g: g[1] - g[0])
    # between the first run's fusion and the second program
    assert longest[1] - longest[0] == pytest.approx(
        43126921 - (39195674 + 13154))
    named = xtrace.name_gaps(gaps, [("host.wait", longest[0] - 5,
                                     longest[1] + 5)], n=2)
    assert named[0] == ["host.wait", pytest.approx(
        (longest[1] - longest[0]) / 1e9)]
    assert named[1][0] == "no span"
    top = xtrace.top_ops(dt, lo, hi, n=1)
    assert top[0][0] == "jit__lambda/%fusion = bf16[512,1024]"
    assert top[0][1] == pytest.approx((13154 + 13211 + 13152) / 1e9)


def test_loop_ops_are_not_counted_twice():
    dt = xtrace.DeviceTrace(
        ops={"d": [("%while.1 = (s32[])", 0, 100), ("%fusion.1 = f32[2]", 0,
                                                    40),
                   ("%fusion.2 = f32[2]", 50, 90)]},
        modules={"d": [("jit_prefill(7)", 0, 100)]})
    assert xtrace.busy_ns(dt, 0, 200) == 100
    assert dict(xtrace.top_ops(dt, 0, 200)) == {
        "jit_prefill/%fusion.2 = f32[2]": 40e-9,
        "jit_prefill/%fusion.1 = f32[2]": 40e-9}
    assert xtrace.union([(0, 40), (50, 90)], 0, 200) == \
        (80, [(40, 50), (90, 200)])

"""The trace reduction, on a small trace recorded on one TPU v5e and on
hand-made events."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import devtrace  # noqa: E402

RECORDED = os.path.join(HERE, "data", "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return devtrace.load(RECORDED)


def test_recorded_trace_reduces(recorded):
    assert list(recorded.modules) == ["/device:TPU:0"]
    red = devtrace.reduce(recorded)
    lo, hi = devtrace.window_of(recorded)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    # three runs of the jitted f and one argmax, all inside the window
    names = sorted(red["module_s"])
    assert len(names) == 2
    assert any(n.startswith("jit_f(") for n in names)
    assert any(n.startswith("jit__argmax(") for n in names)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] == pytest.approx(sum(red["module_s"].values()))
    ops = red["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10
    assert ops[0][0] == "%sort.6 sort"
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = red["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10
    idle = red["window_s"] - red["busy_s"]
    assert sum(t for _, t in gaps) <= idle + 1e-9
    # the longest gap is the compile of argmax, which ran inside the window
    assert gaps[0][0] == "TpuCompiler::Compile"


def test_union_clip_and_gap_names():
    ev = devtrace.TraceEvents(
        modules={"/device:TPU:0": [
            ("jit__batched_search(1)", 0, 40),       # starts before the window
            ("jit__batched_search(1)", 30, 60),      # overlaps the first
            ("jit_matmul(2)", 100, 110),
            ("jit_matmul(2)", 150, 400),             # ends after the window
        ]},
        ops={"/device:TPU:0": [("%while.1 = (s32[4]) while(%x)", 10, 60)]},
        host=[("bench.window", 20, 200),
              ("bench.wait_for_arrival", 60, 100),
              ("np.asarray(jax.Array)", 50, 160)],
    )
    red = devtrace.reduce(ev)
    assert red["window_s"] == pytest.approx(180e-9)
    # busy: [20, 60] + [100, 110] + [150, 200]
    assert red["busy_s"] == pytest.approx(100e-9)
    inside, outside = devtrace.module_seconds(red, "_batched_search")
    assert inside == pytest.approx(50e-9)     # 20 + 30 after clipping
    assert outside == pytest.approx(60e-9)
    assert red["breakdown"]["device_ops"] == [
        ["%while.1 while", pytest.approx(40e-9)]]
    gaps = red["breakdown"]["idle_gaps"]
    # [60, 100] is covered by the arrival wait (shorter than the asarray);
    # [110, 150] only by the asarray
    assert gaps == [["bench.wait_for_arrival", pytest.approx(40e-9)],
                    ["np.asarray(jax.Array)", pytest.approx(40e-9)]]


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        devtrace.reduce(devtrace.TraceEvents(
            modules={"/device:TPU:0": [("m", 0, 1)]}, host=[]))
    with pytest.raises(ValueError, match="no device"):
        devtrace.reduce(devtrace.TraceEvents(host=[("bench.window", 0, 9)]))

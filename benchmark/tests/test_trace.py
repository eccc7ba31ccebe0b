import os

import pytest

from benchmark.harness import readers, trace

SMALL = os.path.join(os.path.dirname(__file__), "data", "small_trace.xplane.pb")


def test_union_of_intervals_and_gaps():
    # 0-10 and 5-20 overlap, 30-40 stands alone; window 0-50 (ns)
    busy, gaps = trace.union_seconds([(0, 10), (5, 20), (30, 40)], 0, 50)
    assert busy == pytest.approx(30e-9)
    assert gaps == [(20, 30), (40, 50)]
    # clipped to the window, and a gap before the first operation
    busy, gaps = trace.union_seconds([(10, 20), (45, 70)], 5, 50)
    assert busy == pytest.approx(15e-9)
    assert gaps == [(5, 10), (20, 45)]
    assert trace.union_seconds([], 0, 10) == (0.0, [])


def test_reduce_on_made_up_events():
    raw = {"window": (0.0, 1000.0), "devices": [{
        "name": "/device:TPU:0",
        "ops": [("%fusion.1 = f32[8] fusion(...)", 100.0, 300.0),
                ('%k = bf16[8] custom-call(...), custom_call_target="tpu_custom_call"',
                 300.0, 400.0),
                ("%fusion.1 = f32[8] fusion(...)", 600.0, 800.0)],
        "modules": [("jit_step(123)", 100.0, 400.0), ("jit_step(123)", 600.0, 800.0)]}]}
    red = trace.reduce(raw)
    assert red["busy_s"] == pytest.approx(500e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert trace.module_durations(red, r"^jit_step\(") == pytest.approx([300e-9, 200e-9])
    assert trace.op_seconds(red, "tpu_custom_call") == pytest.approx(100e-9)
    assert red["device_ops"][0][0].startswith("%fusion.1")
    gaps = dict(red["idle_gaps"])
    assert gaps["after jit_step before jit_step"] == pytest.approx(200e-9)
    ctx = {"trace": red, "work": {"k": {"flops": 1.0, "bytes": 0.0}},
           "peaks": {"flops_per_s": 1e8, "bytes_per_s": 1.0}, "chips": 1}
    assert readers.trace_idle(ctx, {}) == pytest.approx(50.0)
    # 1 flop at 1e8 flop/s = 10 ns of 100 ns of kernel time
    assert readers.kernel_roofline(ctx, {"pattern": "tpu_custom_call", "work": "k"}) \
        == pytest.approx(10.0)
    assert readers.kernel_roofline(ctx, {"pattern": "no such kernel", "work": "k"}) is None
    assert readers.trace_module_ms(ctx, {"pattern": r"^jit_step\("}) \
        == pytest.approx(250e-6)


def test_the_recorded_trace_of_three_small_steps():
    """data/small_trace.xplane.pb: recorded on a TPU v5e by
    record_small_trace.py (PR 25): three calls of jit(small_step) with 20 ms
    host sleeps between them inside the window annotation."""
    raw = trace.load(SMALL)
    assert [d["name"] for d in raw["devices"]] == ["/device:TPU:0"]
    assert raw["window"] is not None
    red = trace.reduce(raw)
    assert len(raw["devices"][0]["modules"]) == 3
    # the device's clock runs some 1.2 ms ahead of the host's in this trace:
    # the first step reads as 1.16 ms BEFORE the annotation that its call is
    # inside of, so two of the three steps count. An edge effect of about a
    # millisecond, nothing beside a window of seconds.
    first = min(s for _, s, _ in raw["devices"][0]["modules"])
    assert -1.3e6 < first - raw["window"][0] < -1.0e6
    steps = trace.module_durations(red, r"^jit_small_step\(")
    assert len(steps) == 2 and all(20e-6 < d < 30e-6 for d in steps)
    # the device worked for some 48 us of a window of over 60 ms
    assert 40e-6 < red["busy_s"] < 55e-6
    assert 0.06 < red["window_s"] < 0.2
    assert red["busy_s"] <= sum(steps) + 1e-9
    idle = readers.trace_idle({"trace": red}, {})
    assert 99.8 < idle < 100.0
    assert red["device_ops"][0][0].startswith("%fusion")
    assert sum(v for _, v in red["idle_gaps"]) == \
        pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)

"""The trace reduction on hand-counted intervals (nanoseconds)."""
from pathlib import Path

import pytest

import trace_reduce as tr

# two chips; chip 0 runs a matmul, the decode kernel twice (overlapping the
# matmul by 5 ns) and an all-reduce half-hidden behind a fusion; chip 1
# runs one kernel call and an all-reduce that nothing hides
TRACE = {
    "devices": {
        "/device:TPU:0": [(10, 30, "dot.1"), (25, 40, "paged_fairkv_kernel"),
                          (60, 70, "paged_fairkv_kernel"),
                          (80, 100, "all-reduce.3"), (90, 95, "fusion.2")],
        "/device:TPU:1": [(0, 20, "paged_fairkv_kernel"),
                          (50, 60, "all-reduce.3")],
    },
    "host": [(0, 120, "bench.traced"), (40, 60, "bench.step"),
             (42, 58, "PjitFunction(fn)"), (100, 120, "bench.readout")],
}


def test_busy_kernel_and_exposed_collective_by_hand():
    red = tr.reduce(TRACE, 0, 120, {"paged_decode_roofline": "paged_fairkv"})
    # chip 0 busy: [10, 40] + [60, 70] + [80, 100] = 60; chip 1: 20 + 10 = 30
    assert red["busy_ns"] == pytest.approx(45.0)
    assert red["window_ns"] == 120
    # kernel: chip 0 15 + 10, chip 1 20 -> mean 22.5
    assert red["kernel_ns"]["paged_decode_roofline"] == pytest.approx(22.5)
    # all-reduce: chip 0 20 (15 exposed), chip 1 10 (10 exposed)
    assert red["collective_ns"] == pytest.approx(15.0)
    assert red["exposed_collective_ns"] == pytest.approx(12.5)
    assert red["op_ns"]["dot.1"] == pytest.approx(10.0)


def test_idle_gaps_go_to_the_innermost_host_event():
    red = tr.reduce(TRACE, 0, 120, {})
    gaps = red["idle_gaps"]
    # chip 0 gaps (midpoint): [0,10] (5) [40,60] (50) [70,80] (75)
    # [100,120] (110); chip 1: [20,50] (35) [60,120] (90); halved over
    # the two chips
    assert gaps["PjitFunction(fn)"] == pytest.approx(20 / 2)
    assert gaps["bench.traced"] == pytest.approx((10 + 10 + 30 + 60) / 2)
    assert gaps["bench.readout"] == pytest.approx(20 / 2)
    assert sum(gaps.values()) == pytest.approx(2 * 120 / 2 - 45)
    top = tr.top(gaps, 2)
    assert top[0][0] == "bench.traced" and top[0][1] == pytest.approx(55e-9)


def test_window_clips_operations():
    red = tr.reduce(TRACE, 15, 35, {"k": "paged_fairkv"})
    # chip 0: [15, 35] fully busy; chip 1: [15, 20]
    assert red["busy_ns"] == pytest.approx((20 + 5) / 2)
    assert red["kernel_ns"]["k"] == pytest.approx((10 + 5) / 2)


def test_a_trace_without_a_tpu_plane_is_refused():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "host": []}, 0, 1, {})


# a trace recorded on one TPU v5e by ``bench/testdata/record_trace.py``:
# inside the ``bench.traced`` span, two rounds of a 2048² bf16 matmul and
# the program's paged decode kernel (a ``tpu_custom_call``) reach the
# device.  The numbers below were added up by hand from the event list the
# recorder printed (ns): each round's matmul group covers 13 + 11607 +
# 90841 (then 13 + 11463 + 90841) and its kernel group, adjacent events
# merged, 41859 (then 41841), of which the kernel itself is 37106 (37102).
RECORDED = Path(__file__).resolve().parents[1] / "testdata" / "v5e_small.xplane.pb"


def test_recorded_chip_trace_by_hand():
    trace = tr.load(RECORDED)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    w0, w1 = tr.window_of(trace, "bench.traced")
    assert (w0, w1) == (42678645.0, 56350533.0)
    red = tr.reduce(trace, w0, w1, {"kernel": "tpu_custom_call"})
    assert red["window_ns"] == 13671888.0
    assert red["busy_ns"] == 288478.0
    assert red["kernel_ns"]["kernel"] == 37106.0 + 37102.0
    assert red["collective_ns"] == 0.0
    assert sum(red["idle_gaps"].values()) == pytest.approx(13671888 - 288478)


# HeadKV's SnapKV scoring kernel as a v5e compile names it (operand list
# from ``snapkv_scores_pallas`` at B 1, W 32, 64/8 heads, Dh 128, T 2048)
SNAPKV = ('%snapkv_scores_pallas.1 = f32[1,8,1,2048]{3,2,1,0:T(1,128)S(1)} '
          'custom-call(s32[1,32]{1,0} %copy-done.2, bf16[8,256,128]{2,1,0} '
          '%copy_bitcast_fusion.1, bf16[1,8,2048,128]{3,2,1,0} '
          '%copy_bitcast_fusion, s32[1,1,2048]{2,1,0} %bitcast.12), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          '{s32[1,32]{1,0}, bf16[8,256,128]{2,1,0}, bf16[1,8,2048,128]'
          '{3,2,1,0}, s32[1,1,2048]{2,1,0}}, frontend_attributes='
          '{kernel_metadata={}}')


def test_roofline_reader_finds_the_paged_kernel():
    import run
    reader = run._reader("paged_decode_roofline")
    trace = tr.load(RECORDED)
    w0, w1 = tr.window_of(trace, "bench.traced")
    # an admission's SnapKV scoring call inside the window
    trace["devices"]["/device:TPU:0"].append((w0 + 1000, w0 + 6000, SNAPKV))
    red = tr.reduce(trace, w0, w1, {"paged": reader.KERNEL})
    assert red["kernel_ns"]["paged"] == 37106.0 + 37102.0
    assert red["op_ns"]["snapkv_scores_pallas.1"] == 5000.0
    assert red["op_ns"]["fusion"] == 2 * 90841.0

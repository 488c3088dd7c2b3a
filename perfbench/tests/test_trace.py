"""The reduction from a profiler trace to device numbers, on a trace
built by hand."""
import math

import pytest

from harness import trace as tr
from harness.trace import DevicePlane, DeviceTrace, Interval

MS = 1_000_000   # ns


def _trace():
    """One device, a 100 ms window.  Modules: an SpDMM call 10-30 ms, a
    pad (glue) 30-35 ms, an SpDMM call 50-70 ms overlapping a GEMM
    60-80 ms, and one whole-pass executable 90-98 ms whose operations
    carry named scopes.  Host spans label what the host did."""
    mods = [Interval(10 * MS, 30 * MS, "jit__spdmm_xla(3)"),
            Interval(30 * MS, 35 * MS, "jit_pad(7)"),
            Interval(50 * MS, 70 * MS, "jit__spdmm_xla(3)"),
            Interval(60 * MS, 80 * MS, "jit__gemm_xla(2)"),
            Interval(90 * MS, 98 * MS, "jit_pass(9)")]
    ops = [Interval(m.start, m.end, "fusion") for m in mods[:4]] + [
        Interval(90 * MS, 94 * MS, "gather.1", "jit(pass)/ack.spdmm/gather"),
        Interval(94 * MS, 98 * MS, "dot.2", "jit(pass)/ack.gemm/dot")]
    host = [Interval(0, 40 * MS, "pass"), Interval(40 * MS, 100 * MS,
                                                   "idle_wait")]
    return DeviceTrace([DevicePlane("/device:TPU:0", ops, mods)], host,
                       (0, 100 * MS))


def test_union_merges_overlap_and_clips_to_window():
    assert tr.union_ns([(5, 10), (8, 12), (20, 30), (-5, 2)], 0, 25) == [
        (0, 2), (5, 12), (20, 25)]


def test_busy_and_idle_share_are_an_interval_union():
    t = _trace()
    # busy: 10-35, 50-80, 90-98 = 25 + 30 + 8 = 63 ms of 100
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s() == pytest.approx(0.063)
    assert t.idle_share() == pytest.approx(0.37)


def test_kernel_time_by_module_and_by_scope():
    t = _trace()
    spdmm = t.kernel_s([r"^jit__spdmm_xla\b"], [r"\back\.spdmm\b"])
    # two whole SpDMM executables (40 ms) + one scoped op (4 ms)
    assert spdmm == pytest.approx(0.044)
    assert t.kernel_s([r"^jit__gemm_xla\b"]) == pytest.approx(0.020)
    assert t.kernel_s([r"^jit__sddmm_xla\b"]) == 0.0


def test_scoped_op_inside_a_matching_module_is_not_counted_twice():
    mods = [Interval(0, 10 * MS, "jit__spdmm_xla(1)")]
    ops = [Interval(0, 10 * MS, "gather", "ack.spdmm/gather")]
    t = DeviceTrace([DevicePlane("/device:TPU:0", ops, mods)], [],
                    (0, 20 * MS))
    assert t.kernel_s([r"^jit__spdmm_xla\b"], [r"\back\.spdmm\b"]) == \
        pytest.approx(0.010)


def test_idle_gaps_are_labelled_by_the_host_span():
    gaps = dict(_trace().idle_gaps())
    # 0-10 under "pass"; 35-50 overlaps "idle_wait" most (10 of 15 ms);
    # 80-90 and 98-100 under "idle_wait"
    assert gaps["pass"] == pytest.approx(0.010)
    assert gaps["idle_wait"] == pytest.approx(0.027)


def test_top_ops_rank_executables_by_device_time():
    top = _trace().top_ops()
    assert top[0] == ["jit__spdmm_xla(3)", pytest.approx(0.040)]
    assert [n for n, _ in top] == ["jit__spdmm_xla(3)", "jit__gemm_xla(2)",
                                   "jit_pass(9)", "jit_pad(7)"]


def test_roofline_share_and_the_binding_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # call A: 1000 FLOPs (10 s) vs 50 bytes (5 s) -> 10 s, flops-bound
    # call B: 100 FLOPs (1 s) vs 300 bytes (30 s) -> 30 s, bytes-bound
    least, bound = tr.least_time_s([(1000, 50), (100, 300)], peaks)
    assert least == pytest.approx(40.0) and bound == "bytes"
    share, bound = tr.roofline_share([(1000, 50), (100, 300)], 2, 160.0,
                                     peaks)
    assert share == pytest.approx(50.0) and bound == "bytes"
    assert tr.roofline_share([(1, 1)], 1, 0.0, peaks) is None


def test_roofline_never_exceeds_the_kernel_time_it_is_given():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    work = [(2e9, 4e8)]
    least, _ = tr.least_time_s(work, peaks)
    share, _ = tr.roofline_share(work, 3, 3 * least, peaks)
    assert math.isclose(share, 100.0)

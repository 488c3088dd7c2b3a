"""The GAT cell's entries in ``BENCHMARK.json`` and its edge softmax
reader, on a trace built by hand."""
import os

import pytest

import run
from harness import common, work
from harness.trace import DevicePlane, DeviceTrace, Interval

MS = 1_000_000   # ns
V, E = 19_717, 64_055      # the deployed PU graph, self loops in


def _trace(names):
    """One device, a 100 ms window, one 2 ms executable every 10 ms."""
    mods = [Interval(i * 10 * MS, i * 10 * MS + 2 * MS, f"{n}(4)")
            for i, n in enumerate(names)]
    ops = [Interval(m.start, m.end, "fusion") for m in mods]
    return DeviceTrace([DevicePlane("/device:TPU:0", ops, mods)], [],
                       (0, 100 * MS))


def _ctx(trace, peaks, passes=2):
    w = {"gemm": [], "spdmm": [], "sddmm": [work.sddmm_pair(V, E)] * 2}
    return run.Context(trace, {"passes": passes, "work": w}, {}, peaks)


def test_edge_softmax_roofline_reads_its_executable(peaks):
    read = run._reader("kernel.edge_softmax_roofline")
    # two passes of two layers: four 2 ms softmax executables, one
    # scoring executable and one vertex activation that are not its own
    t = _trace(["jit_edge_softmax"] * 4 + ["jit_sddmm", "jit_act"])
    value, extra = read(_ctx(t, peaks))
    least = 2 * 2 * 12 * E / peaks["hbm_bytes_per_s"]   # bytes-bound
    assert extra == {"bound": "bytes"}
    assert value == pytest.approx(100 * least / 0.008)


def test_edge_softmax_roofline_is_silent_without_its_executable(peaks):
    read = run._reader("kernel.edge_softmax_roofline")
    assert read(_ctx(None, peaks)) is None
    assert read(_ctx(_trace(["jit_act"] * 4), peaks)) is None
    gcn = run.Context(_trace(["jit_edge_softmax"]),
                      {"passes": 1, "work": {"sddmm": []}}, {}, peaks)
    assert read(gcn) is None


def test_gat_cell_reports_its_metrics():
    cell = common.Cell(common.load_spec(), "gat-b6.pubmed.full")
    assert cell.config["program_model"] == "b6" and cell.chips == 1
    assert cell.traffic["kind"] == "full_graph"
    assert cell.limits == {"rel_err": 0.0012, "bad_shape": 0}
    assert [m["name"] for m in cell.end_to_end] == ["pass_ms", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "kernel.spdmm_roofline", "pass.mfu", "device.idle_share.full",
        "kernel.sddmm_roofline", "kernel.edge_softmax_roofline"}
    for m in cell.per_layer:
        assert m["moves"] == "pass_ms"
        assert os.path.exists(common.bench_file("metrics",
                                                m["name"] + ".py"))

"""Algorithmic work of a pass against counts made by hand, on a graph
of 10 vertices and 30 edges (self loops included)."""
from conftest import tiny_cell
from harness import work

V, E = 10, 30


def test_gcn_b2_counts():
    cfg = tiny_cell("gcn-b2.flickr.full").config     # 500 -> 128 -> 7
    w = work.pass_work(cfg, V, E)
    assert w["gemm"] == [
        (2 * 10 * 500 * 128, 4 * (10 * 500 + 500 * 128 + 10 * 128)),
        (2 * 10 * 128 * 7, 4 * (10 * 128 + 128 * 7 + 10 * 7))]
    # aggregation at min(f_in, f_out): 128, then 7; bytes: features in
    # and out, a source id and a value per edge, V + 1 row offsets
    assert w["spdmm"] == [(2 * 30 * 128, 4 * (2 * 10 * 128 + 2 * 30 + 11)),
                          (2 * 30 * 7, 4 * (2 * 10 * 7 + 2 * 30 + 11))]
    assert w["sddmm"] == []
    assert work.pass_flops(w) == 1_280_000 + 17_920 + 7_680 + 420


def test_gat_b6_counts():
    cfg = tiny_cell("gat-b6.pubmed.full").config     # 500 -> 64 -> 3
    w = work.pass_work(cfg, V, E)
    assert w["gemm"] == [
        (640_000, 4 * (5000 + 32000 + 640)),          # projection
        (2_560, 4 * (640 + 128 + 20)),                # scores 64 -> 2
        (3_840, 4 * (640 + 192 + 30)),
        (120, 4 * (30 + 6 + 20))]
    # pair-sum scores: one add per edge; two scores per vertex, source
    # and destination ids per edge read, one score per edge written
    assert w["sddmm"] == [(30, 4 * (20 + 60 + 30))] * 2
    assert w["spdmm"] == [(2 * 30 * 64, 4 * (2 * 10 * 64 + 60 + 11)),
                          (2 * 30 * 3, 4 * (2 * 10 * 3 + 60 + 11))]

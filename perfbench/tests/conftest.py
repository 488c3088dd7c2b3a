"""Tests of the chip benchmark's harness, run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""
import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from harness import common  # noqa: E402

# Each cell at a size a CPU test holds: the graph cut to this share of
# its published |V| and |E|; widths, models and traffic shape as run.
TINY = {"gcn-b2.flickr.full": 0.01, "gat-b6.pubmed.full": 0.05,
        "gcn-b2.flickr.minibatch": 0.01}
# A cell whose files the benchmark keeps but which BENCHMARK.json does
# not run (its pass time spreads too widely on one chip's host); its
# code path stays tested.
HELD_BACK = {
    "configs": [{"name": "gat-b6.pubmed",
                 "file": "perfbench/configs/gat-b6.pubmed.json"}],
    "workloads": [{"name": "gat-b6.pubmed.full", "config": "gat-b6.pubmed",
                   "traffic": "full", "chips": 1}],
}


def spec() -> dict:
    s = common.load_spec()
    for k, v in HELD_BACK.items():
        s[k] = s[k] + v
    for m in s["end_to_end"]:
        if m["name"] == "pass_ms":
            m["workloads"] = m["workloads"] + ["gat-b6.pubmed.full"]
    return s


def tiny_cell(name: str, **traffic) -> common.Cell:
    cell = common.Cell(spec(), name)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = dict(copy.deepcopy(cell.traffic), **traffic)
    # the mix's warm list indexes buckets of the published-size graph
    cell.traffic.pop("warm", None)
    for k in ("n_vertices", "n_edges"):
        cell.config[k] = int(cell.config[k] * TINY[name])
    return cell


@pytest.fixture
def peaks():
    return common.peaks_for("TPU v5 lite")

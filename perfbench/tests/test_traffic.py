"""The traffic generator and the graph generator: what every seed shares,
what the mix's data can state, and the graph's published shape."""
import json

import numpy as np
import pytest

from conftest import ROOT, tiny_cell
from harness import common, data, traffic

MIX = dict(tiny_cell("gcn-b2.flickr.minibatch").traffic, rate_rps=40.0)
N_VERTICES = 5000


@pytest.mark.parametrize("seed", [1, 2 ** 40 + 3])
def test_every_seed_serves_the_same_requests_and_gaps(seed):
    pool, order, due = traffic.minibatch(MIX, N_VERTICES, seed, 10.0)
    base, base_order, base_due = traffic.minibatch(MIX, N_VERTICES, 0, 10.0)
    assert pool == base and sorted(order) == list(range(len(pool)))
    assert order != base_order
    assert sorted(np.diff([0.0] + due).round(9)) == pytest.approx(
        sorted(np.diff([0.0] + base_due).round(9)))
    assert due[-1] == pytest.approx(10.0)


def test_a_shorter_run_serves_the_start_of_a_longer_ones_pool():
    short = traffic.request_pool(MIX, N_VERTICES, 50)
    assert traffic.request_pool(MIX, N_VERTICES, 400)[:50] == short
    for targets, _ in short:
        assert MIX["targets_min"] <= len(targets) <= MIX["targets_max"]
        assert len(set(targets)) == len(targets)


def test_on_off_arrivals_fall_inside_bursts_at_the_mean_rate():
    mix = dict(MIX, arrivals="onoff", on_s=1.0, off_s=3.0)
    _, order, due = traffic.minibatch(mix, N_VERTICES, 7, 20.0)
    due = np.asarray(due)
    assert len(order) == 800 and np.all(np.diff(due) >= 0)
    assert np.all(due % 4.0 <= 1.0 + 1e-9)
    assert due[-1] <= 20.0


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError):
        traffic.minibatch(dict(MIX, arrivals="bursty"), N_VERTICES, 1, 1.0)


def test_undirected_graph_is_simple_and_symmetric():
    cfg = tiny_cell("gcn-b2.flickr.full").config
    src, dst = data.synth_edges(cfg)
    assert src.shape[0] == cfg["n_edges"] // 2 * 2
    assert not np.any(src == dst)
    n = cfg["n_vertices"]
    keys = src.astype(np.int64) * n + dst
    assert np.unique(keys).shape[0] == keys.shape[0]
    assert np.array_equal(np.sort(keys),
                          np.sort(dst.astype(np.int64) * n + src))
    again = data.synth_edges(cfg)
    assert np.array_equal(again[0], src) and np.array_equal(again[1], dst)


def test_published_graph_shape():
    """The configuration at its published size: |V|, |E| in both
    directions, and the largest degree its assumed exponent gives."""
    cfg = common.load_json(f"{ROOT}/perfbench/configs/gcn-b2.flickr.json")
    src, dst = data.synth_edges(cfg)
    deg = np.bincount(dst, minlength=cfg["n_vertices"])
    assert src.shape[0] == cfg["n_edges"] == 899_756
    assert deg.mean() == pytest.approx(10.08, abs=0.01)
    assert 2_000 <= deg.max() <= 9_000
    assert cfg["assumed"] == ["alpha"]


def test_warm_list_covers_every_bucket_of_a_run():
    """The mix's stored ``warm`` list, cut to a run's pool, is the first
    request of each bucket that a run of ``run_seconds`` reaches
    (sampling and layout only, on the host).  The list was found for a
    pool of 20,400 requests (400/s over 51 s), which starts with every
    pool a run at a lower rate draws."""
    import run
    spec = common.load_spec()
    cell = common.Cell(spec, "gcn-b2.flickr.minibatch")
    drv = run.runner("minibatch")(cell, 0, spec["run_seconds"],
                                  run.spans(False))
    drv.build()
    drv._requests(drv.model, spec["run_seconds"])
    reps = drv.representatives()
    drv.release()
    n = len(drv.pool)
    assert sorted(reps.values()) == [j for j in cell.traffic["warm"]
                                     if j < n], json.dumps(
        sorted(reps.values()))

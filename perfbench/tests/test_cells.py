"""Each cell's code path at a tiny size on the CPU, through the harness
(``run.execute``: everything a run does after its look for a chip), and
the comparison that decides ``correct``: the program passes it, the
lower-precision control and planted faults fail it."""
import jax
import jax.numpy as jnp
import pytest

import run
from conftest import tiny_cell
from repro.engine import Engine
from repro.engine.executor import BinaryExecutor
from repro.sampling import service

SEED = 12_345_678_901          # seeds may exceed 32 bits
CELLS = ["gcn-b2.flickr.full", "gat-b6.pubmed.full",
         "gcn-b2.flickr.minibatch"]
# Mini-batch runs short and slow enough to stay small on the CPU; a long
# batching deadline makes batches of several requests form.
MINI = {"rate_rps": 20.0}
BATCHING = {"rate_rps": 40.0, "max_wait_us": 200_000.0}


def _cell(name, **traffic):
    return tiny_cell(name, **(dict(MINI, **traffic)
                              if "minibatch" in name else traffic))


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name, peaks):
    cell = _cell(name)
    res = run.execute(cell, SEED, 1.5, False, peaks)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"
    assert res["device"]["count"] == len(jax.devices())


def test_traced_run_reports_host_side_layer_metrics(peaks):
    """On the CPU no device plane exists: the device readers find
    nothing and stay silent; the host and program readers report."""
    cell = _cell("gcn-b2.flickr.minibatch")
    res = run.execute(cell, SEED, 1.5, True, peaks)
    assert res["correct"]
    assert {"sampling.prepare_ms", "runtime.queue_wait_ms",
            "runtime.batch_size", "exec.batch_pass_ms"} <= set(
                res["metrics"])
    assert "device.idle_share.minibatch" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _driven(name, seconds=1.5, **traffic):
    cell = _cell(name, **traffic)
    drv = run.runner(cell.traffic["kind"])(cell, SEED, seconds,
                                           run.spans(False))
    drv.setup()
    drv.window(seconds)
    drv.release()
    cmp, _, _ = drv.check(cell.limits)
    return cell, drv, cmp


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_fails_the_limit(name):
    cell, drv, cmp = _driven(name)
    assert cmp.correct
    control = drv.reading("control", cell.config["matmul_precision"])
    assert control > cell.limits["rel_err"]


@pytest.mark.parametrize("name", ["gcn-b2.flickr.full", "gat-b6.pubmed.full"])
def test_altered_answer_is_not_correct(name, peaks, monkeypatch):
    real = Engine.run

    def altered(self, prog, x, **kw):
        y = real(self, prog, x, **kw)
        return y.at[0, 0].add(jnp.max(jnp.abs(y)))

    monkeypatch.setattr(Engine, "run", altered)
    res = run.execute(_cell(name), SEED, 0.5, False, peaks)
    assert not res["correct"]
    assert res["compared"]["rel_err"]["value"] > 0.5


def _planted(fault):
    real = BinaryExecutor.run_batch

    def run_batch(self, prog, xs, **kw):
        return fault(real(self, prog, xs, **kw))
    return run_batch


def test_half_of_each_batch_left_out_is_not_correct(peaks, monkeypatch):
    monkeypatch.setattr(BinaryExecutor, "run_batch", _planted(
        lambda ys: ys.at[(ys.shape[0] + 1) // 2:].set(0.0)))
    res = run.execute(_cell("gcn-b2.flickr.minibatch", **BATCHING), SEED,
                      2.0, False, peaks)
    assert not res["correct"]
    assert res["compared"]["rel_err"]["value"] >= 1.0


def test_altered_served_answer_is_not_correct(peaks, monkeypatch):
    monkeypatch.setattr(BinaryExecutor, "run_batch", _planted(
        lambda ys: ys.at[:, 0, 0].add(jnp.max(jnp.abs(ys)))))
    res = run.execute(_cell("gcn-b2.flickr.minibatch"), SEED, 1.5, False,
                      peaks)
    assert not res["correct"]


def test_answer_around_other_vertices_is_not_correct(peaks, monkeypatch):
    """A sampler that builds a valid ego network around the wrong
    vertices gives logits that match the reference on that network: the
    check of the network against the request's targets catches it."""
    real = service.sample_ego

    def shifted(g, targets, fanouts, seed=0):
        return real(g, [(t + 1) % g.n_vertices for t in targets], fanouts,
                    seed=seed)

    monkeypatch.setattr(service, "sample_ego", shifted)
    res = run.execute(_cell("gcn-b2.flickr.minibatch"), SEED, 1.5, False,
                      peaks)
    assert not res["correct"]
    assert res["compared"]["bad_samples"]["value"] > 0
    assert res["compared"]["rel_err"]["value"] <= res["compared"][
        "rel_err"]["limit"]


def test_host_streamed_passes_run_and_are_correct(peaks):
    """The full-graph driver takes residency and budget from the mix."""
    cell = _cell("gcn-b2.flickr.full", residency="host",
                 resident_budget_bytes=1 << 30)
    drv = run.runner(cell.traffic["kind"])(cell, SEED, 0.5,
                                           run.spans(False))
    drv.setup()
    drv.window(0.5)
    assert drv.engine.exec_stats.shards_streamed > 0
    drv.release()
    cmp, attempted, _ = drv.check(cell.limits)
    assert cmp.correct and attempted > 0

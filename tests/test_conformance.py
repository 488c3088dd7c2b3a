"""repro.obs conformance + attribution — PR 8 acceptance tests.

  * ``predict_loh`` residency terms: device < host-streaming, overlap
    helps, constants injectable, unknown residency refused;
  * ``ExecStats.per_layer`` populated on device and host paths and
    merged (not clobbered) by ``ExecStats.add``;
  * a synthetic 4-thread trace round-trips through the span DAG with
    the critical path exactly matching the known span nesting;
  * overlapped ``stage`` spans induce ~0 stall, serialized ones expose
    the staging time;
  * on a real traced host-streaming run the least-squares-calibrated
    model error is strictly lower than the uncalibrated error;
  * the attribution table joins wall time / staged bytes back to
    decoded instruction index ranges;
  * the trajectory gate prices the new ``model_error`` metrics.
"""
import json
import types

import jax.numpy as jnp
import pytest

from repro.core import graph as G
from repro.core.passes.partition import PartitionConfig
from repro.core.perfmodel import (ModelConstants, block_costs, layer_costs,
    predict_loh)
from repro.engine import Engine
from repro.engine.executor import ExecStats
from repro.obs import (DEFAULT_SPECS, attribution_table, build_dag,
                       build_report, fit_stage_bw, ls_scale, nrmse,
                       parse_spans, tracing)

GEOM = PartitionConfig(n1=32, n2=8)


def _g(nv=90, ne=340, f=8, c=3, seed=0):
    g = G.random_graph(nv, ne, seed=seed).gcn_normalized()
    g.feat_dim, g.n_classes = f, c
    return g


def _compiled(eng, name, g):
    prog = eng.compile(name, g)
    if prog.source is None:          # program-cache hit returns a slim copy
        prog = eng.compile(name, g, use_cache=False)
    return prog


# --------------------------------------------------------------------------- #
# perfmodel: residency-aware predict_loh (satellite).
# --------------------------------------------------------------------------- #
def _program():
    eng = Engine(geometry=GEOM, n_pes=4)
    return _compiled(eng, "b1", _g()).source.program


def test_predict_loh_host_streaming_adds_staging_time():
    prog = _program()
    t_dev = predict_loh(prog, residency="device")
    t_host = predict_loh(prog, residency="host")
    t_host_serial = predict_loh(prog, residency="host", overlap=False)
    assert 0 < t_dev < t_host <= t_host_serial


def test_predict_loh_constants_injection():
    prog = _program()
    slow_pcie = ModelConstants(stage_bw=1e9)
    assert predict_loh(prog, residency="host", constants=slow_pcie) \
        > predict_loh(prog, residency="host")
    # stage bandwidth is a host-path term only: device time unchanged
    assert predict_loh(prog, residency="device", constants=slow_pcie) \
        == pytest.approx(predict_loh(prog, residency="device"))


def test_predict_loh_unknown_residency_refused():
    prog = _program()
    with pytest.raises(ValueError):
        predict_loh(prog, residency="accelerator")


def test_layer_costs_sum_to_predict_loh_and_expose_blocks():
    prog = _program()
    lcs = layer_costs(prog, residency="host")
    assert sum(lc.t for lc in lcs) == pytest.approx(
        predict_loh(prog, residency="host"))
    bcs = block_costs(prog)
    assert sum(b.flops for b in bcs) == pytest.approx(
        sum(lc.flops for lc in lcs))
    assert all(b.t >= max(b.t_compute, b.t_memory) - 1e-18 for b in bcs)


# --------------------------------------------------------------------------- #
# ExecStats.per_layer: populated everywhere, merged by add (satellite).
# --------------------------------------------------------------------------- #
def test_per_layer_populated_on_device_and_host_paths():
    g = _g()
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = Engine(geometry=GEOM, n_pes=4)
    prog = _compiled(eng, "b1", g)
    for residency in ("device", "host"):
        eng.run(prog, x, residency=residency)
        rows = eng.exec_stats.per_layer
        assert rows, residency
        assert {r["kernel"] for r in rows} \
            <= {"gemm", "spdmm", "sddmm", "vadd", "act"}
        for r in rows:
            assert r["wall_s"] > 0
            assert 0 <= r["instr_lo"] <= r["instr_hi"]
        if residency == "host":
            assert sum(r.get("h2d_bytes", 0) for r in rows) \
                == eng.exec_stats.h2d_bytes > 0


def test_exec_stats_add_merges_per_layer():
    a, b = ExecStats(), ExecStats()
    a.note_layer(layer=0, kernel="gemm", step=0, instr_lo=1, instr_hi=4,
                 wall_s=0.5, tile_ops=10)
    b.note_layer(layer=0, kernel="gemm", step=0, instr_lo=1, instr_hi=4,
                 wall_s=0.25, tile_ops=5)
    b.note_layer(layer=1, kernel="spdmm", step=1, instr_lo=5, instr_hi=9,
                 wall_s=1.0, tile_ops=7)
    b.halo_gather_bytes = 64
    a.add(b)
    assert a.halo_gather_bytes == 64
    assert len(a.per_layer) == 2
    gemm = next(r for r in a.per_layer if r["kernel"] == "gemm")
    assert gemm["wall_s"] == pytest.approx(0.75)   # accumulated
    assert gemm["tile_ops"] == 15
    assert gemm["instr_lo"] == 1                   # identity, not summed


# --------------------------------------------------------------------------- #
# Span DAG round-trip: 4 interleaved threads, known nesting (satellite).
# --------------------------------------------------------------------------- #
def _ev(name, ts, dur, tid, **args):
    return {"ph": "X", "name": name, "cat": "t", "ts": float(ts),
            "dur": float(dur), "pid": 1, "tid": tid, "args": args}


def test_trace_dag_four_thread_round_trip_critical_path():
    evs = [
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 0,
         "args": {"name": "main"}},
        _ev("root", 0, 1000, 0),
        _ev("c1", 10, 190, 0),
        _ev("c2", 200, 200, 0),
        _ev("c3", 400, 250, 0),
        _ev("c4", 650, 340, 0),
        # three other threads, alive across c1..c4's starts, so neither
        # containment nor the last-predecessor fallback can pull the
        # walk off the known chain
        _ev("w1", 5, 900, 1),
        _ev("w2", 5, 900, 2),
        _ev("w3", 5, 900, 3),
    ]
    # JSON round trip: analyze the serialized trace, not live dicts
    doc = json.loads(json.dumps({"traceEvents": evs}))
    spans = parse_spans(doc)
    assert [s.track for s in spans if s.name == "root"] == ["main"]
    dag = build_dag(doc)
    root = next(s for s in dag.spans if s.name == "root")
    kids = [dag.spans[i].name for i in root.children]
    assert kids == ["c1", "c2", "c3", "c4"]
    assert all(dag.spans[i].parent == root.index for i in root.children)

    cp = [s.name for s in dag.critical_path()]
    # the critical path IS the known nesting: the sequential child chain
    # explaining root's span, nothing from the overlapping threads
    assert cp == ["c1", "c2", "c3", "c4", "root"]
    summ = dag.summary()
    assert summ["makespan_us"] == pytest.approx(1000.0)
    assert summ["critical_path_us"] == pytest.approx(1000.0)
    assert summ["n_spans"] == 8


def test_stage_overlap_induces_zero_stall_serialization_exposes_it():
    def trace(stage_ts, compute1_ts):
        return {"traceEvents": [
            _ev("compute", 0, 100, 0, shard=0, layer=1),
            _ev("compute", compute1_ts, 100, 0, shard=1, layer=1),
            _ev("stage", stage_ts, 40, 1, shard=1, layer=1, bytes=4096),
        ]}

    # overlapped: the stage hid entirely under shard 0's compute
    dag = build_dag(trace(stage_ts=10, compute1_ts=100))
    stage = next(s for s in dag.spans if s.name == "stage")
    assert dag.stall_us()[stage.index] == pytest.approx(0.0, abs=1e-6)
    # producer edge exists either way
    c1 = next(s for s in dag.spans
              if s.name == "compute" and s.args["shard"] == 1)
    assert stage.index in dag.producers[c1.index]

    # serialized: the same transfer after the compute exposes its 40µs
    dag = build_dag(trace(stage_ts=100, compute1_ts=140))
    stage = next(s for s in dag.spans if s.name == "stage")
    assert dag.stall_us()[stage.index] == pytest.approx(40.0, abs=1e-2)


# --------------------------------------------------------------------------- #
# Real traced run: conformance join + calibration (tentpole acceptance).
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def traced_run():
    g = _g(nv=120, ne=460)
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = Engine(geometry=GEOM, n_pes=4)
    prog = _compiled(eng, "b3", g)
    eng.run(prog, x, residency="host")          # warm (jit compiles)
    eng._executor.profile_tiles = True          # the report's density join
    with tracing() as t:
        eng.run(prog, x, residency="host")
    return prog, eng, t.events()


def test_calibrated_error_strictly_lower(traced_run):
    prog, eng, events = traced_run
    rep = build_report(prog, eng.exec_stats, residency="host",
                       events=events)
    assert rep.per_layer and rep.measured_s > 0
    # per-mode: the through-origin LS fit can never lose
    for m, e in rep.model_error.items():
        assert rep.model_error_calibrated[m] <= e + 1e-12
        assert rep.scales[m] > 0
    # overall: strictly lower (wall-clock noise makes exact fits
    # impossible, so the fitted scale must strictly reduce the error)
    assert rep.model_error_overall_calibrated < rep.model_error_overall
    # effective constants cover the modes seen + the traced staging fit
    assert "stage_bw" in rep.calibrated_constants
    assert set(rep.calibrated_constants) <= set(rep.constants)
    # the report serializes (CI writes it into BENCH_fullgraph.json)
    d = json.loads(json.dumps(rep.to_dict()))
    assert d["model_error_overall_calibrated"] \
        == pytest.approx(rep.model_error_overall_calibrated)
    md = rep.to_markdown()
    assert "Cost-model conformance" in md and "| mode |" in md


def test_fit_stage_bw_from_traced_stage_spans(traced_run):
    _, eng, events = traced_run
    bw = fit_stage_bw(events)
    assert bw is not None and bw > 0
    # sanity: a synthetic 1 GB/s trace fits exactly
    evs = [_ev("stage", 0, 1000, 0, bytes=10 ** 6),
           _ev("stage", 2000, 2000, 0, bytes=2 * 10 ** 6)]
    assert fit_stage_bw(evs) == pytest.approx(1e9)


def test_attribution_table_joins_instruction_ranges(traced_run):
    prog, eng, events = traced_run
    rows = attribution_table(events)
    layer_rows = [r for r in rows if r["shard"] is None]
    shard_rows = [r for r in rows if r["shard"] is not None]
    assert layer_rows and shard_rows
    for r in layer_rows:
        assert 0 <= r["instr_lo"] <= r["instr_hi"]
        assert r["wall_us"] > 0
    # staged bytes attribute to the decoded layers that streamed them
    assert sum(r["staged_bytes"] for r in layer_rows) \
        == eng.exec_stats.h2d_bytes > 0
    # the critical path of the same trace stays within the makespan
    summ = build_dag(events).summary()
    assert 0 < summ["critical_path_us"] <= summ["makespan_us"] + 1e-3


def test_build_report_refuses_slim_or_unrun_programs(traced_run):
    prog, eng, _ = traced_run
    with pytest.raises(ValueError, match="use_cache=False"):
        build_report(types.SimpleNamespace(source=None), eng.exec_stats)
    with pytest.raises(ValueError, match="per_layer"):
        build_report(prog, ExecStats())


# --------------------------------------------------------------------------- #
# LS helpers + trajectory gate wiring (satellite).
# --------------------------------------------------------------------------- #
def test_ls_scale_is_exact_minimizer():
    pairs = [(1.0, 2.1), (2.0, 3.9), (3.0, 6.3)]
    a = ls_scale(pairs)
    for probe in (a * 0.9, a * 1.1, 1.0):
        assert nrmse(pairs, a) <= nrmse(pairs, probe) + 1e-12
    assert ls_scale([]) == 1.0
    assert nrmse([]) == 0.0


def test_trajectory_gate_prices_model_error():
    specs = {s.path: s for s in DEFAULT_SPECS["BENCH_fullgraph.json"]}
    for mode in ("gemm", "spdmm"):
        s = specs[f"models.0.conformance.model_error.{mode}"]
        assert s.direction == "lower"
    assert specs["models.0.conformance.model_error_overall"].direction \
        == "lower"
    # calibration must keep strictly reducing the error (gain >= 0)
    assert specs["models.0.conformance.calibration_gain"].direction \
        == "higher"

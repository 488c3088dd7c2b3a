"""repro.obs — tracing, profiling, trajectory-gate, telemetry tests.

PR 7 acceptance criteria:
  * a traced host-streaming run (b3, 1 graph) produces Perfetto-valid
    trace JSON in which stage spans and compute spans demonstrably
    overlap (span timestamp intersection);
  * ``check_trajectory`` passes on the committed BENCH_*.json and fails
    on a synthetically degraded copy;
  * (satellites) ``percentile`` edge cases, tracer thread-interleaving
    round-trips as valid JSON, ``ExecStats.add`` merges ``per_device``,
    ``Metrics`` p90/max + wait-vs-execute split + cutover skew.
"""
import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core.passes.partition import PartitionConfig
from repro.engine import Engine, InferenceRequest
from repro.engine.executor import ExecStats
from repro.engine.program import CompiledProgram
from repro.obs import (DEFAULT_SPECS, MetricSpec, NullTracer, Tracer,
                       compare_docs, compare_metrics, lookup, tracing)
from repro.obs.tracer import get_tracer
from repro.runtime import Metrics, OverlayPool, ServeLoop
from repro.runtime.metrics import percentile

GEOM = PartitionConfig(n1=32, n2=8)


def _g(nv=70, ne=260, f=8, c=3, seed=0):
    g = G.random_graph(nv, ne, seed=seed).gcn_normalized()
    g.feat_dim, g.n_classes = f, c
    return g


def _overlaps(a, b):
    return max(a["ts"], b["ts"]) < min(a["ts"] + a["dur"],
                                       b["ts"] + b["dur"])


# --------------------------------------------------------------------------- #
# percentile() edge cases (satellite).
# --------------------------------------------------------------------------- #
def test_percentile_empty_is_zero():
    assert percentile([], 50) == 0.0
    assert percentile([], 0) == 0.0
    assert percentile([], 100) == 0.0


def test_percentile_single_sample_every_q():
    for q in (0, 1, 50, 99, 100):
        assert percentile([7.5], q) == 7.5


def test_percentile_q0_and_q100_are_min_and_max():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(xs, 0) == 1.0       # nearest-rank: rank >= 1
    assert percentile(xs, 100) == 5.0
    assert percentile(xs, 50) == 3.0


def test_percentile_deque_cap_evicts_oldest():
    m = Metrics(max_samples=4)
    class R:  # minimal response stub
        cache_hit = True
        cache_key = "k"
        t_loc = 0.0
        t_loh = 0.0
        model_name = "m"
        graph_name = "g"
        request_id = "r"
    for v in (100.0, 1.0, 2.0, 3.0, 4.0):   # 100.0 evicted by cap
        m.record_response(R(), v)
    snap = m.snapshot()
    assert snap["global"]["max_latency_ms"] == 4000.0
    assert snap["global"]["p99_latency_ms"] == 4000.0


# --------------------------------------------------------------------------- #
# Tracer: spans, nesting, threads, Perfetto JSON round-trip.
# --------------------------------------------------------------------------- #
def test_null_tracer_is_default_and_noop():
    t = get_tracer()
    assert isinstance(t, NullTracer) and not t.enabled
    s = t.span("x")
    assert s.add(a=1) is s          # chainable no-op
    s.done()
    t.instant("i")
    t.counter("c", 1.0)
    assert t.to_dict() == {"traceEvents": [], "displayTimeUnit": "ms"}
    with pytest.raises(RuntimeError):
        t.save("/tmp/never.json")


def test_tracing_scope_restores_previous_tracer():
    before = get_tracer()
    with tracing() as t:
        assert get_tracer() is t and t.enabled
    assert get_tracer() is before


def test_span_nesting_and_json_round_trip(tmp_path):
    t = Tracer()
    with t.span("outer", cat="a", track="tk"):
        with t.span("inner", cat="a", track="tk", args={"k": 1}):
            pass
    t.instant("mark", track="tk")
    t.counter("depth", 3, track="tk")
    path = tmp_path / "trace.json"
    t.save(str(path))
    doc = json.loads(path.read_text())      # schema round-trip
    evs = doc["traceEvents"]
    X = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(X) == {"outer", "inner"}
    # Perfetto infers nesting from containment: inner ⊆ outer.
    assert X["outer"]["ts"] <= X["inner"]["ts"]
    assert (X["inner"]["ts"] + X["inner"]["dur"]
            <= X["outer"]["ts"] + X["outer"]["dur"] + 1e-6)
    assert X["inner"]["args"] == {"k": 1}
    # One named track -> one tid, announced by thread_name metadata.
    assert X["outer"]["tid"] == X["inner"]["tid"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(m["args"]["name"] == "tk" for m in meta)
    for e in evs:                           # minimal Chrome-format keys
        assert {"ph", "name", "pid", "tid"} <= set(e)


def test_tracer_thread_interleaving_valid_json():
    t = Tracer()
    gate = threading.Barrier(4)             # keep all 4 alive at once
                                            # (thread idents get reused)

    def work(n):
        gate.wait()
        for i in range(20):
            with t.span(f"w{n}", cat="t"):
                t.counter(f"c{n}", i)

    threads = [threading.Thread(target=work, args=(n,)) for n in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    doc = json.loads(json.dumps(t.to_dict()))
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 80
    # Each recording thread claimed its own tid (separate tracks).
    assert len({e["tid"] for e in spans}) == 4
    summ = t.summary()
    assert sum(s["count"] for s in summ["spans"].values()) == 80


# --------------------------------------------------------------------------- #
# ACCEPTANCE: traced host-streaming run -> stage/compute spans overlap.
# --------------------------------------------------------------------------- #
def test_traced_host_streaming_stage_compute_overlap(tmp_path):
    g = _g(nv=90, ne=340)
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = Engine(geometry=GEOM, n_pes=4)
    with tracing() as t:
        prog = eng.compile("b3", g)
        y = eng.run(prog, x, residency="host")
    assert y.shape == (g.n_vertices, g.n_classes)
    path = tmp_path / "trace.json"
    t.save(str(path))
    doc = json.loads(path.read_text())      # Perfetto-valid JSON
    evs = doc["traceEvents"]
    stages = [e for e in evs if e["ph"] == "X" and e["name"] == "stage"]
    computes = [e for e in evs
                if e["ph"] == "X" and e["name"] == "compute"]
    assert stages and computes
    # The double buffer stages shard j+1 INSIDE shard j's compute span:
    # timestamp intersection is the structural overlap, asserted.
    pairs = sum(1 for s in stages for c in computes if _overlaps(s, c))
    assert pairs > 0
    # Compile passes were traced too (b3 paid one compile).
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"order_opt", "fusion", "partition", "kernel_map",
            "schedule", "codegen", "compile", "decode"} <= names
    # stage spans carry the staged byte counts the stats aggregate.
    assert sum(e["args"]["bytes"] for e in stages) \
        == eng.exec_stats.h2d_bytes


def test_tracing_disabled_emits_nothing_and_same_results():
    g = _g()
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = Engine(geometry=GEOM, n_pes=4)
    prog = eng.compile("b3", g)
    y0 = eng.run(prog, x, residency="host")
    with tracing() as t:
        y1 = eng.run(prog, x, residency="host")
    assert np.allclose(np.asarray(y0), np.asarray(y1))
    assert get_tracer().to_dict()["traceEvents"] == []
    assert len(t.events()) > 0


# --------------------------------------------------------------------------- #
# Per-tile execution profile -> manifest -> .gagi round-trip.
# --------------------------------------------------------------------------- #
def test_exec_profile_recorded_and_roundtrips_gagi(tmp_path):
    g = _g()
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = Engine(geometry=GEOM, n_pes=4)
    prog = eng.compile("b1", g)
    assert "exec_profile" not in prog.manifest
    eng._executor.profile_tiles = True      # no tracer needed
    eng.run(prog, x)
    prof = prog.manifest["exec_profile"]
    assert prof["runs"] == 1
    assert prof["kernel_modes"].get("spdmm", 0) > 0
    assert prof["kernel_modes"].get("gemm", 0) > 0
    assert len(prof["tiles"]) > 0
    for key, rec in prof["tiles"].items():
        j, k, s = map(int, key.split(":"))
        assert rec["kernel"] == "spdmm"
        assert 0.0 <= rec["density"] <= 1.0
        assert rec["nnz"] <= rec["slots"]
    assert sum(prof["density_histogram"]) == len(prof["tiles"])
    # Second run accumulates.
    eng.run(prog, x)
    assert prog.manifest["exec_profile"]["runs"] == 2
    # Round-trips the .gagi bundle (manifest is serialized verbatim).
    p = tmp_path / "b1.gagi"
    prog.save(str(p))
    loaded = CompiledProgram.load(str(p))
    assert loaded.manifest["exec_profile"]["kernel_modes"] \
        == prof["kernel_modes"]


def test_profile_off_by_default():
    g = _g()
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = Engine(geometry=GEOM, n_pes=4)
    prog = eng.compile("b1", g)
    eng.run(prog, x)
    assert "exec_profile" not in prog.manifest


# --------------------------------------------------------------------------- #
# ExecStats.add merges per_device instead of clobbering (satellite).
# --------------------------------------------------------------------------- #
def test_exec_stats_add_merges_per_device():
    total = ExecStats()
    run1 = ExecStats(per_device=[
        {"device": 0, "tile_ops": 10, "shards": 2, "halo_bytes": 100,
         "blocks": 3},
        {"device": 1, "tile_ops": 20, "shards": 3, "halo_bytes": 200,
         "blocks": 2}])
    run2 = ExecStats(per_device=[
        {"device": 0, "tile_ops": 5, "shards": 1, "halo_bytes": 50,
         "blocks": 3},
        {"device": 2, "tile_ops": 7, "shards": 1, "halo_bytes": 0,
         "blocks": 1}])
    total.add(run1)
    total.add(run2)
    by = {d["device"]: d for d in total.per_device}
    assert by[0]["tile_ops"] == 15 and by[0]["shards"] == 3
    assert by[0]["halo_bytes"] == 150
    assert by[1]["tile_ops"] == 20          # untouched by run2
    assert by[2]["tile_ops"] == 7           # new device appended
    assert by[0]["blocks"] == 3             # geometry kept, not summed
    assert [d["device"] for d in total.per_device] == [0, 1, 2]
    # run1/run2 are themselves untouched (add deep-copies).
    assert run1.per_device[0]["tile_ops"] == 10


# --------------------------------------------------------------------------- #
# Metrics: p90/max, wait-vs-execute split, slowest(), cutover skew.
# --------------------------------------------------------------------------- #
class _Resp:
    def __init__(self, rid="r", hit=True):
        self.request_id = rid
        self.cache_hit = hit
        self.cache_key = "key"
        self.t_loc = 0.0
        self.t_loh = 0.0
        self.model_name = "b1"
        self.graph_name = "g"


def test_metrics_p90_max_and_phase_split():
    m = Metrics()
    for i in range(10):
        lat = (i + 1) / 1000.0              # 1..10 ms
        m.record_response(_Resp(rid=f"r{i}"), lat,
                          queue_wait_s=lat * 0.25,
                          execute_s=lat * 0.75)
    g = m.snapshot()["global"]
    assert g["p90_latency_ms"] == 9.0
    assert g["max_latency_ms"] == 10.0
    assert g["p50_latency_ms"] == 5.0
    assert g["queue_wait_ms"]["mean"] == pytest.approx(1.375)
    assert g["execute_ms"]["mean"] == pytest.approx(4.125)
    # slowest() joins the tail sample to its phase breakdown.
    worst = m.slowest(2)
    assert [w["request_id"] for w in worst] == ["r9", "r8"]
    assert worst[0]["queue_wait_ms"] == pytest.approx(2.5)
    assert worst[0]["execute_ms"] == pytest.approx(7.5)
    json.dumps(m.snapshot())                # stays serializable


def test_metrics_without_phase_terms_keeps_old_shape():
    m = Metrics()
    m.record_response(_Resp(), 0.005)
    g = m.snapshot()["global"]
    assert "queue_wait_ms" not in g and "execute_ms" not in g
    assert m.slowest() == []


def test_record_cutover_version_skew():
    m = Metrics()
    m.set_active_version(1)
    m.record_cutover(1, 2, pinned_old=3)
    m.record_cutover(2, 3)                  # default: no skew
    snap = m.snapshot()["livegraph"]
    assert snap["cutovers"] == 2
    assert snap["active_version"] == 3
    assert snap["cutover_log"] == [
        {"from": 1, "to": 2, "pinned_old": 3},
        {"from": 2, "to": 3, "pinned_old": 0}]
    assert snap["max_version_skew"] == 3
    json.dumps(snap)


# --------------------------------------------------------------------------- #
# ServeLoop lifecycle spans + phase split wiring.
# --------------------------------------------------------------------------- #
class _WaitRecorder(Metrics):
    """Metrics that keep each response's queue_wait_s by request id."""

    def __init__(self):
        super().__init__()
        self.waits = {}

    def record_response(self, resp, latency_s, queue_wait_s=None,
                        execute_s=None, compile_s=None):
        super().record_response(resp, latency_s, queue_wait_s=queue_wait_s,
                                execute_s=execute_s, compile_s=compile_s)
        self.waits[resp.request_id] = queue_wait_s


def test_serve_loop_emits_lifecycle_spans_and_phase_split():
    g = _g()
    metrics = _WaitRecorder()
    pool = OverlayPool(n_overlays=1, geometry=GEOM, n_pes=4,
                       metrics=metrics)
    loop = ServeLoop(pool, max_batch=4)
    x = jnp.asarray(G.random_features(g, seed=1))
    reqs = [InferenceRequest(model="b1", graph=g, features=x,
                             request_id=f"q{i}") for i in range(4)]
    with tracing() as t:
        resps = loop.serve(reqs)
    assert len(resps) == 4
    spans = [e for e in t.events() if e["ph"] == "X"]
    names = ("serve.batching", "serve.handoff", "serve.respond",
             "serve.request")
    by = {n: {} for n in names}
    for e in spans:
        if e["name"] in by:
            rid = e["args"]["request"]
            assert rid not in by[e["name"]], f"two {e['name']} for {rid}"
            by[e["name"]][rid] = e
    ids = {"q0", "q1", "q2", "q3"}
    for n in names:
        assert set(by[n]) == ids, n
    # The replaced spans are gone.
    assert not {"admit", "queue_wait", "batch"} & {
        e["name"] for e in t.events()}
    for rid in ids:
        b, h, r, q = (by[n][rid] for n in names)
        # one batch per request, shared by its spans
        assert b["args"]["batch"] == h["args"]["batch"] \
            == r["args"]["batch"] == q["args"]["batch"]
        # batching ends where hand-off starts; the request spans both
        # and ends with the response
        assert b["ts"] + b["dur"] == pytest.approx(h["ts"], abs=2e-3)
        assert q["ts"] == pytest.approx(b["ts"], abs=1e-3)
        assert q["ts"] + q["dur"] == pytest.approx(r["ts"] + r["dur"],
                                                   abs=1e-3)
        assert h["ts"] + h["dur"] <= r["ts"] + 1e-3
        # batching + hand-off is the loop's own queue wait (µs vs s)
        assert (b["dur"] + h["dur"]) * 1e-6 == pytest.approx(
            metrics.waits[rid], abs=50e-6)
    # Metrics got the wait-vs-execute split from the same code path.
    snap = pool.metrics.snapshot()["global"]
    assert "queue_wait_ms" in snap and "execute_ms" in snap
    assert len(pool.metrics.slowest(10)) == 4
    loop.shutdown()


def test_serve_loop_records_no_stamps_without_tracing():
    g = _g()
    pool = OverlayPool(n_overlays=1, geometry=GEOM, n_pes=4)
    loop = ServeLoop(pool, max_batch=4)
    x = jnp.asarray(G.random_features(g, seed=1))
    loop.serve([InferenceRequest(model="b1", graph=g, features=x)
                for _ in range(3)])
    assert loop._admitted_ns == {} and loop._batch_seq == 0
    loop.shutdown()


def test_batch_spans_cover_the_overlay_execution():
    """Between a batch's hand-off and its responses, the engine's
    ``exec.batch_stage``, ``exec.batch_pass`` and ``exec.batch_unstack``
    follow one another and leave next to nothing unspanned."""
    g = _g()
    pool = OverlayPool(n_overlays=1, geometry=GEOM, n_pes=4)
    loop = ServeLoop(pool, max_batch=2)
    x = jnp.asarray(G.random_features(g, seed=1))
    reqs = [InferenceRequest(model="b1", graph=g, features=x,
                             request_id=f"q{i}") for i in range(4)]
    loop.serve(reqs[:2])        # compile outside the traced part
    with tracing() as t:
        loop.serve(reqs)
    loop.shutdown()
    spans = [e for e in t.events() if e["ph"] == "X"]
    names = ("exec.batch_stage", "exec.batch_pass", "exec.batch_unstack")
    execs = [e for e in spans if e["name"] in names]
    assert [e["name"] for e in execs] == list(names) * 2
    windows = {}
    for e in spans:
        if e["name"] == "serve.handoff":
            windows[e["args"]["batch"]] = [e["ts"] + e["dur"]]
        elif e["name"] == "serve.respond":
            windows[e["args"]["batch"]].append(e["ts"])
    assert len(windows) == 2
    for i, (a, b) in enumerate(sorted(windows.values())):
        stage, pas, unstack = execs[3 * i: 3 * i + 3]
        assert a <= stage["ts"] + 1e-3
        assert stage["ts"] + stage["dur"] <= pas["ts"] + 1e-3
        assert pas["ts"] + pas["dur"] <= unstack["ts"] + 1e-3
        assert unstack["ts"] + unstack["dur"] <= b + 1e-3
        assert pas["args"] == {"requests": 2, "lanes": 2}
        uncovered = (b - a) - sum(e["dur"] for e in (stage, pas, unstack))
        assert uncovered <= max(1e3, 0.2 * (b - a))     # µs


# --------------------------------------------------------------------------- #
# One clock: tracer spans laid on a jax.profiler trace through anchor().
# --------------------------------------------------------------------------- #
def _profile_events(log_dir, names):
    import glob
    import os
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.duration_ns), dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_anchor_puts_tracer_spans_on_the_profiler_clock(tmp_path):
    import time
    import jax
    from repro.obs.tracer import CLOCK_ANCHOR
    t = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        t.anchor()
        time.sleep(0.02)
        with jax.profiler.TraceAnnotation("probe"):
            with t.span("probe"):
                time.sleep(0.03)
        time.sleep(0.02)
        t.anchor()
    finally:
        jax.profiler.stop_trace()
    evs = _profile_events(str(tmp_path), {CLOCK_ANCHOR, "probe"})
    anchors = [e for e in evs if e[0] == CLOCK_ANCHOR]
    probe, = [e for e in evs if e[0] == "probe"]
    assert len(anchors) == 2
    # The tracer's own instants carry the same perf_ns stamps.
    mine = [e for e in t.events() if e["name"] == CLOCK_ANCHOR]
    assert [int(a[3]["perf_ns"]) for a in anchors] \
        == [e["args"]["perf_ns"] for e in mine]
    # Map the tracer span through the anchors: a line through both.
    (_, y0, _, s0), (_, y1, _, s1) = anchors
    x0, x1 = int(s0["perf_ns"]), int(s1["perf_ns"])
    slope = (y1 - y0) / (x1 - x0)
    span, = [e for e in t.events() if e["name"] == "probe"]
    # tracer ts (µs from its start) -> perf_counter_ns via an anchor
    base = mine[0]["args"]["perf_ns"] - mine[0]["ts"] * 1e3
    start = y0 + slope * (base + span["ts"] * 1e3 - x0)
    end = start + slope * span["dur"] * 1e3
    assert abs(start - probe[1]) < 1e6           # within 1 ms
    assert abs(end - (probe[1] + probe[2])) < 1e6
    # zero-length anchors, and a drift far below the window
    assert abs(slope - 1.0) < 1e-2


def test_null_tracer_anchor_is_a_noop():
    assert NullTracer().anchor() == 0


# --------------------------------------------------------------------------- #
# Stable device names: the batched pass and the ACK modes' scopes.
# --------------------------------------------------------------------------- #
def test_batched_pass_is_named_and_scoped_by_ack_mode():
    from repro.engine.executor import device_tiles, reads_edges
    g = _g()
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = Engine(geometry=GEOM, n_pes=4)
    prog = eng.compile("b1", g)
    xs = jnp.stack([x, x])
    eng.run_batch(prog, xs)
    (fn, _), = prog.__dict__["_batch_exec"].values()
    gd = device_tiles(prog.pgraph, edges=reads_edges(prog.plan()))
    text = fn.lower(xs, gd).as_text(debug_info=True)
    assert "@jit_batched_pass" in text
    assert "ack.spdmm" in text and "ack.gemm" in text


def test_tracing_leaves_tile_profile_off_and_mutes_the_jit_trace():
    g = _g()
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = Engine(geometry=GEOM, n_pes=4)
    prog = eng.compile("b1", g)
    with tracing() as t:
        eng.run_batch(prog, jnp.stack([x, x]))      # traces run()
        eng.run(prog, x)                            # traces the pass
        eng.run(prog, x)                            # replays it
    assert "exec_profile" not in prog.manifest
    names = [e["name"] for e in t.events() if e["ph"] == "X"]
    # one decode a single pass, and no span from either trace
    assert names.count("decode") == 2
    assert not [n for n in names if n.startswith("layer")]
    counters = {e["name"]: e["args"]["value"] for e in t.events()
                if e["ph"] == "C"}
    assert counters == {"exec.pass_compiles": 1, "exec.pass_replays": 1,
                        "exec.edge_scatters": 0}
    # per-tile dispatch keeps one decode and one span per layer
    eager = Engine(geometry=GEOM, n_pes=4, overlap=False)
    prog = eager.compile("b1", g)
    with tracing() as t:
        eager.run(prog, x)
    names = [e["name"] for e in t.events() if e["ph"] == "X"]
    assert names.count("decode") == 1
    layers = [n for n in names if n.startswith("layer")]
    assert len(layers) == len(prog.plan().layers)


# --------------------------------------------------------------------------- #
# Trajectory: tolerance bands, mode guard, markdown, degraded copies.
# --------------------------------------------------------------------------- #
def test_lookup_dotted_paths_and_list_indices():
    doc = {"a": {"b": [10, {"c": 5}]}}
    assert lookup(doc, "a.b.0") == 10
    assert lookup(doc, "a.b.1.c") == 5
    with pytest.raises(KeyError):
        lookup(doc, "a.z")
    with pytest.raises(KeyError):
        lookup(doc, "a.b.9")


def test_compare_metrics_bands_and_directions():
    specs = [MetricSpec("thr", "higher", 0.2),
             MetricSpec("p99", "lower", 0.5),
             MetricSpec("flag", "higher", 0.0, 0.0)]
    base = {"thr": 100.0, "p99": 10.0, "flag": True}
    # Inside the bands: ok / improved, never regressed.
    rs = compare_metrics(base, {"thr": 90.0, "p99": 12.0, "flag": True},
                         specs)
    assert [r.status for r in rs] == ["ok", "ok", "ok"]
    rs = compare_metrics(base, {"thr": 150.0, "p99": 5.0, "flag": True},
                         specs)
    assert [r.status for r in rs] == ["improved", "improved", "ok"]
    # Outside: regressed (and .failed); a flipped flag regresses at 0-tol.
    rs = compare_metrics(base, {"thr": 70.0, "p99": 16.0, "flag": False},
                         specs)
    assert all(r.status == "regressed" and r.failed for r in rs)
    # Missing fresh metric fails; missing baseline metric is "new".
    rs = compare_metrics(base, {"p99": 10.0, "flag": True}, specs)
    assert rs[0].status == "missing" and rs[0].failed
    rs = compare_metrics({"p99": 10.0, "flag": True},
                         {"thr": 1.0, "p99": 10.0, "flag": True}, specs)
    assert rs[0].status == "new" and not rs[0].failed


def test_compare_docs_mode_guard_skips():
    specs = [MetricSpec("x", "higher")]
    rep = compare_docs("f.json", {"mode": "full", "x": 1},
                       {"mode": "smoke", "x": 0}, specs)
    assert rep.skipped is not None and rep.ok


def test_trajectory_on_committed_bench_files(tmp_path):
    """The real gate: committed BENCH_*.json pass against themselves;
    a synthetically degraded copy fails."""
    import os
    import shutil
    repo = os.path.join(os.path.dirname(__file__), "..")
    from repro.obs import compare_dirs
    base = tmp_path / "base"
    fresh = tmp_path / "fresh"
    base.mkdir(), fresh.mkdir()
    names = [n for n in DEFAULT_SPECS
             if os.path.exists(os.path.join(repo, n))]
    assert names, "no committed BENCH_*.json found"
    for n in names:
        shutil.copy(os.path.join(repo, n), base / n)
        shutil.copy(os.path.join(repo, n), fresh / n)
    rep = compare_dirs(str(base), str(fresh))
    assert rep.ok                           # identical -> PASS
    compared = [f for f in rep.files if f.skipped is None]
    assert compared and all(f.results for f in compared)
    md = rep.to_markdown()
    assert "**PASS**" in md and "| metric |" in md

    # Degrade one semantic metric in one comparable file.
    victim = compared[0].name
    doc = json.loads((fresh / victim).read_text())
    spec = next(s for s in DEFAULT_SPECS[victim]
                if s.rel_tol == 0.0)        # a zero-band metric
    # walk to the parent and flip/bump the leaf the wrong way
    *parents, leaf = spec.path.split(".")
    cur = doc
    for seg in parents:
        cur = cur[int(seg)] if isinstance(cur, list) else cur[seg]
    old = cur[leaf]
    cur[leaf] = (not old) if isinstance(old, bool) else \
        (old + 1 if spec.direction == "lower" else max(0, old - 1)
         if isinstance(old, int) else old * 0.5
         if spec.direction == "higher" else old * 2)
    (fresh / victim).write_text(json.dumps(doc))
    rep2 = compare_dirs(str(base), str(fresh))
    assert not rep2.ok
    assert any(r.path == spec.path for r in rep2.regressions)
    md2 = rep2.to_markdown()
    assert "**FAIL**" in md2 and "**REGRESSED**" in md2


def test_check_trajectory_cli_exit_codes(tmp_path):
    import os
    import shutil
    import subprocess
    import sys
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    base = tmp_path / "base"
    fresh = tmp_path / "fresh"
    base.mkdir(), fresh.mkdir()
    name = "BENCH_serve.json"
    src = os.path.join(repo, name)
    if not os.path.exists(src):
        pytest.skip("no committed BENCH_serve.json")
    shutil.copy(src, base / name)
    shutil.copy(src, fresh / name)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out_md = tmp_path / "TRAJECTORY.md"
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks",
                                      "check_trajectory.py"),
         "--baseline-dir", str(base), "--fresh-dir", str(fresh),
         "--files", name, "--out", str(out_md)],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "**PASS**" in out_md.read_text()
    # Degrade: zero-band binary_passes metric bumped the wrong way.
    doc = json.loads((fresh / name).read_text())
    doc["traffic"]["same_key"]["batched"]["binary_passes"] += 5
    (fresh / name).write_text(json.dumps(doc))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks",
                                      "check_trajectory.py"),
         "--baseline-dir", str(base), "--fresh-dir", str(fresh),
         "--files", name],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 1
    assert "REGRESSED" in r.stdout

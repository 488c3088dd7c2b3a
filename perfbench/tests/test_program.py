"""The program's spans on the device trace's clock (``harness.program``)
on synthetic spans and anchors, a traced window on the CPU through
``phases.measure``, and a ``--trace 0`` run that never turns the program's
tracer on."""
import pytest

import phases
import run
from conftest import tiny_cell
from harness import program
from harness.trace import DevicePlane, DeviceTrace, Interval
from repro.obs.tracer import NullTracer, get_tracer

MS = 1_000_000   # ns
SEED = 98_765_432_109


def test_clock_maps_through_two_anchors_with_drift():
    # the trace clock runs 100 ppm fast against perf_counter, offset 5 s
    anchors = [(5_000 * MS, 1_000 * MS),
               (5_000 * MS + 50_005 * MS // 10, 1_000 * MS + 5_000 * MS)]
    c = program.Clock(anchors)
    assert c.drift_ns == 500_000                 # 0.5 ms over 5 s
    assert c(1_000 * MS) == 5_000 * MS
    assert c(3_500 * MS) == 5_000 * MS + 2_500 * MS + 250_000
    one = program.Clock(anchors[:1])             # offset alone
    assert one.drift_ns == 0 and one(1_001 * MS) == 5_001 * MS
    with pytest.raises(ValueError):
        program.Clock([])


def _events(base_perf_ns=10_000 * MS):
    """Tracer events as ``Tracer.events()`` gives them: ts/dur in µs since
    the tracer's start, which was ``base_perf_ns`` on its clock."""
    def X(name, ts_ms, dur_ms, **args):
        return {"ph": "X", "name": name, "ts": ts_ms * 1e3,
                "dur": dur_ms * 1e3, "args": args}
    anchor = {"ph": "i", "name": program.ANCHOR, "ts": 1e3,
              "args": {"perf_ns": base_perf_ns + MS}}
    return [anchor,
            X("sampling.sample", 10, 2, request="r0"),
            X("sampling.layout", 12, 1, request="r0"),
            X("serve.batching", 14, 3, request="r0", batch=0),
            X("serve.handoff", 17, 5, request="r0", batch=0),
            X("exec.batch_stage", 22, 0.5),
            X("exec.batch_pass", 22.5, 1),
            X("serve.respond", 23.5, 0.5, request="r0", batch=0),
            X("serve.request", 14, 10, request="r0", batch=0),
            X("sampling.sample", 30, 4, request="r1")]


def test_mapped_spans_land_on_the_trace_clock():
    # trace clock = perf_counter + 2 s
    base = 10_000 * MS
    anchors = [(base + MS + 2_000 * MS, base + MS)]
    spans, clock = program.mapped(_events(base), anchors)
    assert clock is not None
    first = spans[0]
    assert first.name == "sampling.sample"
    assert first.start == base + 10 * MS + 2_000 * MS
    assert first.dur == 2 * MS and first.args == {"request": "r0"}
    # no anchors on either side: nothing mapped
    assert program.mapped(_events(base), []) == ([], None)
    assert program.mapped([e for e in _events(base)
                           if e["name"] != program.ANCHOR], anchors) \
        == ([], None)


def _spans():
    spans, _ = program.mapped(_events(0), [(MS, MS)])
    return spans


def test_span_readers_and_their_silence():
    got = program.span_readings(_spans())
    assert got == pytest.approx({
        "sampling.sample_ms": 3.0, "sampling.layout_ms": 1.0,
        "runtime.batching_ms": 3.0, "runtime.handoff_ms": 5.0,
        "exec.batch_stage_ms": 0.5, "runtime.respond_ms": 0.5})
    assert all(v is None for v in program.span_readings([]).values())
    assert program.mean_ms(_spans(), "exec.batch_pass") == pytest.approx(1)


def _device(mods, host=()):
    ops = [Interval(m.start, m.end, "fusion") for m in mods]
    return DeviceTrace([DevicePlane("/device:TPU:0", ops, mods)],
                       list(host), (0, 40 * MS))


def test_batch_device_time_reads_the_named_pass_only():
    t = _device([Interval(22 * MS, 22 * MS + 200_000,
                          "jit_batched_pass(3)"),
                 Interval(30 * MS, 30 * MS + 100_000,
                          "jit_batched_pass(5)"),
                 Interval(31 * MS, 32 * MS, "jit__lambda_(1)")])
    assert program.batch_device_ms(t) == pytest.approx(0.15)
    assert program.batch_device_ms(_device(
        [Interval(0, MS, "jit__lambda_(1)")])) is None


def test_batch_device_metric_reads_the_trace_and_is_silent_without(peaks):
    read = run._reader("exec.batch_device_ms")
    named = _device([Interval(22 * MS, 22 * MS + 300_000,
                              "jit_batched_pass(3)")])
    assert read(run.Context(named, {}, {}, peaks)) == pytest.approx(0.3)
    # a program whose pass has no name, or no trace at all: nothing
    anon = _device([Interval(0, MS, "jit__lambda_(1)")])
    assert read(run.Context(anon, {}, {}, peaks)) is None
    assert read(run.Context(None, {}, {}, peaks)) is None


def _req(rid, overlay, batch, handoff_end, respond_start):
    S = program.Span
    return {"serve.handoff": S(handoff_end - MS, handoff_end,
                               "serve.handoff",
                               {"overlay": overlay, "batch": batch}),
            "serve.respond": S(respond_start, respond_start + MS,
                               "serve.respond",
                               {"overlay": overlay, "batch": batch})}


def test_batch_exec_spans_go_to_their_overlays_thread():
    # overlay 0 runs on tracer thread 7, overlay 1 on thread 9; the
    # overlay-1 batch runs inside the overlay-0 batch's execution
    S = program.Span
    by = {"a": _req("a", 0, 0, 10 * MS, 30 * MS),
          "b": _req("b", 1, 1, 12 * MS, 20 * MS)}
    spans = [S(10 * MS + 10, 14 * MS, "exec.batch_stage", {}, 7),
             S(12 * MS + 10, 15 * MS, "exec.batch_stage", {}, 9),
             S(14 * MS, 15 * MS, "exec.batch_pass", {}, 7),
             S(15 * MS, 16 * MS, "exec.batch_pass", {}, 9),
             S(16 * MS, 17 * MS, "exec.batch_unstack", {}, 9),
             S(15 * MS, 28 * MS, "exec.batch_unstack", {}, 7)]
    got = phases._batch_exec(by, spans)
    assert got[(0, 0)] == {"exec.batch_stage": 4 * MS - 10,
                           "exec.batch_pass": MS,
                           "exec.batch_unstack": 13 * MS}
    assert got[(1, 1)] == {"exec.batch_stage": 3 * MS - 10,
                           "exec.batch_pass": MS,
                           "exec.batch_unstack": MS}


def test_idle_time_is_labelled_by_program_leaves():
    host = [Interval(0, 10 * MS, "idle_wait"),
            Interval(10 * MS, 14 * MS, "prepare")]
    t = _device([Interval(9 * MS, 10 * MS, "jit_x(1)"),
                 Interval(15 * MS, 17 * MS, "jit_x(1)"),
                 Interval(23 * MS, 24 * MS, "jit_batched_pass(3)")], host)
    gaps = dict(program.idle_gaps(t, _spans()))
    # each gap goes whole to the span overlapping it most; the parent
    # serve.request never labels one, and the benchmark's "prepare"
    # gives way to the sampling spans that split it
    assert gaps == pytest.approx({"idle_wait": 0.009,
                                  "sampling.sample": 0.005 + 0.016,
                                  "serve.handoff": 0.006})
    # without program spans the benchmark's labels stay
    assert "prepare" in dict(program.idle_gaps(t, []))


MINI = {"rate_rps": 20.0, "max_wait_us": 50_000.0}


def test_a_traced_window_maps_every_phase_on_the_cpu(peaks):
    """The whole of ``phases.measure`` at a tiny size: on the CPU no
    device plane exists, so only the device reading stays silent."""
    cell = tiny_cell("gcn-b2.flickr.minibatch", **MINI)
    out = phases.measure(cell, SEED, 1.5, peaks)
    assert out["correct"] and out["failed"] == 0
    assert out["spans"] > 0 and abs(out["drift_us"]) < 1e4
    for m in program.SPAN_METRICS:
        assert out[m] is not None and out[m] >= 0, m
    assert out["exec.batch_device_ms"] is None
    split = out["request_split_ms"]
    assert split["requests"] > 0
    # batching + hand-off is the loop's own queue wait
    assert split["batching"] + split["handoff"] == pytest.approx(
        out["runtime.queue_wait_ms"], rel=0.05)
    assert out["sampling.sample_ms"] + out["sampling.layout_ms"] \
        <= out["sampling.prepare_ms"]
    assert 0.9 < split["named_share"] <= 1.05
    assert 0 <= split["handoff_behind_batch"] <= split["handoff"]
    # the engine's spans split the execution, next to nothing left over
    assert min(split["stage"], split["pass"], split["unstack"]) > 0
    assert 0 <= split["execute_unspanned"] <= 0.2 * split["execute"]
    assert out["compiles_in_window"] == 0
    assert isinstance(get_tracer(), NullTracer)


def test_trace_0_never_turns_the_program_tracer_on(peaks, monkeypatch):
    seen = []
    real = run.runner

    def watched(kind):
        base = real(kind)

        class Watched(base):
            def setup(self):
                seen.append(get_tracer())
                out = super().setup()
                seen.append(get_tracer())
                return out

            def window(self, seconds):
                seen.append(get_tracer())
                super().window(seconds)
                seen.append(get_tracer())

            def check(self, limits):
                seen.append(get_tracer())
                return super().check(limits)
        return Watched

    monkeypatch.setattr(run, "runner", watched)
    cell = tiny_cell("gcn-b2.flickr.minibatch", **MINI)
    res = run.execute(cell, SEED, 1.0, False, peaks)
    assert res["correct"]
    assert len(seen) == 5
    assert all(isinstance(t, NullTracer) for t in seen)

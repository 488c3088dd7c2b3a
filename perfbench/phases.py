#!/usr/bin/env python3
"""Where a cell's time goes, read from the program's own spans on the
device trace's clock: one traced run of a cell.

    python3 perfbench/phases.py --workload gcn-b2.flickr.minibatch \\
        --seed 7 --seconds 51

A run sets up as ``run.py`` does, then turns on the program's tracer
for the window inside a profiler session, with a clock anchor at the
window's start and end (``harness/program.py``).  It prints on standard
error the compiles inside the window, the anchors' drift, each program
span's mean, the device's idle time labelled by the program's and the
benchmark's spans and, for a mini-batch cell, a request's latency split
phase by phase; and last on standard output one JSON line of the same
numbers.  It checks the window's answers against the plain reference as
``run.py`` does.  Like ``run.py``, it refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")]

import run  # noqa: E402
from harness import common, program  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.common import mean, percentile, say  # noqa: E402


def measure(cell, seed: int, seconds: float, peaks: dict) -> dict:
    """Set up, run the traced window with the program's tracer on, check
    and reduce; returns the readings, the cell's accepted per-layer
    metrics among them."""
    import jax
    compiles = common.CompileClock()
    span = run.spans(True)
    drv = run.runner(cell.traffic["kind"])(cell, seed, seconds, span)
    log_dir = None
    prog = program.ProgramTracer()
    try:
        info = drv.setup()
        snap = compiles.snapshot()
        log_dir = tempfile.mkdtemp(prefix="phases-")
        prog.start()
        jax.profiler.start_trace(log_dir,
                                 profiler_options=run._profile_options())
        prog.anchor()
        with span("window"):
            drv.window(seconds)
        prog.anchor()
        jax.profiler.stop_trace()
        prog.stop()
        inwin = compiles.since(snap)
        counters = drv.counters()
        drv.release()
        cmp, _, failed = drv.check(cell.limits)
        path = tr.find_xplane(log_dir)
        dtrace, _ = tr.load(path, run.HOST_SPANS)
        spans, clock = program.mapped(prog.events,
                                      program.read_anchors(path))
        spans = program.in_window(spans, dtrace.window)
        out = {"correct": cmp.correct, "failed": failed,
               "compiles_in_window": inwin["compiles"],
               "window_s": dtrace.window_s, "busy_s": dtrace.busy_s(),
               "drift_us": None if clock is None else clock.drift_ns / 1e3,
               "spans": len(spans)}
        out.update(program.span_readings(spans))
        out["exec.batch_pass_span_ms"] = program.mean_ms(
            spans, "exec.batch_pass")
        out["exec.batch_device_ms"] = program.batch_device_ms(dtrace)
        out["idle_gaps"] = program.idle_gaps(dtrace, spans)
        ctx = run.Context(dtrace, counters, info, peaks)
        for m in cell.per_layer:
            got = run._reader(m["name"])(ctx)
            out[m["name"]] = got[0] if isinstance(got, tuple) else got
        if "late_s" in counters:
            out["late_ms"] = mean(counters["late_s"]) * 1e3
            out["request_split_ms"] = request_split(drv, spans, clock)
        return out
    finally:
        prog.stop()
        compiles.close()
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)


def request_split(drv, spans, clock) -> dict:
    """Mean of each phase of an answered request, in ms, from the moment
    it was due to its answer: the generator's lateness, then
    ``SamplingService.prepare`` (its two spans and the rest), the way
    to admission, batching, hand-off (and the part of it spent behind
    an earlier batch on the same overlay), the batch's execution (from
    the worker's start to ``execute_on``'s return): its
    ``exec.batch_stage``, ``exec.batch_pass`` and ``exec.batch_unstack``
    and the part no span covers; then response delivery up to the
    harness's answer stamp.  Beside them the mean latency and the share
    of it that the named phases hold, the unspanned part of the
    execution left out."""
    if clock is None:
        return {}
    names = ("sampling.sample", "sampling.layout", "serve.batching",
             "serve.handoff", "serve.respond", "serve.request")
    by: dict = {}
    for s in spans:
        rid = s.args.get("request")
        if rid is not None:
            by.setdefault(rid, {})[s.name] = s
    by = {rid: got for rid, got in by.items()
          if rid in drv.answers and all(n in got for n in names)}
    behind = _behind(by)
    execs = _batch_exec(by, spans)
    rows = []
    for i, req in enumerate(drv.requests[: len(drv.late_s)]):
        rid = req.request_id
        if rid not in by:
            continue
        got = by[rid]
        due = drv.due[rid]
        late, prep = drv.late_s[i], drv.prepare_s[i]
        ns = {n: got[n].dur for n in names}
        prepared = clock((due + late + prep) * 1e9)
        answered = clock(drv.answers[rid][0] * 1e9)
        h = got["serve.handoff"]
        ex = execs[(h.args.get("overlay"), h.args.get("batch"))]
        execute = got["serve.respond"].start - h.end
        rows.append({
            "late": late * 1e9,
            "sample": ns["sampling.sample"],
            "layout": ns["sampling.layout"],
            "prepare_rest": prep * 1e9 - ns["sampling.sample"]
            - ns["sampling.layout"],
            "to_admission": got["serve.request"].start - prepared,
            "batching": ns["serve.batching"],
            "handoff": ns["serve.handoff"],
            "handoff_behind_batch": behind[rid],
            "execute": execute,
            "stage": ex["exec.batch_stage"],
            "pass": ex["exec.batch_pass"],
            "unstack": ex["exec.batch_unstack"],
            "execute_unspanned": execute - sum(ex.values()),
            "respond_to_answer": answered - got["serve.respond"].start,
            "latency": (drv.answers[rid][0] - due) * 1e9})
    if not rows:
        return {}
    out = {k: mean([r[k] for r in rows]) * 1e-6 for k in rows[0]}
    named = ("late", "sample", "layout", "batching", "handoff", "stage",
             "pass", "unstack", "respond_to_answer")
    out["named_share"] = sum(out[k] for k in named) / out["latency"]
    out["requests"] = len(rows)
    out["p50_latency"] = percentile([r["latency"] for r in rows], 50) * 1e-6
    return out


EXEC = ("exec.batch_stage", "exec.batch_pass", "exec.batch_unstack")


def _batch_exec(by: dict, spans) -> dict:
    """Per batch ``(overlay, batch)``, the duration in ns of each of its
    ``EXEC`` spans: those on its overlay's thread between its
    hand-off's end and its responses' start.  An overlay's thread is
    the one whose ``exec.batch_stage`` most often opens first there."""
    window = {}
    for got in by.values():
        h = got["serve.handoff"]
        window[(h.args.get("overlay"), h.args.get("batch"))] = (
            h.end, got["serve.respond"].start)
    execs = sorted((s for s in spans if s.name in EXEC),
                   key=lambda s: s.start)
    starts = [s.start for s in execs]

    def inside(a, b):
        lo, hi = bisect.bisect_left(starts, a), bisect.bisect_right(starts, b)
        return [s for s in execs[lo:hi] if s.end <= b]

    votes = collections.Counter()
    for (overlay, _), (a, b) in window.items():
        first = next((s for s in inside(a, b)
                      if s.name == "exec.batch_stage"), None)
        if first is not None:
            votes[(overlay, first.tid)] += 1
    tid: dict = {}
    for (overlay, t), _ in votes.most_common():
        tid.setdefault(overlay, t)
    out = {}
    for key, (a, b) in window.items():
        got = dict.fromkeys(EXEC, 0)
        for s in inside(a, b):
            if s.tid == tid.get(key[0]):
                got[s.name] += s.dur
        out[key] = got
    return out


def _behind(by: dict) -> dict:
    """Per request, the part of its hand-off during which its overlay
    was still running an earlier batch (from that batch's start, its
    hand-off's end, to its last response), in ns."""
    busy: dict = {}
    for got in by.values():
        h, r = got["serve.handoff"], got["serve.respond"]
        key = (h.args.get("overlay"), h.args.get("batch"))
        s, e = busy.get(key, (h.end, r.end))
        busy[key] = (min(s, h.end), max(e, r.end))
    per: dict = {}
    for (overlay, batch), (s, e) in busy.items():
        per.setdefault(overlay, []).append((s, e, batch))
    for ivs in per.values():
        ivs.sort()
    starts = {o: [iv[0] for iv in ivs] for o, ivs in per.items()}
    out = {}
    for rid, got in by.items():
        h = got["serve.handoff"]
        ivs = per[h.args.get("overlay")]
        i = bisect.bisect_left(starts[h.args.get("overlay")], h.end) - 1
        tot = 0
        # an overlay runs its batches one after another: walk back
        # from the last one started before this hand-off ended
        while i >= 0 and ivs[i][1] > h.start:
            s, e, b = ivs[i]
            if b != h.args.get("batch"):
                tot += max(0, min(e, h.end) - max(s, h.start))
            i -= 1
        out[rid] = tot
    return out


def report(out: dict) -> None:
    say(f"compiles inside the window: {out['compiles_in_window']}")
    say(f"clock anchors: drift {out['drift_us']} us over the window; "
        f"{out['spans']} program spans in the window")
    for k, v in out.items():
        if k.endswith("_ms") and not isinstance(v, dict):
            say(f"{k}: {v}")
    idle = out["window_s"] - out["busy_s"]
    named = sum(s for label, s in out["idle_gaps"] if label != "none")
    say(f"device idle {idle:.3f} s of {out['window_s']:.3f} s; labelled "
        f"by a span: {100 * named / idle if idle > 0 else 0:.2f} %")
    for label, s in out["idle_gaps"]:
        say(f"  idle gaps labelled {label}: {s:.4f} s")
    for k, v in out.get("request_split_ms", {}).items():
        say(f"request {k}: {v}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = common.Cell(common.load_spec(), args.workload)
    common.configure_cache()
    import jax
    dev = jax.devices()[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind}")
    if dev.platform != "tpu":
        say(f"no TPU: refusing to run on {dev.platform}")
        return 2
    try:
        peaks = common.peaks_for(dev.device_kind)
    except KeyError as e:
        say(str(e))
        return 2
    t0 = time.perf_counter()
    out = measure(cell, args.seed, args.seconds, peaks)
    out["device"] = dev.device_kind
    say(f"run {time.perf_counter() - t0:.1f} s")
    report(out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

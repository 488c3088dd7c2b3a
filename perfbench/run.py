#!/usr/bin/env python3
"""The chip benchmark of the GraphAGILE overlay: one run of one cell.

    python3 perfbench/run.py --workload gcn-b2.flickr.full --seed 7 \\
        --seconds 51 --trace 0

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``perfbench/configs/<config>.json``, whose architecture's plain
reference and work count are ``perfbench/references/<arch>.py``) and a
traffic mix (``perfbench/traffic/<traffic>.json``, whose ``kind`` names
its driver, ``perfbench/harness/<kind>.py``); its limits are in
``perfbench/limits/<cell>.json`` and each per-layer metric is read by
``perfbench/metrics/<metric>.py``.  A run builds its data from
``--seed``, sets up (compiles, places, warms every shape), measures for
``--seconds``, checks what the window produced against the plain
reference and prints one JSON line last on standard output: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.  It refuses
to run (non-zero exit, no result) without a TPU it knows the peaks of.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")]

from harness import common  # noqa: E402
from harness.common import say  # noqa: E402


def spans(on: bool):
    """``name -> context manager``: a host span in the profiler's trace
    when tracing, nothing otherwise."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return lambda name: jax.profiler.TraceAnnotation(name)


def runner(kind: str):
    """The driver of a traffic kind: ``Driver`` in ``harness/<kind>.py``."""
    if not kind.isidentifier():
        raise ValueError(f"traffic kind {kind!r} is no module name")
    return importlib.import_module(f"harness.{kind}").Driver


def _reader(name: str):
    path = common.bench_file("metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a per-layer metric's reader may read."""

    def __init__(self, trace, counters: dict, setup: dict, peaks: dict):
        self.trace, self.counters = trace, counters
        self.setup, self.peaks = setup, peaks


HOST_SPANS = ("pass", "prepare", "submit", "idle_wait")


def _profile_options():
    """Device activity and the benchmark's host spans; no tracing of
    every Python call, which would slow the host it measures."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def execute(cell, seed: int, seconds: float, trace: bool, peaks: dict,
            t_start: float = T_START) -> dict:
    """Set up, measure, check and reduce one run; returns the result
    object (the last line of standard output)."""
    import jax
    clock = common.CompileClock()
    span = spans(trace)
    drv = runner(cell.traffic["kind"])(cell, seed, seconds, span)
    info = drv.setup()
    setup_s = time.perf_counter() - t_start
    say(f"set-up {setup_s:.3f} s: {info}; compile clock {clock.snapshot()}")
    snap = clock.snapshot()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(log_dir,
                                     profiler_options=_profile_options())
        with span("window"):
            drv.window(seconds)
        if trace:
            jax.profiler.stop_trace()
        inwin = clock.since(snap)
        say(f"compiles inside the window: {inwin['compiles']} "
            f"({inwin['compile']:.3f} s compiling, {inwin['trace']:.3f} s "
            f"tracing, {inwin['cache_hits']} persistent-cache hits)")
        devs = jax.devices()[: cell.chips]
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
        counters = drv.counters()
        _report_counters(counters)
        drv.release()
        gc.collect()
        t_chk = time.perf_counter()
        cmp, attempted, failed = drv.check(cell.limits)
        say(f"check {time.perf_counter() - t_chk:.3f} s")
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}
        result = {"correct": cmp.correct, "attempted": attempted,
                  "failed": failed}
        e2e = dict(drv.end_to_end(), setup_s=setup_s)
        say(f"end-to-end readings: {e2e}")
        if not trace:
            result["metrics"] = {
                m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end}
            result["device"] = device
        else:
            from harness import trace as tr
            t_red = time.perf_counter()
            dtrace, inventory = tr.load(tr.find_xplane(log_dir), HOST_SPANS)
            say(f"trace planes {inventory}")
            ctx = Context(dtrace, counters, info, peaks)
            result["metrics"] = {}
            for m in cell.per_layer:
                got = _reader(m["name"])(ctx)
                if got is None:
                    continue
                value, extra = got if isinstance(got, tuple) else (got, {})
                result["metrics"][m["name"]] = dict(
                    value=value, unit=m["unit"], **extra)
            device.update(busy_s=dtrace.busy_s(), window_s=dtrace.window_s)
            result["device"] = device
            result["breakdown"] = {"device_ops": dtrace.top_ops(10),
                                   "idle_gaps": dtrace.idle_gaps(10)}
            say(f"trace reduced in {time.perf_counter() - t_red:.3f} s")
        result["compared"] = cmp.as_json()
        for line in cmp.lines():
            say(line)
        return result
    finally:
        clock.close()
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)


def _report_counters(c: dict) -> None:
    """Earlier lines: what the window did, beside its metrics."""
    if "late_s" in c and c["late_s"]:
        late = c["late_s"]
        say(f"generator lateness: mean {sum(late) / len(late) * 1e3:.3f} ms"
            f", p95 {common.percentile(late, 95) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms over {len(late)} requests")
    say("window: " + ", ".join(
        f"{k} {v}" for k, v in c.items()
        if not isinstance(v, (list, dict))))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = common.Cell(common.load_spec(), args.workload)
    common.configure_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}; compile cache {common.CACHE_DIR}")
    if dev.platform != "tpu":
        say(f"no TPU: refusing to run on {dev.platform}")
        return 2
    if len(devs) < cell.chips:
        say(f"{args.workload} needs {cell.chips} chips, JAX sees {len(devs)}")
        return 2
    try:
        peaks = common.peaks_for(dev.device_kind)
    except KeyError as e:
        say(str(e))
        return 2
    result = execute(cell, args.seed, args.seconds, bool(args.trace), peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

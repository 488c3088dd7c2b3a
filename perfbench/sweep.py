#!/usr/bin/env python3
"""Find the knee of a mini-batch cell: the highest offered rate at which
the completed rate equals the offered rate, the backlog does not grow
across the window and no request is refused.  Runs a window at each
rate in one process (the graph, service and compiled buckets are
shared) and prints one JSON line per rate.  Not part of a benchmark
run; its result is written into the cell's traffic file by hand.

    python3 perfbench/sweep.py --workload gcn-b2.flickr.minibatch \\
        --rates 20,40,80,160 --seconds 15 --seed 5
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")]

from harness import common  # noqa: E402
from harness.common import mean, percentile, say  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated rps")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    common.configure_cache()
    import jax
    from harness.minibatch import Minibatch
    import run
    cell = common.Cell(common.load_spec(), args.workload)
    say(f"device {jax.devices()[0].device_kind}")
    drv = Minibatch(cell, args.seed, args.seconds, run.spans(False))
    clock = common.CompileClock()
    # Other rates draw other pools: each rate finds its own buckets.
    base = {k: v for k, v in cell.traffic.items() if k != "warm"}
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        drv.tr = dict(base, rate_rps=rate)
        drv.reset()
        if i == 0:
            drv.setup()
        else:
            drv.warm()
        snap = clock.snapshot()
        drv.window(args.seconds)
        compiles = clock.since(snap)["compiles"]
        c = drv.counters()
        lat = drv.latencies_s()
        q = max(len(c["backlog"]) // 4, 1)
        line = {
            "rate_rps": rate, "requests": c["requests"],
            "answered": c["answered"], "refused": c["refused"],
            "completed_rps": c["answered"] / c["window_s"],
            "p50_ms": percentile(lat, 50) * 1e3 if lat else None,
            "p95_ms": percentile(lat, 95) * 1e3 if lat else None,
            "backlog_first_quarter": mean(c["backlog"][:q]),
            "backlog_last_quarter": mean(c["backlog"][-q:]),
            "late_mean_ms": mean(c["late_s"]) * 1e3,
            "late_max_ms": max(c["late_s"]) * 1e3,
            "prepare_mean_ms": mean(c["prepare_s"]) * 1e3,
            "prepare_max_ms": max(c["prepare_s"]) * 1e3,
            "compiles_in_window": compiles,
            "batch_size": c["answered"] / c["batches"]
            if c["batches"] else None,
            "errors": c["errors"][:3],
        }
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings for the limits of a cell's ``correct``: on each seed, the
numbers a run compares, for the program (its timed path, after a short
window) and for the lower-precision control (the plain reference in
bfloat16 put in the program's place on the same inputs), each against
the fp32 reference at default and at highest matmul precision, all in
one process.  Not part of a benchmark run.

    python3 perfbench/calibrate.py --workload gcn-b2.flickr.full \\
        --seeds 11,12,13 --seconds 0 --out cal.jsonl
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")]

from harness import common  # noqa: E402
from harness.common import say  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window per seed (0: one pass or request batch)")
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    common.configure_cache()
    import jax
    import run
    cell = common.Cell(common.load_spec(), args.workload)
    dev = jax.devices()[0]
    say(f"device {dev.platform} {dev.device_kind}")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        drv = run.runner(cell.traffic["kind"])(cell, seed, args.seconds,
                                               run.spans(False))
        drv.setup()
        drv.window(args.seconds)
        drv.release()
        cmp, attempted, failed = drv.check(cell.limits)
        line = {"workload": args.workload, "seed": seed,
                "device": dev.device_kind, "attempted": attempted,
                "failed": failed, "program": cmp.values,
                "correct": cmp.correct,
                "readings": {f"{served}_vs_{prec}":
                             drv.reading(served, prec)
                             for served in ("program", "control")
                             for prec in ("default", "highest")},
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())

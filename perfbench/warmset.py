#!/usr/bin/env python3
"""The ``warm`` list of a mini-batch mix: for each bucket that a run of
``run_seconds`` at ``--rate`` reaches, the first pool index whose
request reaches it.  A run at a lower rate draws the start of that
pool, so the list found at a high rate serves every lower one.
Sampling and layout run on the host, so this needs no chip.  Not part
of a benchmark run; its output is written into the mix's file by hand.

    JAX_PLATFORMS=cpu python3 perfbench/warmset.py \\
        --workload gcn-b2.flickr.minibatch --rate 400
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")]

from harness import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="requests per second (default: the mix's)")
    args = ap.parse_args(argv)
    import run
    spec = common.load_spec()
    cell = common.Cell(spec, args.workload)
    if args.rate:
        cell.traffic = dict(cell.traffic, rate_rps=args.rate)
    drv = run.runner(cell.traffic["kind"])(cell, 0, spec["run_seconds"],
                                           run.spans(False))
    drv.build()
    drv._requests(drv.model, spec["run_seconds"])
    reps = drv.representatives()
    drv.release()
    print(json.dumps({"requests": len(drv.pool), "buckets": sorted(reps),
                      "warm": sorted(reps.values())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only table7,...]

Output: ``name,us_per_call,derived`` CSV rows per benchmark, where
``derived`` carries the paper-metric (speedup / bytes / predicted-TPU
latency) for that table.  Big graphs run at a labeled synthesis scale
(see benchmarks/common.py); latency *ratios* (the paper's ablation
claims) are scale-free.
"""
from __future__ import annotations

import argparse

from . import (fig14_order, fig15_fusion, fig16_overlap, roofline_report,
               table7_latency, table8_binary, table10_loh)

ALL = {
    "table7": table7_latency.run,
    "table8": table8_binary.run,
    "fig14": fig14_order.run,
    "fig15": fig15_fusion.run,
    "fig16": fig16_overlap.run,
    "table10": table10_loh.run,
    "roofline": roofline_report.run,
}


def main() -> None:
    from repro.engine import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small graphs only (CI smoke of the harness)")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    names = [n.strip() for n in args.only.split(",") if n.strip()] \
        or list(ALL)
    print("benchmark,name,us_per_call,derived")
    for n in names:
        ALL[n](quick=args.quick)


if __name__ == "__main__":
    main()

"""Serving benchmark: sequential Engine.serve vs batched OverlayPool.

  PYTHONPATH=src python benchmarks/bench_serve.py [--smoke]

Measures the traffic layer PR 2 added on top of the single-request
engine: the same request stream is served (a) one at a time by one
Engine and (b) by a K-overlay pool with dynamic batching — same
programs, one binary pass per batch.  Two traffic shapes:

  * ``same_key`` — one deployed (model, graph) pair queried repeatedly
    with fresh features (the batcher's best case: every flush is full);
  * ``mixed``    — four deployed pairs interleaved (batches form per
    key; cache-affinity routing spreads keys across overlays).

Both paths are warmed first (programs compiled, tile kernels jitted for
the shapes each path uses), so the timed pass measures steady-state
serving throughput.  Results land in ``BENCH_serve.json`` at the repo
root: throughput, p50/p99 latency, program-cache hit rate, batch
occupancy, and the batched/sequential speedup per traffic shape.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp  # noqa: E402

try:                                    # script: python benchmarks/bench_serve.py
    from common import provenance, verify_section
except ImportError:                     # module: python -m benchmarks.bench_serve
    from benchmarks.common import provenance, verify_section

from repro.core import graph as G  # noqa: E402
from repro.core.passes.partition import PartitionConfig  # noqa: E402
from repro.engine import (Engine, InferenceRequest,  # noqa: E402
                          enable_compile_cache)
from repro.runtime import Metrics, OverlayPool, ServeLoop  # noqa: E402
from repro.runtime.metrics import percentile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_graphs(smoke: bool, seed: int):
    if smoke:
        ga = G.random_graph(120, 480, seed=11 + seed).gcn_normalized()
        gb = G.random_graph(150, 600, seed=12 + seed).gcn_normalized()
        ga.feat_dim, ga.n_classes = 16, 4
        gb.feat_dim, gb.n_classes = 16, 4
        ga.name, gb.name = "SA", "SB"
    else:
        ga = G.synthesize("CI", seed=seed).gcn_normalized()
        gb = G.synthesize("CO", seed=seed).gcn_normalized()
    return ga, gb


def make_traffic(shape: str, n: int, ga, gb,
                 seed: int) -> List[InferenceRequest]:
    pairs = [("b1", ga)] if shape == "same_key" else \
        [("b1", ga), ("b6", gb), ("b7", ga), ("b3", gb)]
    reqs = []
    for i in range(n):
        m, g = pairs[i % len(pairs)]
        x = jnp.asarray(G.random_features(g, seed=1000 + seed + i))
        reqs.append(InferenceRequest(model=m, graph=g, features=x,
                                     request_id=f"{shape}{i}"))
    return reqs


def bench_sequential(geom, reqs, n_pes: int) -> dict:
    eng = Engine(geometry=geom, n_pes=n_pes)
    eng.serve(reqs)                       # warm: programs + tile kernels
    h0, n0 = eng.stats.cache_hits, eng.stats.requests
    t0 = time.perf_counter()
    resps = eng.serve(reqs)
    wall = time.perf_counter() - t0
    lats = [r.t_loc + r.t_loh for r in resps]
    return {
        "wall_s": round(wall, 6),
        "throughput_rps": round(len(reqs) / wall, 3),
        "p50_ms": round(percentile(lats, 50) * 1e3, 3),
        "p99_ms": round(percentile(lats, 99) * 1e3, 3),
        "cache_hit_rate": round(
            (eng.stats.cache_hits - h0) / (eng.stats.requests - n0), 6),
        "binary_passes": len(reqs),
    }


def bench_batched(geom, reqs, n_pes: int, n_overlays: int,
                  max_batch: int) -> dict:
    pool = OverlayPool(n_overlays=n_overlays, geometry=geom, n_pes=n_pes)
    # warm with the real traffic once: programs compiled, batched-shape
    # tile kernels jitted, affinity established
    pool.serve(reqs, max_batch=max_batch, max_wait_us=1e6)
    metrics = Metrics()
    loop = ServeLoop(pool, max_batch=max_batch, max_wait_us=1e6,
                     max_queue=4 * max_batch * max(1, n_overlays),
                     metrics=metrics)
    try:
        t0 = time.perf_counter()
        loop.serve(reqs)
        wall = time.perf_counter() - t0
    finally:
        loop.shutdown()
    snap = metrics.snapshot(max_batch=max_batch)["global"]
    return {
        "wall_s": round(wall, 6),
        "throughput_rps": round(len(reqs) / wall, 3),
        "p50_ms": snap["p50_latency_ms"],
        "p99_ms": snap["p99_latency_ms"],
        "cache_hit_rate": snap["cache_hit_rate"],
        "batch_occupancy": snap["batch_occupancy"],
        "binary_passes": snap["batches"],
    }


def run(smoke: bool, n_requests: int, n_overlays: int, max_batch: int,
        out_path: str, seed: int = 0) -> dict:
    geom = PartitionConfig(n1=32, n2=8) if smoke \
        else PartitionConfig(n1=256, n2=32)
    n_pes = 4 if smoke else 8
    ga, gb = make_graphs(smoke, seed)
    report: dict = {
        "benchmark": "bench_serve",
        "mode": "smoke" if smoke else "full",
        "requests_per_shape": n_requests,
        "overlays": n_overlays,
        "max_batch": max_batch,
        "provenance": provenance(seed),
        "traffic": {},
    }
    print("shape,path,wall_s,throughput_rps,p50_ms,p99_ms")
    for shape in ("same_key", "mixed"):
        reqs = make_traffic(shape, n_requests, ga, gb, seed)
        seq = bench_sequential(geom, reqs, n_pes)
        bat = bench_batched(geom, reqs, n_pes, n_overlays, max_batch)
        speedup = bat["throughput_rps"] / seq["throughput_rps"] \
            if seq["throughput_rps"] else 0.0
        report["traffic"][shape] = {
            "sequential": seq, "batched": bat,
            "batched_speedup": round(speedup, 3),
        }
        for path, r in (("sequential", seq), ("batched", bat)):
            print(f"{shape},{path},{r['wall_s']},{r['throughput_rps']},"
                  f"{r['p50_ms']},{r['p99_ms']}")
        print(f"{shape},speedup,{speedup:.3f}x,,,")
    # Static verification of every (model, graph) program the mixed
    # traffic exercises — semantic trajectory metrics, not wall time.
    report["verify"] = verify_section(
        Engine(geometry=geom, n_pes=n_pes),
        [("b1", ga), ("b6", gb), ("b7", ga), ("b3", gb)])
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {out_path}")
    return report


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small graphs + short stream (CI gate)")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per traffic shape")
    ap.add_argument("--overlays", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0,
                    help="offsets graph/feature seeds; recorded in the "
                         "report provenance")
    ap.add_argument("--out", default=os.path.join(ROOT,
                                                  "BENCH_serve.json"))
    args = ap.parse_args()
    n = args.requests if args.requests is not None \
        else (16 if args.smoke else 64)
    run(args.smoke, n, args.overlays, args.max_batch, args.out,
        seed=args.seed)


if __name__ == "__main__":
    main()

"""Mini-batch serving benchmark: per-subgraph compiles vs bucketed pool.

  PYTHONPATH=src python benchmarks/bench_sample.py [--smoke]

The workload is per-user ego-network inference on a power-law (RE-class)
graph: every request carries its own targets, target count, and fanouts,
so every sampled subgraph is topologically unique.  Two serving paths:

  * ``sequential_unbucketed`` — each subgraph compiled and executed
    exactly as sampled on one Engine.  Unique topology means a unique
    program-cache key per request: steady state still pays T_LoC every
    time (hit rate ~0).  This is what the pre-sampling repo would do.
  * ``bucketed_batched`` — the :class:`repro.sampling.SamplingService`
    path: subgraphs padded to power-of-two geometry buckets and shipped
    as runtime graph data, so the cache key collides per bucket, the
    Batcher coalesces users, and steady state replays compiled programs
    (hit rate ~1).

Both paths are warmed with a disjoint request stream (tile kernels +
batched executables jitted; for the sequential path programs can NOT
warm — that is the point).  Results land in ``BENCH_sample.json``:
p50/p99 latency, throughput, cache hit rate, bucket census, speedup,
plus seed/backend/CPU provenance (run-to-run variance attribution).
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
import numpy as np  # noqa: E402

try:                                   # script: python benchmarks/bench_sample.py
    from common import provenance, verify_section
except ImportError:                    # module: python -m benchmarks.bench_sample
    from benchmarks.common import provenance, verify_section

from repro.core import graph as G  # noqa: E402
from repro.core.passes.partition import PartitionConfig  # noqa: E402
from repro.engine import (Engine, InferenceRequest,  # noqa: E402
                          enable_compile_cache)
from repro.runtime.metrics import percentile  # noqa: E402
from repro.sampling import SamplingService, TargetRequest  # noqa: E402
from repro.sampling.sampler import sample_ego  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FANOUTS = [(6, 4), (4, 2), (6, 2)]


def make_graph(smoke: bool, seed: int):
    """RE-class power-law parent, duplicate draws folded into weights."""
    nv, ne = (466, 24000) if smoke else (2330, 240000)
    g = G.random_graph(nv, ne, seed=seed, degree="powerlaw", alpha=1.1,
                       dedupe=True)
    g.feat_dim, g.n_classes = (16, 5) if smoke else (64, 41)
    g.name = f"RE-class@{nv}"
    return g


def make_traffic(g, n: int, seed: int, tag: str) -> List[TargetRequest]:
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        t = rng.choice(g.n_vertices, size=int(rng.integers(1, 4)),
                       replace=False)
        reqs.append(TargetRequest(
            targets=[int(v) for v in t], model="b1",
            fanouts=FANOUTS[i % len(FANOUTS)],
            request_id=f"{tag}{i}", seed=seed * 10007 + i))
    return reqs


def bench_sequential(g, X, geom, n_pes, warm, reqs) -> dict:
    eng = Engine(geometry=geom, n_pes=n_pes, cache_capacity=8)

    def submit(tr: TargetRequest):
        ego = sample_ego(g, tr.targets, tr.fanouts, seed=tr.seed)
        sub = ego.graph.gcn_normalized()
        x = jnp.asarray(X[ego.vertices])
        r = eng.submit(InferenceRequest(model=tr.model, graph=sub,
                                        features=x,
                                        request_id=tr.request_id))
        return r.t_loc + r.t_loh

    for tr in warm:                    # jit tile kernels; programs can't warm
        submit(tr)
    c0, n0 = eng.stats.cache_hits, eng.stats.requests
    lats = []
    t0 = time.perf_counter()
    for tr in reqs:
        lats.append(submit(tr))
    wall = time.perf_counter() - t0
    return {
        "wall_s": round(wall, 6),
        "throughput_rps": round(len(reqs) / wall, 3),
        "p50_ms": round(percentile(lats, 50) * 1e3, 3),
        "p99_ms": round(percentile(lats, 99) * 1e3, 3),
        "cache_hit_rate": round(
            (eng.stats.cache_hits - c0) / (eng.stats.requests - n0), 6),
        "compiles": eng.stats.compiles,
    }


def bench_bucketed(g, X, geom, n_pes, n_overlays, max_batch, warm,
                   reqs) -> dict:
    svc = SamplingService(g, X, n_overlays=n_overlays, geometry=geom,
                          n_pes=n_pes, max_batch=max_batch,
                          max_wait_us=1e6)
    try:
        # programs + every power-of-two batch-shape executable per bucket
        svc.warm(warm)
        h0 = sum(e.stats.cache_hits for e in svc.pool.engines)
        n0 = sum(e.stats.requests for e in svc.pool.engines)
        t0 = time.perf_counter()
        resps = svc.serve(reqs)
        wall = time.perf_counter() - t0
        h1 = sum(e.stats.cache_hits for e in svc.pool.engines)
        n1 = sum(e.stats.requests for e in svc.pool.engines)
        lats = [r.t_loc + r.t_loh for r in resps]
        return {
            "wall_s": round(wall, 6),
            "throughput_rps": round(len(reqs) / wall, 3),
            "p50_ms": round(percentile(lats, 50) * 1e3, 3),
            "p99_ms": round(percentile(lats, 99) * 1e3, 3),
            "cache_hit_rate": round((h1 - h0) / (n1 - n0), 6),
            "mean_batch_size": round(
                float(np.mean([r.batch_size for r in resps])), 3),
            "buckets": svc.stats_snapshot()["buckets"],
        }
    finally:
        svc.shutdown()


def run(smoke: bool, n_requests: int, n_overlays: int, max_batch: int,
        out_path: str, seed: int = 0) -> dict:
    geom = PartitionConfig(n1=32, n2=8) if smoke \
        else PartitionConfig(n1=256, n2=32)
    n_pes = 4 if smoke else 8
    g = make_graph(smoke, seed)
    X = G.random_features(g, seed=seed + 1)
    warm = make_traffic(g, max(8, n_requests // 4), seed + 1, "warm")
    reqs = make_traffic(g, n_requests, seed + 2, "u")

    seq = bench_sequential(g, X, geom, n_pes, warm, reqs)
    bkt = bench_bucketed(g, X, geom, n_pes, n_overlays, max_batch, warm,
                         reqs)
    speedup = bkt["throughput_rps"] / seq["throughput_rps"] \
        if seq["throughput_rps"] else 0.0
    report = {
        "benchmark": "bench_sample",
        "mode": "smoke" if smoke else "full",
        "requests": n_requests,
        "overlays": n_overlays,
        "max_batch": max_batch,
        "graph": {"n_vertices": g.n_vertices, "n_edges": g.n_edges,
                  "profile": "powerlaw", "alpha": 1.1},
        "fanouts": [list(f) for f in FANOUTS],
        "provenance": provenance(seed),
        "sequential_unbucketed": seq,
        "bucketed_batched": bkt,
        "bucketed_speedup": round(speedup, 3),
    }
    print("path,wall_s,throughput_rps,p50_ms,p99_ms,cache_hit_rate")
    for path, r in (("sequential_unbucketed", seq),
                    ("bucketed_batched", bkt)):
        print(f"{path},{r['wall_s']},{r['throughput_rps']},"
              f"{r['p50_ms']},{r['p99_ms']},{r['cache_hit_rate']}")
    print(f"speedup,{speedup:.3f}x,,,,")
    # Static verification of the served model against the parent graph.
    report["verify"] = verify_section(
        Engine(geometry=geom, n_pes=n_pes), [("b1", g.gcn_normalized())])
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {out_path}")
    return report


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small graph + short stream (CI gate)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--overlays", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0,
                    help="graph/traffic seed; recorded in provenance")
    ap.add_argument("--out", default=os.path.join(ROOT,
                                                  "BENCH_sample.json"))
    args = ap.parse_args()
    n = args.requests if args.requests is not None \
        else (24 if args.smoke else 96)
    run(args.smoke, n, args.overlays, args.max_batch, args.out,
        seed=args.seed)


if __name__ == "__main__":
    main()

"""Live-graph benchmark: incremental update cost vs full recompilation.

  PYTHONPATH=src python benchmarks/bench_live.py [--smoke]

Measures the ``repro.livegraph`` subsystem three ways:

  * **update latency** — applying a delta of D edges to a deployed
    graph (incremental tile patch + version build + program rebind)
    against the do-nothing-clever baseline (mutate the COO, recompile
    through the full pipeline), at D = 1 / 100 / 10k (smoke: 1/16/64).
    Also reports the fraction of tiles retained by reference per delta.
  * **cutover under load** — a request stream served through a
    ``ServeLoop`` while deltas cut the graph over mid-stream: sustained
    QPS, response count (asserted: zero dropped), misroutes (asserted:
    zero — every response carries the version it was admitted on), and
    requests per version.

Results land in ``BENCH_live.json`` at the repo root.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

try:                                    # script: python benchmarks/bench_live.py
    from common import provenance, verify_section
except ImportError:                     # module: python -m benchmarks.bench_live
    from benchmarks.common import provenance, verify_section

from repro.core import graph as G  # noqa: E402
from repro.core.passes.partition import PartitionConfig  # noqa: E402
from repro.engine import (Engine, InferenceRequest,  # noqa: E402
                          enable_compile_cache)
from repro.livegraph import (GraphDelta, GraphVersionStore,  # noqa: E402
                             LiveGraphServer)
from repro.runtime import Metrics, OverlayPool, ServeLoop  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_graph(smoke: bool, seed: int) -> "G.Graph":
    if smoke:
        g = G.random_graph(180, 900, seed=21 + seed,
                           dedupe=True).gcn_normalized()
        g.feat_dim, g.n_classes = 16, 4
        g.name = "SL"
    else:
        g = G.synthesize("PU", seed=seed).gcn_normalized()
    return g


def make_delta(g: "G.Graph", n_edges: int, rng) -> GraphDelta:
    """Mixed churn: ~90% adds, ~10% removes of existing edges."""
    d = GraphDelta(g.n_vertices, feat_dim=g.feat_dim)
    n_rm = max(1, n_edges // 10) if g.n_edges else 0
    n_add = n_edges - n_rm
    for _ in range(n_add):
        u, v = map(int, rng.integers(0, g.n_vertices, 2))
        d.add_edge(u, v, float(rng.uniform(0.1, 1.0)))
    picks = rng.choice(g.n_edges, size=min(n_rm, g.n_edges),
                       replace=False)
    seen = set()
    for i in picks:
        pair = (int(g.src[i]), int(g.dst[i]))
        if pair not in seen:        # one removal kills the whole pair
            seen.add(pair)
            d.remove_edge(*pair)
    return d


def bench_updates(geom, g, model: str, delta_sizes: List[int],
                  n_pes: int, seed: int) -> dict:
    """Incremental patch + rebind vs full pipeline recompile, per size."""
    rng = np.random.default_rng(100 + seed)
    eng = Engine(geometry=geom, n_pes=n_pes)
    store = GraphVersionStore(g, geometry=geom)
    live = LiveGraphServer(store)
    x = np.asarray(G.random_features(g, seed=2))
    eng.submit(InferenceRequest(model, live, x))     # compile v0 once
    # Arm the sparsity-adaptive remapper: every content-only rebind
    # below then re-prices exactly the delta-patched tiles in place,
    # and the bound manifest's remap record times that incremental
    # pass (reported next to the patch+rebind latency).
    eng.remap(eng.compile(model, live))
    out = {}
    g_mut = g
    for size in delta_sizes:
        d = make_delta(g_mut, size, rng)
        g_next = d.apply_to(g_mut)
        compiles_before = eng.stats.compiles

        t0 = time.perf_counter()
        v = live.apply(d)                            # patch + cutover
        bound = eng.compile(model, live)             # rebind (no compile)
        t_inc = time.perf_counter() - t0

        cold = Engine(geometry=geom, n_pes=n_pes)
        t0 = time.perf_counter()
        cold.compile(model, g_next)                  # full pipeline
        t_full = time.perf_counter() - t0

        assert v.stats.structural_change or \
            eng.stats.compiles == compiles_before, \
            "content-only delta must hit the program cache"
        rec = (bound.manifest or {}).get("remap")
        out[str(size)] = {
            "incremental_ms": round(t_inc * 1e3, 3),
            "full_recompile_ms": round(t_full * 1e3, 3),
            "speedup": round(t_full / t_inc, 2) if t_inc else 0.0,
            "tiles_retained": v.stats.tiles_retained,
            "tiles_total": v.stats.tiles_after,
            "retention": round(v.stats.retention, 4),
            "structural_change": v.stats.structural_change,
            # incremental remap: only the delta-patched tiles re-priced
            "remap_ms": rec["remap_ms"] if rec else None,
            "tiles_repriced": len(v.stats.patched) if rec else None,
        }
        g_mut = g_next
    out["compiles_incremental_path"] = eng.stats.compiles
    return out


def bench_cutover_qps(geom, g, model: str, n_requests: int,
                      n_cutovers: int, delta_size: int, n_pes: int,
                      n_overlays: int, max_batch: int,
                      seed: int) -> dict:
    """Sustained serving through live cutovers; asserts zero dropped
    and zero misrouted responses."""
    rng = np.random.default_rng(200 + seed)
    store = GraphVersionStore(g, geometry=geom)
    metrics = Metrics()
    pool = OverlayPool(n_overlays=n_overlays, geometry=geom,
                       n_pes=n_pes, metrics=metrics)
    live = LiveGraphServer(store, metrics=metrics)
    feats = [np.asarray(G.random_features(g, seed=300 + seed + i))
             for i in range(4)]
    # warm: compile the structure + jit the batched shapes once
    warm = ServeLoop(pool, max_batch=max_batch, max_wait_us=1e6)
    try:
        warm.serve([InferenceRequest(model, live, feats[i % 4],
                                     request_id=f"w{i}")
                    for i in range(max_batch)])
    finally:
        warm.shutdown()

    loop = ServeLoop(pool, max_batch=max_batch, max_wait_us=1e6,
                     max_queue=8 * max_batch, metrics=metrics)
    cut_every = max(1, n_requests // (n_cutovers + 1))
    expected = {}
    g_mut = live.active.as_graph()
    t0 = time.perf_counter()
    try:
        for i in range(n_requests):
            rid = f"r{i}"
            loop.submit(InferenceRequest(model, live, feats[i % 4],
                                         request_id=rid))
            expected[rid] = live.active.vid
            if (i + 1) % cut_every == 0 and live.cutovers < n_cutovers:
                d = make_delta(g_mut, delta_size, rng)
                g_mut = d.apply_to(g_mut)
                live.apply(d)
        resps = loop.drain()
        wall = time.perf_counter() - t0
    finally:
        loop.shutdown()

    dropped = n_requests - len(resps)
    misrouted = sum(
        not r.graph_name.endswith(f"@v{expected[r.request_id]}")
        for r in resps)
    assert dropped == 0, f"{dropped} requests dropped across cutover"
    assert misrouted == 0, f"{misrouted} requests misrouted"
    snap = metrics.snapshot(max_batch=max_batch)
    return {
        "requests": n_requests,
        "cutovers": live.cutovers,
        "delta_size": delta_size,
        "wall_s": round(wall, 6),
        "throughput_rps": round(n_requests / wall, 3),
        "dropped": dropped,
        "misrouted": misrouted,
        "versions_reclaimed": snap["livegraph"]["versions_reclaimed"],
        "requests_per_version":
            snap["livegraph"]["requests_per_version"],
        "p50_ms": snap["global"]["p50_latency_ms"],
        "p99_ms": snap["global"]["p99_latency_ms"],
        "compiles": sum(e.stats.compiles for e in pool.engines),
    }


def run(smoke: bool, out_path: str, seed: int = 0) -> dict:
    geom = PartitionConfig(n1=32, n2=8) if smoke \
        else PartitionConfig(n1=256, n2=32)
    n_pes = 4 if smoke else 8
    model = "b1"
    delta_sizes = [1, 16, 64] if smoke else [1, 100, 10_000]
    n_requests = 24 if smoke else 128
    g = make_graph(smoke, seed)
    report: dict = {
        "benchmark": "bench_live",
        "mode": "smoke" if smoke else "full",
        "model": model,
        "graph": {"name": g.name, "n_vertices": g.n_vertices,
                  "n_edges": g.n_edges},
        "provenance": provenance(seed),
    }
    print("delta_size,incremental_ms,full_recompile_ms,speedup,retention")
    report["updates"] = bench_updates(geom, g, model, delta_sizes,
                                      n_pes, seed)
    for size in delta_sizes:
        r = report["updates"][str(size)]
        print(f"{size},{r['incremental_ms']},{r['full_recompile_ms']},"
              f"{r['speedup']},{r['retention']}")
    report["cutover"] = bench_cutover_qps(
        geom, make_graph(smoke, seed), model, n_requests,
        n_cutovers=2, delta_size=delta_sizes[1], n_pes=n_pes,
        n_overlays=2, max_batch=4, seed=seed)
    c = report["cutover"]
    print(f"cutover,{c['requests']} reqs,{c['throughput_rps']} rps,"
          f"dropped={c['dropped']},misrouted={c['misrouted']}")
    # Static verification of the live-handle program (the rebind path's
    # capacity-checked kernel legality) — semantic trajectory metrics.
    live = LiveGraphServer(GraphVersionStore(make_graph(smoke, seed),
                                             geometry=geom))
    report["verify"] = verify_section(
        Engine(geometry=geom, n_pes=n_pes), [(model, live)])
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {out_path}")
    return report


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small graph + small deltas (CI gate)")
    ap.add_argument("--seed", type=int, default=0,
                    help="offsets graph/feature seeds; recorded in the "
                         "report provenance")
    ap.add_argument("--out", default=os.path.join(ROOT,
                                                  "BENCH_live.json"))
    args = ap.parse_args()
    run(args.smoke, args.out, seed=args.seed)


if __name__ == "__main__":
    main()

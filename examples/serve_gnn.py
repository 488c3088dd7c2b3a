"""End-to-end driver: a GNN inference *service* on the overlay runtime.

  PYTHONPATH=src python examples/serve_gnn.py

The paper's core claim in action, at traffic scale: a pool of K fixed
compute substrates (virtual overlays) serves a STREAM of (model, graph)
requests — GCN, SAGE, GAT, SGC on different graphs — through
``repro.runtime``:

  * dynamic batching: concurrent requests that share a deployed
    (model, graph) pair are coalesced into ONE binary pass
    (features stacked on a batch axis — the mini-batch trick of
    CPU-FPGA serving systems);
  * cache-affinity routing: a repeated pair is routed to the overlay
    that already compiled its program (T_LoC = 0 on a hit), new pairs
    go to the least-loaded overlay — Algorithm 9's idle-PE rule at
    request granularity;
  * zero tile-kernel recompilation anywhere (the FPGA
    "no reconfiguration" property, XLA edition): kernels are keyed by
    tile geometry, never by model or graph.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp  # noqa: E402

from repro.core import ack  # noqa: E402
from repro.core import graph as G  # noqa: E402
from repro.core import reference as R  # noqa: E402
from repro.core import gnn_builders as B  # noqa: E402
from repro.core.passes.partition import PartitionConfig  # noqa: E402
from repro.engine import (InferenceRequest,  # noqa: E402
                          enable_compile_cache)
from repro.runtime import OverlayPool, ServeLoop  # noqa: E402

# Agreement with the fp32 reference, relative to the output's scale.  It
# is set by the engine's GEMM tiles, which run at the backend's default
# matmul precision: exact fp32 on a CPU, bf16-rounded operands on a TPU.
REL_TOL = 1e-2

# 24-request traffic mix over 4 deployed (model, graph) pairs; each pair
# is queried 6 times with fresh features — the common production shape.
# Topologies are the paper datasets (PU scaled down for one CPU core);
# deployed feature widths are capped so the per-key whole-program jit of
# the batched path stays in seconds — repeats then replay the compiled
# executable in milliseconds, which is the point of the demo.
PAIRS = [("b1", "CO"), ("b6", "CI"), ("b3", "CO"), ("b7", "PU")]
SCALE = {"CI": 0.5, "PU": 0.25}
FEAT_CAP = 128
REPEATS = 6
MAX_BATCH = 3


def build_requests():
    graphs = {}
    reqs = []
    i = 0
    for _ in range(REPEATS):
        for mname, gname in PAIRS:
            if gname not in graphs:   # one deployed graph per dataset
                g = G.synthesize(gname, scale=SCALE.get(gname, 1.0),
                                 seed=0).gcn_normalized()
                g.feat_dim = min(g.feat_dim, FEAT_CAP)
                graphs[gname] = g
            g = graphs[gname]
            x = jnp.asarray(G.random_features(g, seed=i))  # fresh features
            reqs.append(InferenceRequest(model=mname, graph=g, features=x,
                                         request_id=f"req{i}", seed=0))
            i += 1
    return reqs


def main() -> None:
    enable_compile_cache()
    # Fixed tile geometry = the overlay contract (one "bitstream"),
    # stamped out twice: a 2-overlay pool.
    pool = OverlayPool(n_overlays=2,
                       geometry=PartitionConfig(n1=256, n2=32))
    loop = ServeLoop(pool, max_batch=MAX_BATCH, max_wait_us=50_000,
                     max_queue=64)
    requests = build_requests()

    print(f"serving {len(requests)} requests (mixed models x mixed "
          f"graphs) on {len(pool)} overlays, dynamic batching "
          f"max_batch={MAX_BATCH}...\n")
    t0 = time.perf_counter()
    try:
        responses = loop.serve(requests)
    finally:
        loop.shutdown()
    wall = time.perf_counter() - t0

    for req, r in zip(requests, responses):
        m = B.build(req.model, req.graph, req.seed)
        err, rel = R.max_errors(
            r.output, R.run_reference_fp32(m, req.graph, req.features))
        assert rel < REL_TOL, (r.request_id, rel)
        tag = "HIT " if r.cache_hit else "miss"
        print(f"{r.request_id:5s}: {r.model_name:10s} on {r.graph_name:2s} "
              f"(|V|={req.graph.n_vertices:5d}) ov={r.overlay} "
              f"batch={r.batch_size} cache={tag} "
              f"T_LoC={r.t_loc * 1e3:6.1f}ms  "
              f"T_LoH={r.t_loh * 1e3:7.1f}ms  err={err:.1e}")

    snap = pool.metrics.snapshot(max_batch=MAX_BATCH)
    g = snap["global"]
    print(f"\ntotals: {g['requests']} requests in {wall * 1e3:.0f} ms "
          f"wall — {g['throughput_rps']:.1f} req/s, "
          f"p50={g['p50_latency_ms']:.0f} ms, "
          f"p99={g['p99_latency_ms']:.0f} ms")
    print(f"batching: {g['batches']} binary passes for {g['requests']} "
          f"requests (mean batch {g['mean_batch_size']:.1f}, occupancy "
          f"{g['batch_occupancy']:.0%}); program-cache hit rate "
          f"{g['cache_hit_rate']:.0%}")
    print("per-overlay:", json.dumps(pool.stats_snapshot()["overlays"],
                                     indent=1))
    n_kernels = len(ack.counter_snapshot())
    print(f"distinct tile kernels compiled across ALL requests: "
          f"{n_kernels} (bounded by tile geometry, not by #models, "
          f"#graphs or batch size — the overlay property)")


if __name__ == "__main__":
    main()

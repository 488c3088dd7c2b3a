"""Per-user mini-batch serving demo: sampled ego networks on the pool.

  PYTHONPATH=src python examples/minibatch_serve.py

The realistic heavy-traffic workload: every user asks for labels on a
few target vertices of one big deployed power-law graph.  The request
lifecycle (``repro.sampling``):

  sample  — seeded k-hop fanout sampling extracts the ego network a
            2-layer GNN actually reads (GraphSAGE-style caps);
  bucket  — the subgraph is padded into a power-of-two geometry bucket
            with inert zero padding, laid out canonically, and shipped
            as runtime graph DATA over the bucket's compiled program —
            so every user in a bucket shares one program-cache key;
  batch   — the runtime Batcher coalesces same-bucket users into ONE
            binary pass (topology AND features vmapped);
  overlay — cache-affinity routing picks the overlay that already
            compiled the bucket's program;
  un-pad  — target rows are sliced back out: logits[T, n_classes].

Steady state: program-cache hit rate ~1.0, pure T_LoH latency.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.core import graph as G  # noqa: E402
from repro.core.passes.partition import PartitionConfig  # noqa: E402
from repro.engine import enable_compile_cache  # noqa: E402
from repro.sampling import SamplingService, TargetRequest  # noqa: E402


def main() -> None:
    enable_compile_cache()
    # one deployed graph: RE-class power law, duplicate edges folded
    g = G.random_graph(466, 24000, seed=0, degree="powerlaw", alpha=1.1,
                       dedupe=True)
    g.feat_dim, g.n_classes = 16, 5
    g.name = "RE-class@466"
    X = G.random_features(g, seed=1)

    svc = SamplingService(
        g, X, n_overlays=2, geometry=PartitionConfig(n1=32, n2=8),
        n_pes=4, max_batch=4, max_wait_us=1e6)

    rng = np.random.default_rng(7)
    fanouts = [(6, 4), (4, 2), (6, 2)]

    def user(i: int) -> TargetRequest:
        targets = rng.choice(g.n_vertices,
                             size=int(rng.integers(1, 4)), replace=False)
        return TargetRequest(targets=[int(v) for v in targets],
                             model="b1", fanouts=fanouts[i % 3],
                             request_id=f"user{i}", seed=1000 + i)

    try:
        n_buckets = svc.warm([user(i) for i in range(16)])
        print(f"warmed {n_buckets} geometry buckets "
              f"(programs compiled, batch shapes traced)\n")

        h0 = sum(e.stats.cache_hits for e in svc.pool.engines)
        n0 = sum(e.stats.requests for e in svc.pool.engines)
        t0 = time.perf_counter()
        resps = svc.serve([user(i) for i in range(16, 40)])
        wall = time.perf_counter() - t0
        h1 = sum(e.stats.cache_hits for e in svc.pool.engines)
        n1 = sum(e.stats.requests for e in svc.pool.engines)

        for r in resps[:6]:
            pred = np.argmax(r.logits, axis=1)
            print(f"{r.request_id}: targets={r.targets.tolist()} -> "
                  f"classes {pred.tolist()}  [ego {r.n_vertices}V/"
                  f"{r.n_edges}E -> bucket {r.bucket}, "
                  f"batch={r.batch_size}, hit={r.cache_hit}]")
        print("...")

        snap = svc.stats_snapshot()
        print(f"\n{len(resps)} users in {wall * 1e3:.0f} ms "
              f"({len(resps) / wall:.0f} users/s); steady-state "
              f"program-cache hit rate {(h1 - h0) / (n1 - n0):.0%} "
              f"across {snap['distinct_buckets']} buckets")
        print("bucket census:", snap["buckets"])
    finally:
        svc.shutdown()


if __name__ == "__main__":
    main()

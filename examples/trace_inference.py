"""Traced end-to-end inference: compile + host-streaming execution +
serving traffic, exported as Perfetto trace-event JSON.

  PYTHONPATH=src python examples/trace_inference.py [--out trace.json]

Open the written file at https://ui.perfetto.dev — the `compile` track
shows the §6 pass pipeline, `h2d` the double-buffered shard staging,
`exec:host` the per-shard compute (watch the stage spans of shard j+1
overlap the compute span of shard j — the paper's T_LoC/T_LoH overlap,
made visible), and `serve` each request's life through the serving loop
(batching, hand-off to its overlay, response), with each batch's
`exec.batch_stage`, `exec.batch_pass` and `exec.batch_unstack` on its
overlay's thread.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import graph as G  # noqa: E402
from repro.core.passes.partition import PartitionConfig  # noqa: E402
from repro.engine import (Engine, InferenceRequest,  # noqa: E402
                          enable_compile_cache)
from repro.obs import enable_tracing  # noqa: E402
from repro.runtime import OverlayPool, ServeLoop  # noqa: E402


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="trace.json")
    args = ap.parse_args()

    tracer = enable_tracing()

    g = G.synthesize("CI", scale=0.1, seed=0).gcn_normalized()
    x = G.random_features(g, seed=1)
    engine = Engine(geometry=PartitionConfig(n1=32, n2=8))

    # Compile (per-pass spans on the `compile` track) and run the
    # partition-centric host-streaming path (stage/compute overlap on
    # the `h2d` / `exec:host` tracks).
    prog = engine.compile("b3", g)
    y = engine.run(prog, x, residency="host")
    print(f"host-streaming run: output {tuple(y.shape)}, "
          f"{engine.exec_stats.shards_streamed} shards streamed, "
          f"{engine.exec_stats.h2d_bytes} h2d bytes")

    # A little serving traffic: serve.batching -> serve.handoff ->
    # exec.batch_stage / exec.batch_pass / exec.batch_unstack ->
    # serve.respond spans through the ServeLoop (cache-hit instants on
    # the second wave).
    pool = OverlayPool(n_overlays=2, geometry=PartitionConfig(n1=32, n2=8))
    loop = ServeLoop(pool, max_batch=4)
    reqs = [InferenceRequest(model="b1", graph=g, features=x,
                             request_id=f"req{i}") for i in range(8)]
    resps = loop.serve(reqs)
    hits = sum(r.cache_hit for r in resps)
    print(f"served {len(resps)} requests ({hits} cache hits)")
    loop.shutdown()

    path = tracer.save(args.out)
    doc = json.load(open(path))
    print(f"\nwrote {path} ({len(doc['traceEvents'])} events) — open it "
          f"at https://ui.perfetto.dev")

    print("\nspan rollup (count / total ms):")
    summ = tracer.summary()
    for name, s in sorted(summ["spans"].items(),
                          key=lambda kv: -kv[1]["total_ms"])[:12]:
        print(f"  {name:<16} x{s['count']:<5} {s['total_ms']:.2f} ms")


if __name__ == "__main__":
    main()

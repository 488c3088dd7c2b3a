"""Quickstart: compile and run a GCN with the GraphAGILE Engine.

  PYTHONPATH=src python examples/quickstart.py
  (or `pip install -e .` once and drop the PYTHONPATH)

Builds a Cora-like synthetic graph, compiles a 2-layer GCN through the
full pipeline (order optimization -> fusion -> fiber-shard partitioning
-> kernel mapping/scheduling -> 128-bit binary), executes it **by
decoding that binary** on the Adaptive Computation Kernel, verifies
against the pure-jnp reference, then demonstrates the overlay contract:
the ``.gagi`` bundle saved here can be loaded by a *fresh* engine in a
later session and served with zero recompilation.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp  # noqa: E402

from repro.core import gnn_builders as B  # noqa: E402
from repro.core import graph as G  # noqa: E402
from repro.core import reference as R  # noqa: E402
from repro.core.perfmodel import predict_loh  # noqa: E402
from repro.engine import Engine, enable_compile_cache  # noqa: E402
from repro.obs import build_report  # noqa: E402

# Agreement with the fp32 reference, relative to the output's scale.  It
# is set by the engine's GEMM tiles, which run at the backend's default
# matmul precision: exact fp32 on a CPU, bf16-rounded operands on a TPU.
# The same bound as GCN's in chip_smoke.py (a v5e measured 2.7e-3 here).
REL_TOL = 5e-3


def main() -> None:
    enable_compile_cache()
    # Cora statistics, synthesized (offline container).
    g = G.synthesize("CO").gcn_normalized()
    x = jnp.asarray(G.random_features(g, seed=1))
    print(f"graph: |V|={g.n_vertices} |E|={g.n_edges} f={g.feat_dim}")

    model = B.build_gcn(g, hidden=16, n_layers=2)   # the paper's b1
    print("IR:", model.dump())

    engine = Engine()                               # the overlay
    prog = engine.compile(model, g)
    cr = prog.source                                # pass reports
    print(f"\ncompiled in {prog.t_loc * 1e3:.1f} ms "
          f"(the paper's T_LoC; hours for regenerate-the-bitstream flows)")
    print(f"order opt: {len(cr.order_report.exchanges)} exchanges, "
          f"complexity -{cr.order_report.reduction:.1%}")
    print(f"fusion: {cr.fusion_report.layers_before} -> "
          f"{cr.fusion_report.layers_after} layers")
    print(f"binary: {len(prog.binary)} bytes "
          f"({prog.instruction_count()} instructions x 128 bit)")
    print(f"predicted T_LoH on TPU v5e: {predict_loh(cr.program)*1e3:.3f} ms")

    y = engine.run(prog, x)                         # decodes the binary
    err, rel = R.max_errors(y, R.run_reference_fp32(model, g, x))
    print(f"\noverlay output {y.shape}, max |err| vs fp32 reference: "
          f"{err:.2e} ({rel:.2e} of the output scale)")
    assert rel < REL_TOL

    # Cost-model conformance: join the analytic per-layer predictions
    # with the wall time the executor just measured for this run.
    rep = build_report(prog, engine.exec_stats, residency="device")
    print(f"T_LoH predicted {rep.predicted_s * 1e3:.3f} ms vs measured "
          f"{rep.measured_s * 1e3:.3f} ms "
          f"(model error {rep.model_error_overall:.2f} -> "
          f"{rep.model_error_overall_calibrated:.2f} after calibrating "
          f"effective machine constants)")

    # The overlay contract on disk: binary + weights/graph manifest.
    path = os.path.join(os.path.dirname(__file__), "gcn_cora.gagi")
    prog.save(path)
    fresh = Engine()                                # a later session
    y2 = fresh.run(fresh.load(path), x)
    assert bool(jnp.array_equal(y, y2))
    print(f"saved {os.path.getsize(path)} B to {os.path.basename(path)}; "
          f"a fresh engine replayed it bit-identically (T_LoC = 0)")
    os.remove(path)
    print("OK")


if __name__ == "__main__":
    main()

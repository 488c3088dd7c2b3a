"""Full-graph out-of-core benchmark: device-resident vs partition-centric
vs placement-scheduled multi-device.

  PYTHONPATH=src python benchmarks/bench_fullgraph.py [--smoke] [--full]
                                                      [--devices N]

The workload is full-graph inference (GCN b1 / SAGE b3 / GAT b6) on a
power-law graph with community locality: vertex ids are assumed
renumbered so that most edges land within a few neighbouring N1-blocks
of the tile grid (the standard vertex-reordering/community structure of
deployed graphs — and the property the paper's fiber-shard partitioning
exploits: a destination shard's working set is its (j, k) sub-shard
tiles plus the FEW source sub-fibers they reference).

Execution modes over the SAME compiled binary:

  * ``device`` — every padded layer output device-resident.  The
    executor prices the run with its liveness-aware peak estimate and
    REFUSES when it exceeds ``resident_budget_bytes`` (recorded as the
    refusal, naming the first layer that busts the budget).
  * ``host``   — the partition-centric scheme (§6.5, Algorithms 6-8):
    features host-resident, one destination shard's working set staged
    at a time with double-buffered transfers.  Completes within budget
    and is bit-identical (asserted here at smoke size, tested at unit
    size in tests/test_fullgraph.py).
  * ``mesh``   — with ``--devices N``: destination shards LPT-placed on
    N (virtual host) devices, per-device shard schedules with halo
    sub-fiber exchange; records the compile-time placement loads,
    per-device load imbalance, and halo exchange volume.

The budget is placed between the streaming window and the device peak,
so the artifact shows a (graph size, budget) point where ONLY the
partitioned path completes.  Results land in ``BENCH_fullgraph.json``:
per-model device estimates (with and without interval liveness),
streaming latency, peak staged bytes, H2D traffic, shard counts, the
placement/mesh figures, plus seed/backend/CPU/device provenance.

Sizes: --smoke ~33k vertices (CI); default ~262k; --full ~1M vertices.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODELS = ["b1", "b3", "b6"]     # GCN, GraphSAGE-mean, GAT


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI size (~33k vertices)")
    ap.add_argument("--full", action="store_true",
                    help="~1M-vertex point (minutes on CPU)")
    ap.add_argument("--out", default=os.path.join(ROOT,
                                                  "BENCH_fullgraph.json"))
    ap.add_argument("--conformance-out",
                    default=os.path.join(ROOT, "CONFORMANCE.md"),
                    help="markdown ConformanceReport destination")
    ap.add_argument("--seed", type=int, default=0,
                    help="graph seed; recorded in provenance")
    ap.add_argument("--devices", type=int, default=1,
                    help="run the placement-scheduled multi-device path "
                         "on N devices (forces virtual host devices "
                         "when fewer are physically present)")
    ap.add_argument("--remap", dest="remap", action="store_true",
                    default=True,
                    help="race the sparsity-adaptive remapped binary "
                         "against the canonical one (default on)")
    ap.add_argument("--no-remap", dest="remap", action="store_false")
    return ap.parse_args(argv)


def force_device_count(n: int) -> None:
    """Must run BEFORE jax is imported: virtual host devices are an XLA
    boot flag, not a runtime knob."""
    if n > 1 and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = \
                f"{flags} --xla_force_host_platform_device_count={n}".strip()


def make_local_powerlaw(nv: int, ne: int, n1: int, seed: int):
    """Power-law degree profile + community locality: destination drawn
    with a heavy-tailed rank bias (hubs), source placed a geometric
    block-offset away — the post-reordering shape of real graphs.
    Duplicate draws are folded into one weighted edge (multi-edges are
    measurement artifacts; folding also keeps ELL tile widths honest)."""
    from repro.core import graph as G
    rng = np.random.default_rng(seed)
    dst = (nv * rng.random(ne) ** 1.4).astype(np.int64)   # hub bias
    delta = rng.geometric(4.0 / n1, ne) * rng.choice((-1, 1), ne)
    src = np.clip(dst + delta, 0, nv - 1)
    key = src * np.int64(nv) + dst
    uniq, counts = np.unique(key, return_counts=True)
    g = G.Graph(n_vertices=nv, src=(uniq // nv).astype(np.int32),
                dst=(uniq % nv).astype(np.int32),
                weight=counts.astype(np.float32),
                name=f"localpl:{nv}")
    return g.gcn_normalized()


def bench_remap(eng, prog, x, rep, reps: int, devices: int,
                check_bits: bool) -> dict:
    """Sparsity-adaptive remap pass (Dynasparse-style): re-encode the
    binary's aggregate kernels from the probe oracle + the calibrated
    conformance constants, then race the remapped program against the
    canonical one on the streaming path (min-of-reps both sides, same
    warm kernels).  Bit-identity of the remapped run is checked ACROSS
    residency paths — densified GEMM reassociates the per-edge sums, so
    vs the canonical baseline only the max-abs delta is recorded."""
    reps = max(reps, 3)
    y_base = np.asarray(eng.run(prog, x, residency="host"))
    base = []
    for _ in range(reps):
        t0 = time.perf_counter()
        eng.run(prog, x, residency="host")
        base.append(time.perf_counter() - t0)

    rprog = eng.remap(prog, report=rep, probe=True)
    record = rprog.manifest["remap"]
    y_re = np.asarray(eng.run(rprog, x, residency="host"))   # warm
    rlats = []
    for _ in range(reps):
        t0 = time.perf_counter()
        eng.run(rprog, x, residency="host")
        rlats.append(time.perf_counter() - t0)
    st = eng.exec_stats

    identical = []
    if check_bits:
        identical.append(bool(np.array_equal(
            np.asarray(eng.run(rprog, x)), y_re)))
    if devices > 1:
        identical.append(bool(np.array_equal(
            np.asarray(eng.run(rprog, x, mesh=devices)), y_re)))

    buckets: dict = {}
    for t in record["tiles"].values():
        b = min(int(t["density"] * 10), 9)
        key = f"{b / 10:.1f}-{(b + 1) / 10:.1f}"
        buckets.setdefault(key, {"spdmm": 0, "gemm": 0, "skip": 0})
        buckets[key][t["mode"]] += 1

    out = {
        "source": record["source"],
        "probe": record["probe"],
        "calibrated": record["calibrated"],
        "remap_ms": record["remap_ms"],
        "counts": record["counts"],
        "remapped_ops": record["remapped_ops"],
        "skipped_tile_ops": record["skipped_tile_ops"],
        "predicted_gain_s": round(record["predicted_gain_s"], 6),
        "baseline_host_s": round(min(base), 4),
        "remapped_host_s": round(min(rlats), 4),
        "remap_speedup": round(min(base) / min(rlats), 4),
        "max_abs_delta_vs_baseline": float(np.max(np.abs(y_re - y_base))),
        "remap_bit_identical": float(all(identical)) if identical else 1.0,
        "tiles_remapped_per_run": st.tiles_remapped,
        "tile_ops_by_mode": st.tile_ops_by_mode,
        "mode_share_by_density": buckets,
    }
    print(f"    remap: {record['counts']} -> "
          f"{out['remap_speedup']}x host speedup "
          f"(base {out['baseline_host_s']}s, remapped "
          f"{out['remapped_host_s']}s)", flush=True)
    return out


def run_model(name: str, eng, g, x, reps: int, check_bits: bool,
              devices: int, remap: bool = True) -> dict:
    from repro.engine import ResidentBudgetError
    from repro.obs import build_report, tracing
    ex = eng._executor
    eng.resident_budget_bytes = None
    prog = eng.compile(name, g, mesh=devices if devices > 1 else None)
    if prog.source is None:
        # program-cache hit returned a slim copy; conformance needs the
        # object-graph Program behind the analytic cost model
        prog = eng.compile(name, g,
                           mesh=devices if devices > 1 else None,
                           use_cache=False)
    dev_peak = ex.estimate_device_peak_bytes(prog, x.shape[1])
    rec: dict = {
        "model": name,
        "binary_bytes": prog.binary_bytes,
        "n_instructions": prog.instruction_count(),
        "device_peak_bytes_liveness": dev_peak,
        "device_peak_bytes_naive": ex.estimate_device_peak_bytes(
            prog, x.shape[1], assume_liveness=False),
    }
    if devices > 1:
        # Compile-time placement figures: LPT loads over the mesh and
        # the halo volume a targeted exchange would move per pass.
        pl = prog.manifest["placement"]
        mean = sum(pl["loads"]) / devices
        rec["placement"] = {
            "n_devices": devices,
            "loads": pl["loads"],
            "load_imbalance": (max(pl["loads"]) / mean) if mean else 1.0,
            "halo_bytes_total": pl["halo_bytes_total"],
        }

    # Warm-up streaming pass (jits the tile kernels) doubles as the
    # working-set probe: the measured double-buffered window + resident
    # weights is what the streaming path actually needs on device.
    y = np.asarray(eng.run(prog, x, residency="host"))
    window = ex.stats.peak_stage_bytes
    need = window + ex._static_bytes
    rec["host_window_bytes"] = window

    # Traced conformance pass (kernels now warm): per-layer measured
    # wall time joined against the analytic cost model, staging
    # bandwidth fitted from the stage spans, critical path from the
    # span DAG.  This is the run the `model_error` gate prices; its
    # per-tile profile feeds the report's density join.
    ex.profile_tiles = True
    with tracing() as tr:
        y_conf = np.asarray(eng.run(prog, x, residency="host"))
    ex.profile_tiles = False
    assert np.array_equal(y, y_conf)
    rep = build_report(prog, eng.exec_stats, residency="host",
                       events=tr.events())

    if devices > 1:
        t0 = time.perf_counter()
        y_mesh = np.asarray(eng.run(prog, x, mesh=devices))
        mesh_s = time.perf_counter() - t0
        st = eng.exec_stats
        rec["mesh"] = {
            "latency_s": round(mesh_s, 4),
            "bit_identical_to_host": bool(np.array_equal(y, y_mesh)),
            "halo_bytes": st.halo_bytes,
            "halo_gather_bytes": st.halo_gather_bytes,
            "halo_gap_bytes": max(0, st.halo_gather_bytes
                                  - st.halo_bytes),
            "peak_device_bytes": st.peak_device_bytes,
            "per_device_tile_ops": [d["tile_ops"]
                                    for d in st.per_device],
            "per_device_blocks": [d["blocks"] for d in st.per_device],
            "tile_op_imbalance": round(st.device_imbalance, 4),
        }
        # Fold the measured-vs-estimated halo gap of the mesh run into
        # the conformance report (the host pass has no exchange).
        # Signed: positive = the planner under-estimated the exchange,
        # negative = the all_gather moved less than the estimate.
        gap = int(st.halo_gather_bytes) - int(st.halo_bytes)
        rep.halo = {
            "estimated_bytes": int(st.halo_bytes),
            "gathered_bytes": int(st.halo_gather_bytes),
            "gap_bytes": gap,
            "gap_fraction": gap / st.halo_bytes if st.halo_bytes else 0.0,
        }

    overall = rep.model_error_overall
    overall_cal = rep.model_error_overall_calibrated
    rec["conformance"] = {
        "residency": rep.residency,
        "predicted_s": round(rep.predicted_s, 6),
        "measured_s": round(rep.measured_s, 6),
        "model_error": {k: round(v, 4)
                        for k, v in rep.model_error.items()},
        "model_error_calibrated": {
            k: round(v, 4)
            for k, v in rep.model_error_calibrated.items()},
        "model_error_overall": round(overall, 4),
        "model_error_overall_calibrated": round(overall_cal, 4),
        "calibration_gain": round(overall - overall_cal, 4),
        "scales": {k: round(v, 4) for k, v in rep.scales.items()},
        "calibrated_constants": {k: round(v, 1) for k, v
                                 in rep.calibrated_constants.items()},
        "halo": rep.halo,
        "makespan_us": rep.critical_path["makespan_us"],
        "critical_path_us": rep.critical_path["critical_path_us"],
    }
    rec["conformance_markdown"] = rep.to_markdown()

    if remap:
        rec["remap"] = bench_remap(eng, prog, x, rep, reps, devices,
                                   check_bits)

    if need >= dev_peak:
        # No gap (tiny graph / degenerate tiling): record and move on.
        rec["budget_bytes"] = None
        rec["no_gap"] = True
        return rec
    # The demonstration point: a budget the streaming path fits with
    # 2x headroom (capped below the device peak) and the resident path
    # cannot meet.
    budget = min(2 * need, (need + dev_peak) // 2)
    rec["budget_bytes"] = budget
    eng.resident_budget_bytes = budget
    try:
        eng.run(prog, x)
        rec["device_under_budget"] = {"completed": True}
    except ResidentBudgetError as e:
        rec["device_under_budget"] = {"completed": False,
                                      "refusal": str(e)}

    lats = []
    for _ in range(reps):                # under the budget: must fit
        t0 = time.perf_counter()
        y = np.asarray(eng.run(prog, x, residency="host"))
        lats.append(time.perf_counter() - t0)
    st = eng.exec_stats
    rec["host_under_budget"] = {
        "completed": True,
        "latency_s": round(min(lats), 4),
        "peak_stage_bytes": st.peak_stage_bytes,
        "h2d_bytes": st.h2d_bytes,
        "shards_streamed": st.shards_streamed,
        "peak_live_outputs": st.peak_live_outputs,
        "tile_ops": st.tile_ops,
    }
    if check_bits:                       # unbudgeted resident reference
        eng.resident_budget_bytes = None
        t0 = time.perf_counter()
        y_ref = np.asarray(eng.run(prog, x))
        rec["device_latency_s"] = round(time.perf_counter() - t0, 4)
        rec["bit_identical"] = bool(np.array_equal(y_ref, y))
    eng.resident_budget_bytes = None
    print(f"  {name}: device peak {dev_peak:,}B (naive "
          f"{rec['device_peak_bytes_naive']:,}B) vs streamed window "
          f"{window:,}B -> budget {budget:,}B — host "
          f"{rec['host_under_budget']['latency_s']}s, "
          f"{st.shards_streamed} shards", flush=True)
    return rec


def main(mode: str, out_path: str, seed: int, devices: int,
         conformance_out: str = None, remap: bool = True) -> None:
    from repro.engine import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    try:                        # script: python benchmarks/bench_fullgraph.py
        from common import provenance, verify_section
    except ImportError:         # module: python -m benchmarks.bench_fullgraph
        from benchmarks.common import provenance, verify_section

    from repro.core import graph as G
    from repro.core.passes.partition import PartitionConfig
    from repro.engine import Engine

    nv, avg_deg, f, c, n1, reps = {
        "smoke": (1 << 15, 8, 32, 8, 2048, 2),
        "default": (1 << 18, 8, 64, 16, 8192, 1),
        "full": (1 << 20, 8, 64, 16, 8192, 1),
    }[mode]
    devices = min(devices, jax.local_device_count())
    ne = nv * avg_deg
    t0 = time.perf_counter()
    g = make_local_powerlaw(nv, ne, n1, seed)
    g.feat_dim, g.n_classes = f, c
    x = jnp.asarray(G.random_features(g, seed=seed + 1))
    build_s = time.perf_counter() - t0
    print(f"graph: |V|={g.n_vertices:,} |E|={g.n_edges:,} f={f} "
          f"({build_s:.1f}s to build), devices={devices}", flush=True)

    eng = Engine(geometry=PartitionConfig(n1=n1, n2=min(f, 128)))
    results = [run_model(m, eng, g, x, reps,
                         check_bits=(mode == "smoke"), devices=devices,
                         remap=remap)
               for m in MODELS]
    report = {
        "benchmark": "fullgraph_out_of_core",
        "mode": mode,
        "graph": {"n_vertices": g.n_vertices, "n_edges": g.n_edges,
                  "feat_dim": f, "n_classes": c,
                  "generator": "localized_powerlaw"},
        "geometry": {"n1": n1, "n2": eng.geometry.n2,
                     "n_blocks": eng.geometry.n_blocks(g.n_vertices)},
        "devices": {"requested": devices,
                    "available": jax.local_device_count(),
                    "mesh_axes": ["dev"] if devices > 1 else None},
        "models": results,
        "provenance": provenance(seed),
    }
    only_streaming = all(
        not r.get("device_under_budget", {}).get("completed", True)
        and r.get("host_under_budget", {}).get("completed", False)
        for r in results)
    report["only_partitioned_path_completes"] = only_streaming
    # Static verification of every benched program (cache hits off the
    # warm engine — no recompiles) — semantic trajectory metrics.
    report["verify"] = verify_section(eng, [(m, g) for m in MODELS])
    # The per-model ConformanceReports ship as one markdown artifact
    # (CONFORMANCE.md); the JSON keeps only the gated summary numbers.
    sections = [f"# Cost-model conformance — fullgraph {mode}", ""]
    for r in results:
        md = r.pop("conformance_markdown", None)
        if md:
            sections += [f"# model {r['model']}", "", md, ""]
    if conformance_out and len(sections) > 2:
        with open(conformance_out, "w") as fp:
            fp.write("\n".join(sections))
        print(f"wrote {conformance_out}", flush=True)
    with open(out_path, "w") as fp:
        json.dump(report, fp, indent=1)
    print(f"wrote {out_path} (only_partitioned_path_completes="
          f"{only_streaming})", flush=True)


if __name__ == "__main__":
    args = parse_args()
    force_device_count(args.devices)     # before any jax import
    mode = "smoke" if args.smoke else ("full" if args.full else "default")
    main(mode, args.out, args.seed, args.devices,
         conformance_out=args.conformance_out, remap=args.remap)

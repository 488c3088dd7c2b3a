import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes, record memory/cost/collective analysis.

  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all   # full sweep

Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>.json (resumable: cells
with an existing artifact are skipped unless --force).
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, get_config  # noqa: E402
from repro.distributed import sharding as SH  # noqa: E402
from repro.distributed.zero import opt_state_specs  # noqa: E402
from repro.launch.hlo_analysis import analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.config import SHAPES, ModelConfig, ShapeCell  # noqa: E402
from repro.models.steps import (build_model, input_specs,  # noqa: E402
                                make_prefill_step, make_serve_step,
                                make_train_step)
from repro.optim import adamw_init  # noqa: E402

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")

# long_500k runs only for sub-quadratic-capable archs (DESIGN.md §4):
LONG_OK = {"gemma3-12b", "gemma3-27b", "hymba-1.5b", "xlstm-125m"}

# v5e constants for downstream roofline (recorded into artifacts)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def cell_supported(arch: str, shape: str) -> Optional[str]:
    """None if runnable; otherwise the reason for the skip."""
    if shape == "long_500k" and arch not in LONG_OK:
        return ("pure full-attention arch: 512k decode needs sub-quadratic "
                "attention / bounded state (see DESIGN.md §4)")
    return None


def _batch_shardings(cfg: ModelConfig, cell: ShapeCell, mesh, specs):
    out = {}
    for k, s in specs.items():
        nd = len(s.shape)
        if k == "pos":
            out[k] = NamedSharding(mesh, P())
        elif k in ("frames", "vision"):
            out[k] = NamedSharding(
                mesh, SH.batch_spec(mesh, s.shape[0], nd - 1))
        else:
            out[k] = NamedSharding(
                mesh, SH.batch_spec(mesh, s.shape[0], nd - 1))
    return out


def _cache_shardings(cfg: ModelConfig, cell: ShapeCell, mesh, cache_specs):
    b = cell.global_batch

    def leaf(path, s):
        names = [str(getattr(p, "key", getattr(p, "idx", p)))
                 for p in path]
        name = names[-1]
        shape = s.shape[1:]  # strip stacked layer dim
        if name in ("k", "v", "xk", "xv"):
            spec = SH.kv_cache_spec(b, mesh, shape[2], seq_len=shape[1])
        elif (name in ("c", "k_rope") and len(shape) == 3
                and shape[1] >= 4096):
            # MLA latent cache [B, S, R] (vs sLSTM scalar state [B, H, dh])
            spec = SH.latent_cache_spec(b, mesh)
        else:
            spec = SH.state_cache_spec(shape, mesh)
        return NamedSharding(mesh, P(None, *spec))

    return jax.tree_util.tree_map_with_path(leaf, cache_specs)


def run_cell(arch: str, shape: str, mesh_kind: str,
             overrides: Optional[Dict[str, Any]] = None,
             tag: str = "") -> Dict[str, Any]:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size
    moe_impl = "a2a" if cfg.is_moe else "dense"
    model = build_model(cfg, moe_impl=moe_impl, mesh=mesh)

    param_s = model.param_specs()
    param_sh = SH.param_shardings(mesh, param_s)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "n_devices": n_dev, "kind": cell.kind,
        "n_params": cfg.n_params(), "n_active_params": cfg.n_active_params(),
        "tokens": cell.tokens if cell.kind != "decode" else
        cell.global_batch,
        "overrides": overrides or {}, "tag": tag,
    }

    t0 = time.time()
    with jax.set_mesh(mesh):
        if cell.kind == "train":
            fn = make_train_step(model, cfg)
            ospec = jax.eval_shape(adamw_init, param_s)
            osh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                opt_state_specs(param_s, mesh),
                is_leaf=lambda x: isinstance(x, P))
            bspecs = input_specs(cfg, cell)
            bsh = _batch_shardings(cfg, cell, mesh, bspecs)
            lowered = jax.jit(
                fn, in_shardings=(param_sh, osh, bsh),
                donate_argnums=(0, 1)).lower(
                param_s, ospec, bspecs)
        elif cell.kind == "prefill":
            fn = make_prefill_step(model, cfg)
            bspecs = input_specs(cfg, cell)
            bsh = _batch_shardings(cfg, cell, mesh, bspecs)
            lowered = jax.jit(
                fn, in_shardings=(param_sh, bsh)).lower(param_s, bspecs)
        else:  # decode
            fn = make_serve_step(model, cfg)
            if cfg.encoder_decoder:
                cache_s = model.init_cache(
                    cell.global_batch, cfg.decoder_target_len,
                    zeros=False, cross_len=cell.seq_len)
            else:
                cache_s = model.init_cache(cell.global_batch,
                                           cell.seq_len, zeros=False)
            cache_sh = _cache_shardings(cfg, cell, mesh, cache_s)
            dspecs = input_specs(cfg, cell)
            tok_sh = NamedSharding(
                mesh, SH.batch_spec(mesh, cell.global_batch, 1))
            lowered = jax.jit(fn, in_shardings=(
                param_sh, cache_sh, tok_sh,
                NamedSharding(mesh, P())),
                donate_argnums=(1,)).lower(
                param_s, cache_s, dspecs["token"], dspecs["pos"])
        rec["lower_s"] = round(time.time() - t0, 2)

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "code_bytes": mem.generated_code_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "per_device_total": (mem.argument_size_in_bytes
                             + mem.output_size_in_bytes
                             + mem.temp_size_in_bytes
                             - mem.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):   # old jax: list of per-device dicts
        ca = ca[0] if ca else {}
    rec["xla_cost"] = {"flops": ca.get("flops", 0.0),
                       "bytes": ca.get("bytes accessed", 0.0)}
    t2 = time.time()
    hlo = compiled.as_text()
    rec["hlo_bytes"] = len(hlo)
    if os.environ.get("DRYRUN_SAVE_HLO", "1") == "1":
        hp = artifact_path(arch, shape, mesh_kind, tag).replace(
            ".json", ".hlo.gz")
        with gzip.open(hp, "wt") as f:
            f.write(hlo)
    costs = analyze(hlo, n_dev)
    rec["analyze_s"] = round(time.time() - t2, 2)
    rec["analysis"] = {
        "flops_per_device": costs.flops,
        "hbm_bytes_per_device": costs.hbm_bytes,
        "collective_bytes_per_device": costs.collective_bytes,
        "total_collective_bytes_per_device":
            costs.total_collective_bytes,
        "unknown_trip_whiles": costs.unknown_trip_whiles,
    }
    # roofline terms (seconds)
    rec["roofline"] = {
        "compute_s": costs.flops / PEAK_FLOPS,
        "memory_s": costs.hbm_bytes / HBM_BW,
        "collective_s": costs.total_collective_bytes / ICI_BW,
    }
    dom = max(rec["roofline"], key=rec["roofline"].get)
    rec["roofline"]["dominant"] = dom
    return rec


def artifact_path(arch, shape, mesh_kind, tag="") -> str:
    os.makedirs(ART_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    return os.path.join(
        ART_DIR, f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=[None] + list(SHAPES))
    ap.add_argument("--mesh", default=None, choices=[None, "single",
                                                     "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.mesh] if args.mesh else ["single", "multi"]

    results = []
    for arch in archs:
        for shape in shapes:
            reason = cell_supported(arch, shape)
            for mesh_kind in meshes:
                path = artifact_path(arch, shape, mesh_kind)
                if reason:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": mesh_kind, "skipped": reason}
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(f"[skip] {arch} {shape} {mesh_kind}: {reason}")
                    continue
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {arch} {shape} {mesh_kind}")
                    continue
                print(f"[run] {arch} {shape} {mesh_kind} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, mesh_kind)
                    rec["status"] = "ok"
                    print(f"  ok: lower={rec['lower_s']}s "
                          f"compile={rec['compile_s']}s "
                          f"mem/dev={rec['memory']['per_device_total']/2**30:.2f}GiB "
                          f"dominant={rec['roofline']['dominant']}",
                          flush=True)
                except Exception as e:  # record failures, keep sweeping
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "status": "error", "error": str(e)[:2000],
                           "trace": traceback.format_exc()[-4000:]}
                    print(f"  ERROR: {str(e)[:300]}", flush=True)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                results.append(rec)
    print(f"done ({len(results)} cells run)")


if __name__ == "__main__":
    main()

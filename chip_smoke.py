#!/usr/bin/env python3
"""Smoke test of the GraphAGILE overlay on a TPU, through its user paths.

    python chip_smoke.py                # one chip: phases 1-5
    python chip_smoke.py --devices 4    # four chips: phase 6 only

Synthesizes the paper's Flickr graph (FL: 89,250 vertices, ~990k edges
with self loops, 500 features) from ``--seed`` at its published size
and runs, in one process:

  1. device      — what JAX reports; anything but a TPU exits non-zero;
  2. full graph  — ``Engine().compile`` + ``engine.run`` of GAT (b6) and
                   GCN (b2) against the fp32 reference;
  3. streaming   — b2 again with ``residency="host"`` under a residency
                   budget below the device-resident estimate;
  4. serving     — an ``OverlayPool`` + ``ServeLoop`` answering b2
                   requests with fresh features in batches (the jitted
                   ``run_batch`` pass);
  5. pallas      — b2 on ``Engine(backend="pallas")`` with compiled
                   (not interpreted) kernels;
  6. mesh        — with ``--devices 4`` only: b2 placed over four chips
                   (``mesh=4``) against one chip and the reference.

Every output is compared with ``repro.core.reference`` in fp32 and held
to the per-model tolerance below; a failed comparison raises, so the
process exits non-zero.  Wall times printed here are smoke timing, not
metrics.  The last line of standard output is a JSON object naming the
device.  Compiled executables persist in JAX's compilation cache (see
``repro.engine.compile_cache``), so a second run compiles less.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import gnn_builders as B  # noqa: E402
from repro.core import graph as G  # noqa: E402
from repro.core import reference as R  # noqa: E402
from repro.core.passes.partition import PartitionConfig  # noqa: E402
from repro.engine import (BinaryExecutor, Engine,  # noqa: E402
                          InferenceRequest, enable_compile_cache)
from repro.runtime import OverlayPool, ServeLoop  # noqa: E402

# Max |engine - reference| over max |reference|, per model.  The reference
# runs every matmul in fp32 ("highest"); the engine's GEMM tiles run at
# the chip's default matmul precision, which rounds fp32 operands to bf16
# (8-bit mantissa).  That rounding, not the aggregation (exact fp32 on the
# VPU), is what these bound; a v5e measured 1.85e-3 (b2) and 4.42e-3 (b6)
# at seed 0, and each bound leaves a little over twice that.
TOLERANCES = {"b2": 5e-3, "b6": 1e-2}

# Phase 4: six requests in batches of three (each padded to the engine's
# batch bucket of four lanes), so the cold batch compiles the batched
# pass and the warm one replays it.
N_REQUESTS, MAX_BATCH = 6, 3

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """What the smoke runs.  The defaults are the chip run; tests pass a
    small graph, a small geometry and ``interpret=True`` to rehearse
    every phase on the CPU."""

    scale: float = 1.0                 # of Flickr's |V| and |E|
    seed: int = 0
    geometry: Optional[PartitionConfig] = None   # None: the auto geometry
    # Phase 3: below b2's device-resident estimate, so the pass streams,
    # and above its largest double-buffered shard window; None takes
    # nine tenths of the estimate.  (Over ELL planes the two were 6.47
    # and 4.61 GB at Flickr size; SUM aggregation stages edge lists,
    # which shrink both.)
    host_budget_bytes: Optional[int] = None
    interpret: bool = False            # Pallas interpret mode (CPU only)


class CompileClock:
    """Seconds JAX spends tracing/lowering and compiling (backend compile,
    including loads from the persistent cache), and persistent-cache
    hits, summed from JAX's own monitoring events."""

    def __init__(self) -> None:
        self.totals = {"compile": 0.0, "trace": 0.0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        kind = _COMPILE_EVENTS.get(event)
        if kind is not None:
            self.totals[kind] += secs

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self.totals["cache_hits"] += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def timed(self, fn):
        """(result, wall seconds, compile-clock deltas) of ``fn()``, the
        wall time ending when every output is ready."""
        before = dict(self.totals)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
        return out, wall, {k: self.totals[k] - before[k] for k in before}


def _timing(label: str, wall: float, clk: dict) -> str:
    return (f"{label} {wall:.3f} s (compile {clk['compile']:.3f} s, "
            f"trace {clk['trace']:.3f} s, persistent-cache hits "
            f"{clk['cache_hits']})")


class SmokeFailure(RuntimeError):
    """A phase's output or behaviour is not what the overlay promises."""


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _memory(dev) -> str:
    """The device's allocator counters, as the runtime reports them."""
    stats = dev.memory_stats() or {}
    return ", ".join(
        f"{k} {stats[k] if k in stats else 'not reported'}"
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"))


def _check(phase: str, model: str, y, y_ref, shape,
           against: str = "fp32 reference") -> None:
    """Hold ``y`` to ``y_ref`` within the model's tolerance."""
    _require(tuple(y.shape) == tuple(shape),
             f"{phase}: output shape {y.shape}, expected {shape}")
    _require(bool(jnp.all(jnp.isfinite(y))), f"{phase}: non-finite output")
    err, rel = R.max_errors(y, y_ref)
    tol = TOLERANCES[model]
    _say(phase, f"{model} vs {against}: max-abs {err:.6e}, "
                f"max-relative {rel:.6e} (tolerance {tol:g})")
    _require(rel <= tol, f"{phase}: {model} disagrees with the {against} "
                         f"({rel:.3e} > {tol:g})")


class Smoke:
    """One smoke run: the config, the synthesized graph and features,
    the compile clock, and the fp32 reference per model (jitted once, so
    each further request costs one execution)."""

    def __init__(self, cfg: SmokeConfig, clock: CompileClock) -> None:
        self.cfg, self.clock = cfg, clock
        t0 = time.perf_counter()
        self.g = G.synthesize("FL", scale=cfg.scale,
                              seed=cfg.seed).gcn_normalized()
        self.x = jnp.asarray(G.random_features(self.g, seed=cfg.seed + 1))
        self.shape = (self.g.n_vertices, self.g.n_classes)
        self._refs = {}
        g = self.g
        _say("setup", f"{g.name}: |V|={g.n_vertices} |E|={g.n_edges} "
                      f"features={g.feat_dim} classes={g.n_classes} "
                      f"({time.perf_counter() - t0:.3f} s)")

    def reference(self, model: str, x):
        fn = self._refs.get(model)
        if fn is None:
            m, g = B.build(model, self.g, self.cfg.seed), self.g
            fn = self._refs[model] = jax.jit(
                lambda x: R.run_reference_fp32(m, g, x))
        return fn(x)

    def timed(self, phase: str, label: str, fn):
        out, wall, clk = self.clock.timed(fn)
        _say(phase, "smoke timing (not a metric): "
                    + _timing(label, wall, clk))
        return out

    # -- phases ------------------------------------------------------ #
    def full_graph(self, model: str, warm: bool):
        """Phase 2 for one model: compile, run (cold, then warm when
        asked), compare with the reference."""
        phase = f"phase 2 {model}"
        cfg, x = self.cfg, self.x
        eng = Engine(geometry=cfg.geometry)
        prog = self.timed(phase, "software compile (T_LoC)",
                          lambda: eng.compile(model, self.g, seed=cfg.seed))
        pg = prog.pgraph
        _say(phase, f"program: {prog.instruction_count()} instructions, "
                    f"{pg.n_blocks} row blocks, "
                    f"{sum(len(t) for t in pg.tiles.values())} ELL tiles, "
                    f"{pg.tile_bytes()} tile bytes, geometry {pg.config}")
        y = self.timed(phase, "cold run", lambda: eng.run(prog, x))
        if warm:
            y2 = self.timed(phase, "warm run", lambda: eng.run(prog, x))
            _require(bool(jnp.array_equal(y, y2)), f"{phase}: runs differ")
        est = BinaryExecutor().estimate_device_peak_bytes(prog, x.shape[1])
        _say(phase, f"device memory: {_memory(jax.devices()[0])}; "
                    f"estimate_device_peak_bytes {est}")
        _say(phase, f"tile ops {eng.exec_stats.tile_ops}, by mode "
                    f"{eng.exec_stats.tile_ops_by_mode}")
        y_ref = self.timed(phase, "fp32 reference (compile + run)",
                           lambda: self.reference(model, x))
        _check(phase, model, y, y_ref, self.shape)
        return eng, prog, y, y_ref

    def host_streaming(self, prog, y_dev, y_ref) -> None:
        phase = "phase 3"
        est = BinaryExecutor().estimate_device_peak_bytes(
            prog, self.x.shape[1])
        budget = self.cfg.host_budget_bytes or est * 9 // 10
        _require(budget < est, f"{phase}: budget {budget} >= estimate {est}")
        eng = Engine(geometry=self.cfg.geometry,
                     resident_budget_bytes=budget)
        y = self.timed(phase, "host-streaming run",
                       lambda: eng.run(prog, self.x, residency="host"))
        st = eng.exec_stats
        _say(phase, f"budget {budget} bytes vs device-resident estimate "
                    f"{est}: {st.shards_streamed} shards streamed, "
                    f"{st.h2d_bytes} bytes host->device, staged window "
                    f"peak {st.peak_stage_bytes}")
        _require(st.shards_streamed > 0, "host streaming did not engage")
        _check(phase, "b2", y, y_ref, self.shape)
        _check(phase, "b2", y, y_dev, self.shape,
               against="phase 2 device-resident output")

    def serving(self, eng) -> None:
        """Phase 4: batched serving over the engine that already holds
        the b2 program, so the requests hit its program cache."""
        phase = "phase 4"
        cfg, g = self.cfg, self.g
        pool = OverlayPool(engines=[eng])
        # Batches close on size only (no deadline flush), so every
        # batched pass carries max_batch requests.
        loop = ServeLoop(pool, max_batch=MAX_BATCH, max_wait_us=600e6)
        # Request features stay on the host until batched, as a
        # client's would.
        reqs = [InferenceRequest(model="b2", graph=g, seed=cfg.seed,
                                 features=G.random_features(g, seed=100 + i),
                                 request_id=f"req{i}")
                for i in range(N_REQUESTS)]
        try:
            for i in range(0, len(reqs), MAX_BATCH):
                def submit(batch=reqs[i:i + MAX_BATCH]):
                    for r in batch:     # the last submit fills the batch
                        loop.submit(r)  # and runs it (one overlay: inline)
                self.timed(phase, "cold batch" if i == 0 else "warm batch",
                           submit)
            resps = loop.drain()
        finally:
            loop.shutdown()
        _say(phase, f"device memory: {_memory(jax.devices()[0])}")
        _require(len(resps) == len(reqs),
                 f"{len(resps)} responses for {len(reqs)} requests")
        for req, r in zip(reqs, resps):
            _require(r.request_id == req.request_id,
                     f"response {r.request_id} for request "
                     f"{req.request_id}")
            _require(r.cache_hit, f"{r.request_id}: program cache miss")
            _say(phase, f"{r.request_id}: batch of {r.batch_size}")
            _check(phase, "b2", r.output,
                   self.reference("b2", req.features), self.shape)
        _require(max(r.batch_size for r in resps) > 1,
                 "no batched pass ran")

    def pallas(self, prog, y_ref) -> None:
        phase = "phase 5"
        interpret = self.cfg.interpret
        eng = Engine(geometry=self.cfg.geometry, backend="pallas",
                     interpret=interpret)
        y = self.timed(phase, "cold run", lambda: eng.run(prog, self.x))
        st = eng.exec_stats
        _say(phase, f"interpret={interpret}, tile ops by mode "
                    f"{st.tile_ops_by_mode}, pallas->xla fallbacks "
                    f"{st.pallas_fallbacks}")
        _require(st.pallas_fallbacks == 0,
                 "b2 must run on Pallas kernels only")
        _require((st.tile_ops_by_mode or {}).get("spdmm", 0) > 0,
                 "no SpDMM tile ran")
        if not interpret:
            from repro.kernels import ops
            t = max((t for ts in prog.pgraph.tiles.values() for t in ts),
                    key=lambda t: t.width)
            cfg = prog.pgraph.config
            text = jax.jit(ops.spdmm).lower(
                jax.ShapeDtypeStruct(t.cols.shape, jnp.int32),
                jax.ShapeDtypeStruct(t.vals.shape, jnp.float32),
                jax.ShapeDtypeStruct((cfg.n1, cfg.n2), jnp.float32),
            ).compile().as_text()
            custom = "tpu_custom_call" in text
            _say(phase, f"SpDMM tile {t.cols.shape} compiles to "
                        f"tpu_custom_call: {custom}")
            _require(custom, "SpDMM did not lower to a Mosaic kernel")
        _check(phase, "b2", y, y_ref, self.shape)

    def mesh(self, n_devices: int) -> None:
        phase = f"phase 6 mesh={n_devices}"
        x = self.x
        eng = Engine(geometry=self.cfg.geometry)
        prog = eng.compile("b2", self.g, seed=self.cfg.seed, mesh=n_devices)
        self.timed(phase, "cold run",
                   lambda: eng.run(prog, x, mesh=n_devices))
        y = self.timed(phase, "warm run",
                       lambda: eng.run(prog, x, mesh=n_devices))
        st = eng.exec_stats
        for d, dev in enumerate(jax.devices()[:n_devices]):
            per = (st.per_device or [{}] * n_devices)[d]
            _say(phase, f"device {d} ({dev}): {_memory(dev)}; tile ops "
                        f"{per.get('tile_ops')}, row blocks "
                        f"{per.get('blocks')}")
        _say(phase, f"halo all_gather bytes {st.halo_gather_bytes}, "
                    f"imbalance {st.device_imbalance:.3f}")
        y_one = self.timed(phase, "one-chip run", lambda: eng.run(prog, x))
        _check(phase, "b2", y, self.reference("b2", x), self.shape)
        _check(phase, "b2", y, y_one, self.shape, against="one-chip output")


def run(cfg: SmokeConfig, n_devices: int = 1) -> None:
    """Phases 2-5 (``n_devices == 1``) or phase 6 alone (more devices)."""
    clock = CompileClock()
    try:
        smoke = Smoke(cfg, clock)
        if n_devices > 1:
            smoke.mesh(n_devices)
            return
        # GAT first: its tiles leave the device with its program, so
        # b2's placement (kept for phases 3-5) never shares it.  Only b2
        # repeats its run warm: GAT's eager pass is the costliest.
        smoke.full_graph("b6", warm=False)
        gc.collect()
        eng, prog, y, y_ref = smoke.full_graph("b2", warm=True)
        smoke.host_streaming(prog, y, y_ref)
        smoke.serving(eng)
        smoke.pallas(prog, y_ref)
    finally:
        clock.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}; compile cache {cache_dir}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; refusing to run on "
              f"{dev.platform}", file=sys.stderr)
        return 1
    if len(devs) < args.devices:
        print(f"chip_smoke: --devices {args.devices} but JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    run(SmokeConfig(seed=args.seed), n_devices=args.devices)
    print(f"smoke timing (not a metric): all phases "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

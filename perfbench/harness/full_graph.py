"""Closed loop of whole-graph passes (traffic kind ``full_graph``).

Set-up compiles the program (T_LoC), places its tiles on the device and
runs one pass, so every executable the window uses is compiled or
loaded from the persistent cache before it starts.  The mix's
``residency`` (``"device"`` by default, or ``"host"``: features in host
memory, tiles streamed shard by shard) and ``resident_budget_bytes``
(the program's device budget; none by default) are handed to the
program as they stand.  The window runs
``Engine.run`` passes back to back, each ending in
``block_until_ready``, and ends when the first pass that ends past
``--seconds`` completes: ``pass_ms`` is the window over the passes in
it.  Every pass's output is then compared with the plain reference.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.engine import Engine

from . import data, system, work
from .correct import Comparison, rel_err

RESIDENCIES = ("device", "host")


class FullGraph:
    def __init__(self, cell, seed: int, seconds: float, spans) -> None:
        self.cell, self.seed, self.spans = cell, seed, spans
        self.cfg = cell.config
        self.outputs: List = []
        self.elapsed = 0.0

    def setup(self) -> Dict:
        cfg, tr = self.cfg, self.cell.traffic
        self.residency = tr.get("residency", "device")
        if self.residency not in RESIDENCIES:
            raise ValueError(f"residency {self.residency!r}")
        g = self.graph = data.deployed_graph(cfg)
        self.ref = system.reference_module(cfg)
        self.x, self.params = data.make_inputs(
            self.seed, g["n"], cfg["feat_dim"], self.ref.param_leaves(cfg),
            features=self.residency)
        pg = system.program_graph(cfg, g["n"], g["src"], g["dst"],
                                  g["weight"], cfg["name"])
        model = system.model_ir(cfg, pg,
                                self.ref.program_leaves(self.params, cfg))
        self.engine = Engine(
            geometry=system.geometry(cfg.get("geometry")),
            resident_budget_bytes=tr.get("resident_budget_bytes"))
        self.prog = self.engine.compile(model, pg)
        jax.block_until_ready(self._pass())
        p = self.prog.pgraph
        self.work = work.pass_work(cfg, g["n"], int(g["src"].shape[0]))
        return {"t_loc_s": self.prog.t_loc,
                "instructions": self.prog.instruction_count(),
                "row_blocks": p.n_blocks,
                "ell_tiles": sum(len(t) for t in p.tiles.values()),
                "tile_bytes": p.tile_bytes(),
                "geometry": str(p.config),
                "tile_ops": self.engine.exec_stats.tile_ops}

    def _pass(self):
        return self.engine.run(self.prog, self.x, residency=self.residency)

    def window(self, seconds: float) -> None:
        span, outs = self.spans, self.outputs
        t0 = time.perf_counter()
        while True:
            with span("pass"):
                outs.append(jax.block_until_ready(self._pass()))
            if time.perf_counter() - t0 >= seconds:
                break
        self.elapsed = time.perf_counter() - t0

    def end_to_end(self) -> Dict[str, float]:
        return {"pass_ms": self.elapsed / len(self.outputs) * 1e3}

    def counters(self) -> Dict:
        return {"passes": len(self.outputs), "window_s": self.elapsed,
                "work": self.work}

    def release(self) -> None:
        """Free the program's state (its placed tiles go with it)."""
        self.engine = self.prog = None

    def _reference(self, dtype=jnp.float32, precision=None):
        """The plain reference on the run's inputs, fp32 at the
        configuration's matmul precision unless told otherwise."""
        g = self.graph
        gd = {k: jnp.asarray(g[k]) for k in ("src", "dst", "weight")}
        fwd = jax.jit(functools.partial(
            self.ref.forward, n=g["n"], dtype=dtype,
            precision=precision or self.cfg["matmul_precision"]))
        return fwd(self.params, gd, self.x)

    def check(self, limits: Dict[str, float]):
        g, cfg = self.graph, self.cfg
        y_ref = self._reference()
        cmp = Comparison(limits)
        cmp.add("rel_err", max(rel_err(y, y_ref) for y in self.outputs))
        want = (g["n"], cfg["n_classes"])
        cmp.add("bad_shape", sum(tuple(y.shape) != want
                                 for y in self.outputs))
        return cmp, len(self.outputs), 0

    def reading(self, served: str, precision: str) -> float:
        """``rel_err`` of what is served against the fp32 reference at
        matmul ``precision``: ``"program"`` is the window's passes,
        ``"control"`` the reference in bfloat16 put in the program's
        place."""
        y_ref = self._reference(precision=precision)
        if served == "program":
            return max(rel_err(y, y_ref) for y in self.outputs)
        return rel_err(self._reference(jnp.bfloat16, "default"), y_ref)


Driver = FullGraph

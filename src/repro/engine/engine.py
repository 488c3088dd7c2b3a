"""The unified GraphAGILE engine — the repo's single public entry point.

    from repro.engine import Engine

    engine = Engine(geometry=PartitionConfig(n1=256, n2=32))
    prog = engine.compile("b1", graph)          # -> CompiledProgram
    y = engine.run(prog, x)                     # executes the 128-bit binary
    prog.save("gcn_cora.gagi")                  # binary + manifest bundle
    y2 = engine.run(engine.load("gcn_cora.gagi"), x)   # later session

One ``Engine`` is one overlay instance: a fixed tile-geometry contract plus
the ACK kernel cache, exactly like one FPGA bitstream.  Compiling a new
model or a new graph changes the instruction binary only — never the
kernels (the paper's "no reconfiguration" property).

For serving traffic, ``engine.submit(request)`` / ``engine.serve(requests)``
run a streaming loop with an LRU program cache keyed by (model schema
hash, graph partition signature, geometry): repeated (model, graph)
shapes skip software compilation and report ``T_LoC == 0``.
``engine.submit_batch(requests)`` executes ONE binary pass for N
requests that share a cache key (features stacked on a batch axis).

One Engine is one overlay.  The traffic layer above it — dynamic
batching, a pool of K overlays with cache-affinity routing, bounded
work queues with backpressure, and serving telemetry — lives in
:mod:`repro.runtime` (``OverlayPool`` / ``ServeLoop``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import re
import time
import warnings
from typing import Any, Dict, Iterable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compiler import CompileOptions, run_pipeline
from repro.core.gnn_builders import build
from repro.core.graph import Graph
from repro.core.ir import ModelIR
from repro.core.passes.partition import PartitionConfig
from repro.obs.tracer import get_tracer

from .cache import LRUCache
from .executor import (BinaryExecutor, ExecStats, ensure_placement,
                       select_tiles)
from .program import CompiledProgram, from_program

ModelSpec = Union[str, ModelIR]


def _env_verify_default() -> bool:
    """Process default for ``Engine(verify=...)``: the ``REPRO_VERIFY``
    env var (tests/CI export 1; hot serving paths leave it unset)."""
    return os.environ.get("REPRO_VERIFY", "0").lower() in (
        "1", "true", "yes", "on")


def _export_gagi(prog: CompiledProgram) -> None:
    """``GAGI_EXPORT_DIR``: drop every freshly compiled program as a
    ``.gagi`` bundle there (how CI collects the verify-gate corpus)."""
    out = os.environ.get("GAGI_EXPORT_DIR")
    if not out:
        return
    os.makedirs(out, exist_ok=True)
    stem = re.sub(r"[^A-Za-z0-9_.-]+", "_",
                  f"{prog.model_name}-{prog.graph_name}")
    prog.save(os.path.join(
        out, f"{stem}-{prog.cache_key[:8] or 'nokey'}.gagi"))


def _mesh_count(mesh) -> Optional[int]:
    """Device count of the ``mesh`` knob (int, Mesh, or None) — what
    ``compile`` needs to emit a placement schedule; no devices touched."""
    if mesh is None:
        return None
    return int(mesh) if isinstance(mesh, int) else int(mesh.size)


def _resolve_mesh(mesh):
    """``mesh`` knob -> jax Mesh for execution.  Accepts ``None``, a
    device count (int, builds the 1-D ``dev`` mesh over local devices),
    or a prebuilt mesh from :mod:`repro.launch.mesh`."""
    if mesh is None:
        return None
    if isinstance(mesh, int):
        from repro.launch.mesh import make_device_mesh
        mesh = make_device_mesh(mesh)
    return mesh


# --------------------------------------------------------------------------- #
# Cache-key signatures.
# --------------------------------------------------------------------------- #
def _live_version_of(graph):
    """The :class:`repro.livegraph.GraphVersion` a graph-ish object
    denotes, or ``None``.  Duck-typed (no livegraph import): a
    ``LiveGraphServer`` handle carries ``_live_server`` and resolves to
    its *active* version; a version's materialized graph carries
    ``_live_version``."""
    server = getattr(graph, "_live_server", None)
    if server is not None:
        return server.active
    return getattr(graph, "_live_version", None)


def graph_signature(g: Graph) -> str:
    """Partition signature of a graph: everything the compiled program
    depends on — topology (Step 3) plus feat_dim/n_classes, which size
    the layers of builder-constructed models.

    Live-versioned graphs (``repro.livegraph``) return their
    *structural* signature instead: tile-grid geometry + the
    (j, k, n_slices) tile structure, which is everything the
    instruction binary depends on.  Content-only deltas keep the
    signature — and therefore the program-cache key — so a mutated
    live graph reuses its compiled program with rebound tiles.

    The O(|E|) hash over the edge arrays is memoized on the graph object,
    keyed by the array objects themselves (strong references, compared
    with ``is``, so a freed array's id can never be mistaken for a new
    one) plus the graph's ``mutation_token`` dirty counter.  Deployed
    graphs are treated as immutable: rebinding arrays (what
    ``dataclasses.replace`` and every Graph method do) invalidates the
    memo; mutating array *contents* in place requires a
    ``Graph.invalidate_views()`` call (which bumps the token).
    Repeated ``submit`` calls on the same deployed graph cost O(1); the
    cheap scalars are folded in fresh every call.
    """
    lv = _live_version_of(g)
    if lv is not None:
        return lv.structural_signature
    token = getattr(g, "mutation_token", 0)
    cached = g.__dict__.get("_edge_digest")
    if (cached is None or cached[0] is not g.src
            or cached[1] is not g.dst or cached[2] is not g.weight
            or cached[3] != token):
        h = hashlib.sha1()
        h.update(np.ascontiguousarray(g.src).tobytes())
        h.update(np.ascontiguousarray(g.dst).tobytes())
        h.update(np.ascontiguousarray(g.weight).tobytes())
        cached = (g.src, g.dst, g.weight, token, h.hexdigest())
        g.__dict__["_edge_digest"] = cached
    scalars = f"{g.n_vertices}:{g.n_edges}:{g.feat_dim}:{g.n_classes}"
    return hashlib.sha1(f"{scalars}|{cached[4]}".encode()).hexdigest()


def _weight_digest(model: ModelIR) -> str:
    """SHA-1 over weight contents, memoized on the model keyed by the
    array objects themselves (identity compared with ``is``, strong refs
    held) — rebinding an entry invalidates the memo, so repeat submits of
    the same ModelIR cost O(1); in-place array mutation is unsupported,
    as for graphs."""
    names = tuple(sorted(model.weights))
    cached = model.__dict__.get("_weight_digest")
    if (cached is None or cached[0] != names
            or any(a is not model.weights[n]
                   for n, a in zip(names, cached[1]))):
        h = hashlib.sha1()
        for name in names:
            w = np.asarray(model.weights[name])
            h.update(name.encode())
            h.update(repr((w.shape, str(w.dtype))).encode())
            h.update(w.tobytes())
        cached = (names, tuple(model.weights[n] for n in names),
                  h.hexdigest())
        model.__dict__["_weight_digest"] = cached
    return cached[2]


def model_signature(model: ModelSpec, seed: int = 0) -> str:
    """Schema hash of a model: layer DAG + weight contents.  The layer
    structure (cheap, and mutable pre-compile) is hashed fresh every
    call; the weight bytes (the O(MB) part) are memoized."""
    if isinstance(model, str):
        return f"bench:{model}:seed{seed}"
    h = hashlib.sha1()
    h.update(model.name.encode())
    for lid in sorted(model.layers):
        l = model.layers[lid]
        h.update(repr((
            lid, int(l.layer_type), l.f_in, l.f_out,
            int(l.agg_op) if l.agg_op is not None else -1,
            int(l.act), l.act_enabled, tuple(l.parent_ids),
            tuple(sorted((k, repr(v)) for k, v in l.attrs.items())),
        )).encode())
    h.update(_weight_digest(model).encode())
    return h.hexdigest()


# --------------------------------------------------------------------------- #
# Streaming request interface.
# --------------------------------------------------------------------------- #
def stack_features(features: Sequence[Any]) -> "jax.Array":
    """Pad N ``[V, F]`` feature arrays to a common shape and stack them
    into the ``[N, V, F]`` tensor ``run_batch`` consumes.

    Requests that share a cache key come from the same deployed graph,
    so shapes normally already agree; zero-padding is safe regardless
    because the executor zero-pads features *and* weight rows to the
    tile grid — extra zero columns contribute nothing.
    """
    arrs = [np.asarray(f, np.float32) for f in features]
    v = max(a.shape[0] for a in arrs)
    f = max(a.shape[1] for a in arrs)
    arrs = [np.pad(a, ((0, v - a.shape[0]), (0, f - a.shape[1])))
            for a in arrs]
    return jnp.asarray(np.stack(arrs))


def stack_graph_data(gds: Sequence[dict], pad_to: int) -> dict:
    """Stack N per-request ``graph_data`` pytrees (identical structure —
    one geometry bucket) into a leading batch axis, zero-filling up to
    ``pad_to`` lanes.  Zero lanes are inert: mask False and edge-list
    values 0 everywhere, so padded lanes compute on empty graphs and
    their outputs are sliced off with the feature padding."""
    stacked = jax.tree_util.tree_map(
        lambda *a: jnp.stack([jnp.asarray(x) for x in a]), *gds)
    extra = pad_to - len(gds)
    if extra > 0:
        stacked = jax.tree_util.tree_map(
            lambda a: jnp.pad(a, ((0, extra),) + ((0, 0),) * (a.ndim - 1)),
            stacked)
    return stacked


@dataclasses.dataclass
class InferenceRequest:
    """One unit of serving traffic: (model, graph, features).

    ``graph_data`` switches the request to graph-as-data execution (the
    mini-batch sampling layer): ``graph`` is then a geometry-bucket
    *template* shared by every request in the bucket — making the
    program-cache key collide across users — and the request's actual
    topology travels in ``graph_data`` (canonical ELL layout, see
    ``repro.sampling.buckets.layout_graph``)."""

    model: ModelSpec              # benchmark name ("b1".."b8") or a ModelIR
    graph: Graph
    features: Any                 # [V, F] array
    request_id: Optional[str] = None
    seed: int = 0                 # builder seed when model is a name
    graph_data: Optional[dict] = None


@dataclasses.dataclass
class InferenceResponse:
    request_id: str
    output: Any                   # [V, n_classes] jnp array
    t_loc: float                  # compile latency paid by THIS request (s)
    t_loh: float                  # execution latency (s)
    cache_hit: bool
    cache_key: str
    model_name: str
    graph_name: str
    batch_size: int = 1           # requests coalesced into this binary pass
    overlay: Optional[int] = None  # pool overlay index (set by repro.runtime)


@dataclasses.dataclass
class EngineStats:
    requests: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    compiles: int = 0
    total_t_loc: float = 0.0
    total_t_loh: float = 0.0


# --------------------------------------------------------------------------- #
class Engine:
    """One overlay instance: fixed tile contract + ACK kernel cache."""

    def __init__(self, geometry: Optional[PartitionConfig] = None,
                 n_pes: int = 8, backend: str = "xla", *,
                 overlap: bool = True, interpret: bool = False,
                 vmem_budget_bytes: int = 3 << 20,
                 cache_capacity: int = 32,
                 resident_budget_bytes: Optional[int] = None,
                 verify: Optional[bool] = None) -> None:
        self.geometry = geometry
        self.n_pes = n_pes
        self.backend = backend
        # Static verification of every fresh compile / livegraph rebind
        # (repro.verify).  None -> the REPRO_VERIFY env var; tests/CI
        # set it, hot serving paths keep it off.
        self.verify = _env_verify_default() if verify is None else verify
        self.vmem_budget_bytes = vmem_budget_bytes
        self._executor = BinaryExecutor(
            backend=backend, overlap=overlap, interpret=interpret,
            resident_budget_bytes=resident_budget_bytes)
        self.cache: LRUCache[CompiledProgram] = LRUCache(cache_capacity)
        self.stats = EngineStats()

    @property
    def resident_budget_bytes(self) -> Optional[int]:
        """Device-residency budget enforced by the executor: the
        device-resident path refuses runs whose liveness-aware peak
        exceeds it, the ``residency="host"`` path streams within it."""
        return self._executor.resident_budget_bytes

    @resident_budget_bytes.setter
    def resident_budget_bytes(self, v: Optional[int]) -> None:
        self._executor.resident_budget_bytes = v

    # ------------------------------------------------------------------ #
    @property
    def exec_stats(self) -> ExecStats:
        """Counters of the most recent ``run``/``run_batch`` only."""
        return self._executor.stats

    @property
    def exec_stats_total(self) -> ExecStats:
        """Lifetime counters accumulated across all runs."""
        return self._executor.total

    def _geometry_tag(self) -> str:
        if self.geometry is None:
            return f"auto:{self.vmem_budget_bytes}"
        return (f"n1={self.geometry.n1},n2={self.geometry.n2},"
                f"cap={self.geometry.width_cap}")

    def cache_key(self, model: ModelSpec, graph: Graph, *, seed: int = 0,
                  order_opt: bool = True, fusion: bool = True) -> str:
        parts = "|".join([
            model_signature(model, seed), graph_signature(graph),
            self._geometry_tag(), f"pes={self.n_pes}",
            f"oo={int(order_opt)}", f"fu={int(fusion)}",
        ])
        return hashlib.sha1(parts.encode()).hexdigest()

    # ------------------------------------------------------------------ #
    def compile(self, model: ModelSpec, graph: Graph, *, seed: int = 0,
                order_opt: bool = True, fusion: bool = True,
                use_cache: bool = True, residency: Optional[str] = None,
                mesh=None, verify: Optional[bool] = None,
                _key: Optional[str] = None) -> CompiledProgram:
        """Model + graph -> CompiledProgram (through the §6 pipeline).

        ``model`` is a benchmark name ("b1".."b8", built with ``seed``) or
        a :class:`ModelIR`.  Hits in the program cache skip compilation.
        ``_key`` lets callers that already computed the cache key (submit)
        skip rehashing the graph/weights.

        ``residency`` ("device" | "host") sets the program's default
        execution mode: "host" keeps features host-resident and streams
        one destination shard's working set to the device at a time
        (bit-identical results, bounded device footprint).  The returned
        handle carries the default; the shared cache entry is unchanged.

        ``mesh`` (a device count or a mesh from
        ``repro.launch.mesh.make_device_mesh``) records the placement
        schedule — per-device shard orders + halo sets for that many
        devices — in the program manifest, so it round-trips ``.gagi``.
        Programs compiled without it still run on a mesh: the executor
        derives an identical schedule from the binary.

        Live-versioned graphs (a ``repro.livegraph`` handle or a
        version's materialized graph): the cache key is the version's
        *structural* signature, so a content-only delta hits the cache;
        the returned program is then *rebound* to the version's patched
        tiles (``GraphVersion.bind``) — fresh tiles, zero recompiles.

        ``verify`` statically verifies the program (``repro.verify``:
        hazard/coverage/legality/budget checks, no execution) on every
        fresh compile and every livegraph rebind, raising
        :class:`repro.verify.VerifyError` on a failing report.  None
        defers to ``Engine(verify=...)`` / the ``REPRO_VERIFY`` env var;
        plain cache hits are never re-verified.
        """
        if residency not in (None, "device", "host"):
            raise ValueError("residency must be 'device' or 'host', "
                             f"got {residency!r}")
        do_verify = self.verify if verify is None else verify
        n_devices = _mesh_count(mesh)
        lv = _live_version_of(graph)
        if lv is not None:
            graph = lv.as_graph()
        key = _key or self.cache_key(model, graph, seed=seed,
                                     order_opt=order_opt, fusion=fusion)
        tracer = get_tracer()
        if use_cache:
            cached = self.cache.get(key)
            if cached is not None:
                tracer.instant("cache_hit", cat="compile",
                               track="compile", args={"key": key[:12]})
                if n_devices is not None:
                    ensure_placement(cached, n_devices)
                if lv is not None:
                    cached = lv.bind(cached)
                    if do_verify:
                        self._verify_program(cached)
                if residency is not None:
                    return dataclasses.replace(
                        cached, default_residency=residency)
                return cached
        with tracer.span("compile", cat="compile", track="compile",
                         args={"key": key[:12],
                               "graph": graph.name}) as sp:
            model_ir = build(model, graph, seed) \
                if isinstance(model, str) else model
            opts = CompileOptions(order_opt=order_opt, fusion=fusion,
                                  n_pes=self.n_pes,
                                  partition=self.geometry,
                                  vmem_budget_bytes=self.vmem_budget_bytes)
            cr = run_pipeline(model_ir, graph, opts)
            sp.add(t_loc_s=round(cr.t_loc, 6),
                   binary_bytes=len(cr.binary))
        prog = from_program(cr.program, binary=cr.binary, t_loc=cr.t_loc,
                            cache_key=key, graph_name=graph.name,
                            source=cr, n_devices=n_devices)
        if residency is not None:
            prog = dataclasses.replace(prog, default_residency=residency)
        self.stats.compiles += 1
        self.stats.total_t_loc += cr.t_loc
        if use_cache:
            # The cached copy drops `source` (the full IR/Program/report
            # graph): execution needs only binary+manifest+weights+tiles,
            # so a long-lived serving cache stays slim.  The caller that
            # paid for this compile still gets the reports.  It also
            # drops the residency default: serving traffic runs
            # device-resident unless a caller asks otherwise.
            self.cache.put(key, dataclasses.replace(
                prog, source=None, default_residency=None))
        if lv is not None:
            # Rebind to the version's tile store (labels the manifest
            # with version + tile stats); keep this caller's reports.
            prog = dataclasses.replace(lv.bind(prog), source=prog.source,
                                       default_residency=residency)
        if do_verify:
            self._verify_program(prog)
        _export_gagi(prog)
        return prog

    def remap(self, prog: CompiledProgram, report: Any = None, *,
              source: str = "auto", force: Any = None, margin: float = 0.1,
              probe: bool = False,
              modes: Optional[Sequence[str]] = None) -> CompiledProgram:
        """Sparsity-adaptive kernel remapping of a compiled program
        (``repro.core.passes.remap``): re-encode each AGGREGATE tile's
        kernel fields — SpDMM as-is, densified GEMM, or skip-empty —
        from the tile's measured/derived density and a cost oracle.  No
        recompile, no new partition; the cache key is preserved.

        ``report`` supplies the oracle's machine constants: a
        ``repro.obs.conformance.ConformanceReport`` (its LS-fitted
        ``calibrated_constants``), a plain constants dict, or ``None``
        for the paper-default roofline.  ``probe=True`` instead
        microbenchmarks the two ACK kernels at the program's tile
        geometry on this engine's backend.  ``force``/``modes`` pin or
        restrict decisions (oracle tests / ablations).

        If ``prog`` is the cached entry for its key, the cache is
        updated in place (slim copy, same key), so subsequent cache
        hits — and livegraph rebinds on top of them — stay remapped.
        """
        from repro.core.passes.remap import remap_program
        new = remap_program(prog, source=source, constants=report,
                            margin=margin, force=force, modes=modes,
                            probe=probe, ack=self._executor.ack)
        if prog.cache_key and self.cache.get(prog.cache_key) is not None:
            self.cache.put(prog.cache_key, dataclasses.replace(
                new, source=None, default_residency=None))
        if self.verify:
            self._verify_program(new)
        return new

    def _verify_program(self, prog: CompiledProgram) -> None:
        from repro.verify import VerifyError, verify_program
        tracer = get_tracer()
        with tracer.span("verify", cat="compile", track="compile",
                         args={"key": prog.cache_key[:12]}) as sp:
            report = verify_program(prog)
            sp.add(ok=report.ok, violations=len(report.violations))
        if not report.ok:
            raise VerifyError(report)

    def run(self, prog: CompiledProgram, x,
            weights: Optional[Dict[str, np.ndarray]] = None,
            graph_data: Optional[dict] = None,
            residency: Optional[str] = None, mesh=None, graph=None):
        """Execute a compiled program by decoding its ISA binary.

        ``residency="host"`` streams the partition-centric out-of-core
        path (features host-resident, one shard's working set on device
        at a time); ``"device"`` keeps every padded layer output on
        device.  ``mesh`` (a device count or a prebuilt mesh) runs the
        placement-scheduled multi-device path: each device executes its
        assigned destination shards under ``shard_map``, exchanging halo
        sub-fibers with collectives.  Results are bit-identical across
        all three; ``None`` uses the program's compile-time default.
        A device-resident pass is traced once per program and argument
        shapes into one jitted executable per layer and replayed after
        that (``exec_stats.pass_compiles`` / ``pass_replays``).

        ``graph`` (a live-versioned graph or ``repro.livegraph``
        handle) rebinds the program to that version's patched tiles
        before executing — every residency stages the patched tiles
        transparently, since staging reads ``prog.pgraph``."""
        prog = self._rebind_live(prog, graph)
        residency = residency or prog.default_residency or "device"
        mesh = _resolve_mesh(mesh)
        return self._executor.run(prog, x, weights=weights,
                                  graph_data=graph_data,
                                  residency=residency, mesh=mesh)

    @staticmethod
    def _rebind_live(prog: CompiledProgram, graph) -> CompiledProgram:
        if graph is None:
            return prog
        lv = _live_version_of(graph)
        return lv.bind(prog) if lv is not None else prog

    def run_batch(self, prog: CompiledProgram, xs,
                  weights: Optional[Dict[str, np.ndarray]] = None,
                  graph_data: Optional[dict] = None,
                  residency: Optional[str] = None, mesh=None,
                  graph=None):
        """One binary pass for stacked ``[N, V, F]`` features -> [N, V, C].
        ``graph_data`` (stacked, leading batch axis) lets each lane carry
        its own topology over the same compiled program.  ``residency``
        as in :meth:`run` ("host" interleaves the lanes per staged
        shard, so each shard's tile working set ships once per batch —
        note the staged window's sub-fiber half then scales with the
        batch).  ``mesh`` as in :meth:`run`: lanes run as sequential
        eager multi-device passes (tile kernels are cached, but there
        is no whole-pass executable to replay — device-resident
        batching is the throughput path).  ``graph`` rebinds to a live
        version's tiles, as in :meth:`run`."""
        prog = self._rebind_live(prog, graph)
        residency = residency or prog.default_residency or "device"
        mesh = _resolve_mesh(mesh)
        return self._executor.run_batch(prog, xs, weights=weights,
                                        graph_data=graph_data,
                                        residency=residency, mesh=mesh)

    def load(self, path: str) -> CompiledProgram:
        """Load a ``.gagi`` bundle saved by ``CompiledProgram.save``."""
        prog = CompiledProgram.load(path)
        if self.geometry is not None:
            geo = prog.manifest["geometry"]
            mine = (self.geometry.n1, self.geometry.n2,
                    self.geometry.width_cap)
            theirs = (geo["n1"], geo["n2"], geo["width_cap"])
            if theirs != mine:
                warnings.warn(
                    f"{path} was compiled for tile geometry "
                    f"(n1, n2, width_cap)={theirs} but this engine is "
                    f"fixed at {mine}; new tile kernels will be "
                    "compiled", stacklevel=2)
        return prog

    # ------------------------------------------------------------------ #
    @staticmethod
    def _admit_live(req: InferenceRequest):
        """Resolve a live-graph handle at admission: pin the active
        version (inflight refcount) and swap the request's graph for
        that version's materialized snapshot.  Returns ``(req, pin)``;
        callers release the pin when the request completes."""
        server = getattr(req.graph, "_live_server", None)
        if server is None:
            return req, None
        version = server.admit()
        return (dataclasses.replace(req, graph=version.as_graph()),
                (server, version.vid))

    def submit(self, req: InferenceRequest) -> InferenceResponse:
        """Serve one request: cached compile -> binary-driven execution.

        ``req.graph`` may be a ``repro.livegraph.LiveGraphServer``
        handle: the request is then pinned to the version active at
        admission and served on exactly that version's tiles, whatever
        cutovers happen meanwhile."""
        req, pin = self._admit_live(req)
        try:
            key = self.cache_key(req.model, req.graph, seed=req.seed)
            hit = key in self.cache
            prog = self.compile(req.model, req.graph, seed=req.seed,
                                _key=key)
            t0 = time.perf_counter()
            y = self.run(prog, req.features, graph_data=req.graph_data)
            jax.block_until_ready(y)
            t_loh = time.perf_counter() - t0
            t_loc = 0.0 if hit else prog.t_loc

            self.stats.requests += 1
            self.stats.cache_hits += int(hit)
            self.stats.cache_misses += int(not hit)
            self.stats.total_t_loh += t_loh
            rid = req.request_id or f"req{self.stats.requests - 1}"
            return InferenceResponse(
                request_id=rid, output=y, t_loc=t_loc, t_loh=t_loh,
                cache_hit=hit, cache_key=key, model_name=prog.model_name,
                graph_name=req.graph.name)
        finally:
            if pin is not None:
                pin[0].release(pin[1])

    def submit_batch(self, reqs: Sequence[InferenceRequest]
                     ) -> List[InferenceResponse]:
        """Serve N coalesced requests with ONE binary pass.

        All requests must share this engine's cache key — same model
        schema + weights, same deployed graph, same compile options —
        which is exactly the grouping ``repro.runtime.Batcher`` produces.
        Features are padded/stacked to ``[N, V, F]`` and executed by a
        single traversal of the instruction stream (``run_batch``).

        Latency accounting reflects what each request *experienced*:
        every response reports the batch's compile latency (they all
        waited for the one compile on a miss) and the batch's execution
        wall time.
        """
        if not reqs:
            return []
        # Host spans of one batch, on the calling thread:
        # ``exec.batch_stage`` from here to the pass (live-version pins,
        # key checks, cache lookup, stacking and padding), then
        # ``exec.batch_pass`` (the t_loh interval) and
        # ``exec.batch_unstack`` (per-request output slices and the
        # responses).
        stage = get_tracer().span("exec.batch_stage", cat="exec")
        admitted = [self._admit_live(r) for r in reqs]
        reqs = [r for r, _ in admitted]
        pins = [p for _, p in admitted if p is not None]
        try:
            return self._submit_batch_resolved(reqs, stage)
        finally:
            for server, vid in pins:
                server.release(vid)

    def _submit_batch_resolved(self, reqs: Sequence[InferenceRequest],
                               stage) -> List[InferenceResponse]:
        key = self.cache_key(reqs[0].model, reqs[0].graph,
                             seed=reqs[0].seed)
        for r in reqs[1:]:
            k = self.cache_key(r.model, r.graph, seed=r.seed)
            if k != key:
                raise ValueError(
                    "submit_batch requires one cache key per batch: "
                    f"request {r.request_id!r} has key {k[:12]}… but the "
                    f"batch was opened with {key[:12]}…")
        # Live versions share the structural cache key by design, but a
        # batch is ONE binary pass over ONE tile set: mixing versions
        # would silently serve some requests the wrong graph.
        lv = _live_version_of(reqs[0].graph)
        for r in reqs[1:]:
            if _live_version_of(r.graph) is not lv:
                raise ValueError(
                    "submit_batch cannot mix graph versions in one "
                    "batch: all requests must be admitted against the "
                    "same live version (the runtime batches per "
                    "version for exactly this reason)")
        with_gd = sum(r.graph_data is not None for r in reqs)
        if 0 < with_gd < len(reqs):
            raise ValueError(
                "submit_batch cannot mix graph-as-data requests with "
                "baked-topology requests in one batch")
        hit = key in self.cache
        prog = self.compile(reqs[0].model, reqs[0].graph,
                            seed=reqs[0].seed, _key=key)
        if not hit:
            # Execute the long-lived cached copy: the jitted batched
            # executable is memoized on the program object, so it must
            # attach to the instance repeat batches will see.  (On a
            # hit, compile() already returned that instance.)
            prog = self.cache.get(key) or prog
            if lv is not None:
                prog = lv.bind(prog)
        xs = stack_features([r.features for r in reqs])
        # Bucket the batch axis to the next power of two (zero-filled
        # lanes, outputs sliced off): deadline flushes produce ragged
        # sizes 1..max_batch, and each DISTINCT shape would pay a fresh
        # whole-program trace+jit — buckets cap that at log2(max_batch)
        # executables per program for at most 2x lane waste.
        n = len(reqs)
        bucket = 1 << (n - 1).bit_length()
        if bucket != n:
            xs = jnp.pad(xs, ((0, bucket - n), (0, 0), (0, 0)))
        # only the tile arrays the program reads are stacked and shipped
        fields = self._executor._tile_fields(prog)
        gd = stack_graph_data([select_tiles(r.graph_data, fields)
                               for r in reqs], bucket) if with_gd else None
        stage.done()
        t0 = time.perf_counter_ns()
        ys = self.run_batch(prog, xs, graph_data=gd)[:n]
        jax.block_until_ready(ys)
        t1 = time.perf_counter_ns()
        tracer = get_tracer()
        tracer.complete("exec.batch_pass", t0, t1, cat="exec",
                        args={"requests": n, "lanes": bucket})
        t_loh = (t1 - t0) * 1e-9
        t_loc = 0.0 if hit else prog.t_loc

        base = self.stats.requests
        self.stats.requests += n
        self.stats.cache_hits += n * int(hit)
        self.stats.cache_misses += n * int(not hit)
        self.stats.total_t_loh += t_loh
        with tracer.span("exec.batch_unstack", cat="exec"):
            return [InferenceResponse(
                request_id=r.request_id or f"req{base + i}", output=ys[i],
                t_loc=t_loc, t_loh=t_loh, cache_hit=hit, cache_key=key,
                model_name=prog.model_name, graph_name=r.graph.name,
                batch_size=n) for i, r in enumerate(reqs)]

    def serve(self, requests: Iterable[InferenceRequest]
              ) -> List[InferenceResponse]:
        """Drain a request stream through :meth:`submit` (Alg. 9's
        idle-PE rule at request granularity: the queue feeds the overlay
        whenever it drains).  For batched, multi-overlay serving use
        :class:`repro.runtime.OverlayPool` / ``ServeLoop`` instead."""
        return [self.submit(r) for r in requests]

"""The device-resident pass as one jitted executable per layer.

``Engine.run`` on concrete features traces each layer's tile loop once
per program and argument shapes, then replays the compiled layers.  The
replay must give the bits of per-tile dispatch (``overlap=False``) and
of host streaming, take new weights and rebound tiles of unchanged
shapes as arguments without a new trace, compile once more when a shape
changes, free outputs as the eager pass does, and name each layer's
executable after its ACK mode, whose scope it keeps, or an edge-valued
activation after what it computes, counting the pass's scatters into
the global edge array and the source rows its SpDMM tile ops gather.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ack
from repro.core import gnn_builders as B
from repro.core import graph as G
from repro.core.ir import LayerType
from repro.core.passes.partition import PartitionConfig
from repro.engine import Engine, InferenceRequest
from repro.engine.executor import (_KERNEL_MODES, device_tiles,
                                   tile_fields)
from repro.livegraph import (GraphDelta, GraphVersionStore,
                             LiveGraphServer, as_graph_data)
from repro.obs import NullTracer, tracing
from repro.sampling import (bucket_for, layout_graph, sample_ego,
                            template_graph)

GEOM = PartitionConfig(n1=32, n2=8)


def _g(nv=90, ne=400, f=12, c=4, seed=0):
    g = G.random_graph(nv, ne, seed=seed, dedupe=True).gcn_normalized()
    g.feat_dim, g.n_classes = f, c
    return g


def _engine(**kw) -> Engine:
    return Engine(geometry=GEOM, n_pes=4, **kw)


def _passes(eng: Engine):
    t = eng.exec_stats_total
    return t.pass_compiles, t.pass_replays


@pytest.mark.parametrize("name", list(B.BENCHMARKS))
def test_replayed_pass_is_bit_identical_to_eager_and_host(name):
    g = _g()
    x = jnp.asarray(G.random_features(g, seed=2))
    eng = _engine()
    prog = eng.compile(name, g)
    y_traced = np.asarray(eng.run(prog, x))
    y_replayed = np.asarray(eng.run(prog, x))
    assert _passes(eng) == (1, 1)
    eager = _engine(overlap=False)
    y_eager = np.asarray(eager.run(eager.compile(name, g), x))
    assert _passes(eager) == (0, 0)
    # a replay restores the stats its trace left
    assert eng.exec_stats.tile_ops == eager.exec_stats.tile_ops > 0
    assert eng.exec_stats.layers == eager.exec_stats.layers
    y_host = np.asarray(eng.run(prog, x, residency="host"))
    for y in (y_traced, y_replayed, y_host):
        assert np.array_equal(y, y_eager)


def test_weights_of_another_seed_replay_without_a_trace():
    g = _g(seed=4)
    x = jnp.asarray(G.random_features(g, seed=3))
    eng = _engine()
    prog = eng.compile("b3", g)
    y0 = np.asarray(eng.run(prog, x))
    w1 = _engine().compile("b3", g, seed=1).weights
    assert w1.keys() == prog.weights.keys()
    ack.reset_counter()
    y1 = np.asarray(eng.run(prog, x, weights=w1))
    assert ack.counter_snapshot() == {}      # no tile op was traced
    assert _passes(eng) == (1, 1)
    assert not np.array_equal(y0, y1)
    eager = _engine(overlap=False)
    y_eager = np.asarray(eager.run(eager.compile("b3", g), x, weights=w1))
    assert np.array_equal(y1, y_eager)


def test_rebound_tiles_replay_until_a_shape_changes():
    g = _g(seed=7)
    store = GraphVersionStore(g, geometry=GEOM)
    live = LiveGraphServer(store)
    eng = _engine()
    x = np.asarray(G.random_features(g, seed=2))
    prog = eng.compile("b1", live)
    eng.run(prog, x, graph=live)
    assert _passes(eng) == (1, 0)

    # a weight-only delta: the new version's tiles keep their shapes
    i = 9
    d = GraphDelta(g.n_vertices)
    d.remove_edge(int(g.src[i]), int(g.dst[i]))
    d.add_edge(int(g.src[i]), int(g.dst[i]), 123.0)
    g1 = d.apply_to(g)
    live.apply(d)
    y1 = np.asarray(eng.run(prog, x, graph=live))
    assert _passes(eng) == (1, 1)
    cold = _engine(overlap=False)
    y_cold = np.asarray(cold.run(cold.compile("b1", g1), x))
    assert np.array_equal(y1, y_cold)

    # graph-as-data: one trace, then any tiles of the same shapes replay
    bound = eng.compile("b1", live)
    eng.run(bound, x, graph_data=as_graph_data(store.head.pgraph))
    y_gd = np.asarray(eng.run(bound, x,
                              graph_data=as_graph_data(live.active.pgraph)))
    assert _passes(eng) == (2, 2)
    assert np.array_equal(y_gd, y_cold)

    # a new vertex changes the pass's shapes: exactly one more compile
    d = GraphDelta(g1.n_vertices)
    v = d.add_vertex(np.zeros(g1.feat_dim, np.float32))
    d.add_edge(v, 0, 0.5)
    g2 = d.apply_to(g1)
    live.apply(d)
    x2 = np.zeros((g2.n_vertices, g2.feat_dim), np.float32)
    x2[: x.shape[0]] = x
    y2 = np.asarray(eng.run(prog, x2, graph=live))
    assert _passes(eng) == (3, 2)
    eng.run(prog, x2, graph=live)
    assert _passes(eng) == (3, 3)
    y_cold2 = np.asarray(cold.run(cold.compile("b1", g2), x2))
    assert np.array_equal(y2, y_cold2)


def test_an_edge_list_crossing_a_power_of_two_compiles_once_more():
    """Edge lists are powers of two long: a delta that takes a slice's
    edges past one changes the pass's shapes, as an ELL width change
    does, so the pass compiles once more; edges within the length
    replay."""
    rows = np.repeat(np.arange(32), 4)              # tile (0, 0): 128
    src = np.concatenate([(rows + 1 + 5 * np.tile(np.arange(4), 32)) % 32,
                          np.arange(32)])
    dst = np.concatenate([rows, np.arange(32, 64)])
    g = G.Graph(n_vertices=64, src=src.astype(np.int32),
                dst=dst.astype(np.int32),
                weight=np.ones(src.size, np.float32), feat_dim=12,
                n_classes=4)
    store = GraphVersionStore(g, geometry=GEOM)
    live = LiveGraphServer(store)
    eng = _engine()
    x = np.asarray(G.random_features(g, seed=2))
    prog = eng.compile("b1", live)
    eng.run(prog, x, graph=live)
    t0 = live.active.pgraph.tiles[(0, 0)][0]
    assert (t0.nnz, t0.edges["dst"].size) == (128, 128)

    cold = _engine(overlap=False)
    for extra, passes in ((20, (2, 0)), (21, (2, 1))):
        d = GraphDelta(g.n_vertices)
        d.add_edge(extra, 0, 1.0)                   # row 0: 5, 6 of 8
        g = d.apply_to(g)
        live.apply(d)
        y = np.asarray(eng.run(prog, x, graph=live))
        t = live.active.pgraph.tiles[(0, 0)][0]
        assert (t.width, t.edges["dst"].size) == (t0.width, 256)
        assert _passes(eng) == passes
        assert np.array_equal(y, np.asarray(
            cold.run(cold.compile("b1", g), x)))


def test_layers_are_named_and_scoped_by_ack_mode():
    g = _g()
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = _engine()
    prog = eng.compile("b1", g)
    eng.run(prog, x)
    plan = prog.plan()
    modes = ["spdmm", "gemm", "gemm", "spdmm"]
    assert [_KERNEL_MODES[lp.layer_type] for lp in plan.layers] == modes
    entry, = prog.__dict__["_pass_exec"].values()
    pg = prog.pgraph
    gd = device_tiles(pg, tile_fields(plan, "xla"))
    # the first two layers read the padded features, then the padded
    # aggregate: both 16 wide at n2 = 8
    h = jax.ShapeDtypeStruct((pg.n_blocks * GEOM.n1, 16), jnp.float32)
    with entry.called_by(eng._executor, prog) as fns:
        assert len(fns) == len(modes)
        for t in (0, 1):
            text = fns[t].lower(
                h, None, None, None, gd["tiles"], gd["inv_in_degree"],
                prog.weights).as_text(debug_info=True)
            assert f"@jit_{modes[t]}" in text
            assert f"ack.{modes[t]}" in text


def test_edge_layers_are_named_and_their_scatters_counted():
    """GAT (b6) scores edges in ``jit_sddmm``, under the ``ack.sddmm``
    scope, and normalizes them in ``jit_edge_softmax``, apart from a
    vertex activation's ``jit_act``; every edge-valued tile scatters
    into the global edge array once a pass, on the jitted and the eager
    path alike, and GCN (b2) scatters nothing."""
    g = _g(seed=3)
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = _engine()
    prog = eng.compile("b6", g)
    with tracing() as tr:
        eng.run(prog, x)
        eng.run(prog, x)
    plan = prog.plan()
    entry, = prog.__dict__["_pass_exec"].values()
    ex, texts = eng._executor, []
    with entry.called_by(ex, prog) as fns:
        def lowered(prog, t, *args):
            texts.append(fns[t].lower(*args).as_text(debug_info=True))
            return fns[t](*args)
        ex._layers(prog, plan, x,
                   device_tiles(prog.pgraph, tile_fields(plan, "xla")),
                   prog.weights, NullTracer(), lowered)
    names = [re.search(r"module @(\w+)", t).group(1) for t in texts]
    assert names == 2 * ["jit_gemm", "jit_gemm", "jit_sddmm",
                         "jit_edge_softmax", "jit_spdmm"]
    assert all("ack.sddmm" in t for n, t in zip(names, texts)
               if n == "jit_sddmm")

    slices = sum(len(ts) for ts in prog.pgraph.tiles.values())
    edge_tiles = sum(len(lp.tiles) for lp in plan.layers
                     if lp.layer_type == LayerType.VECTOR_INNER
                     or lp.on_edges)
    assert edge_tiles == 2 * 2 * slices     # two layers: SDDMM, softmax
    counted = [e["args"]["value"] for e in tr.events()
               if e["ph"] == "C" and e["name"] == "exec.edge_scatters"]
    assert counted == [edge_tiles, edge_tiles]
    assert eng.exec_stats.edge_scatters == edge_tiles
    assert eng.exec_stats_total.edge_scatters == 2 * edge_tiles
    eager = _engine(overlap=False)
    eager.run(eager.compile("b6", g), x)
    assert eager.exec_stats.edge_scatters == edge_tiles
    gcn = _engine()
    gcn.run(gcn.compile("b2", g), x)
    assert gcn.exec_stats.edge_scatters == 0


def _spdmm_slices(prog):
    """The (j, k, s) slice each SpDMM tile op of a pass reads."""
    return [(tp.out_j, ins.args[1], ins.args[3] >> 1)
            for lp in prog.plan().layers
            if lp.layer_type == LayerType.AGGREGATE
            for tp in lp.tiles for ins in tp.compute]


def test_spdmm_rows_are_counted_on_trace_and_restored_on_replay():
    """``exec.spdmm_rows`` is the source rows a pass's SpDMM tile ops
    gather: over the program's own tiles the lengths of their edge
    lists, set when the layers trace, restored on every replay and the
    same tile by tile; a batched pass over sampled buckets the bucket's
    one list length a slice, and over buckets stripped of their lists
    every ELL slot."""
    g = _g(seed=6)
    x = jnp.asarray(G.random_features(g, seed=1))
    eng = _engine()
    prog = eng.compile("b3", g)
    with tracing() as tr:
        eng.run(prog, x)
        eng.run(prog, x)
    pg = prog.pgraph
    used = [pg.tiles[(j, k)][s] for j, k, s in _spdmm_slices(prog)]
    rows = sum(t.edges["dst"].size for t in used)
    real = sum(t.nnz for t in used)
    assert real < rows < sum(t.cols.size for t in used)
    counted = [e["args"]["value"] for e in tr.events()
               if e["ph"] == "C" and e["name"] == "exec.spdmm_rows"]
    assert counted == [rows, rows]
    assert _passes(eng) == (1, 1)
    assert eng.exec_stats.spdmm_rows == rows
    assert eng.exec_stats_total.spdmm_rows == 2 * rows
    eager = _engine(overlap=False)
    eager.run(eager.compile("b3", g), x)
    assert eager.exec_stats.spdmm_rows == rows

    parent = G.random_graph(400, 24000, seed=3, degree="powerlaw",
                            dedupe=True)
    parent.feat_dim, parent.n_classes = 16, 4
    feats = G.random_features(parent, seed=1)
    reqs = []
    for targets, seed in (([5, 90], 11), ([6, 91], 12)):
        sub = sample_ego(parent, targets, (6, 4), seed=seed).graph \
            .gcn_normalized()
        bucket = bucket_for(sub, GEOM)
        x_pad = np.zeros((bucket.n_vertices, parent.feat_dim), np.float32)
        x_pad[: sub.n_vertices] = feats[: sub.n_vertices]
        reqs.append(InferenceRequest(
            model="b1", graph=template_graph(bucket, GEOM),
            features=jnp.asarray(x_pad),
            graph_data=layout_graph(sub, bucket, GEOM)))
    served = _engine()
    served.submit_batch(reqs)
    tpl = served.compile("b1", reqs[0].graph)
    tiles = reqs[0].graph_data["tiles"]
    used = [tiles[f"{j}:{k}:{s}"] for j, k, s in _spdmm_slices(tpl)]
    length = bucket_for(sub, GEOM).list_length(GEOM.n1)
    assert all(d["edges"]["dst"].size == length for d in used)
    assert served.exec_stats.spdmm_rows == length * len(used)
    for r in reqs:
        r.graph_data = {"tiles": {key: {f: a for f, a in d.items()
                                        if f != "edges"}
                                  for key, d in r.graph_data["tiles"].items()},
                        "inv_in_degree": r.graph_data["inv_in_degree"]}
    served.submit_batch(reqs)
    assert served.exec_stats.spdmm_rows == sum(d["cols"].size for d in used)


def test_replayed_layers_free_outputs_as_the_eager_pass_does():
    g = _g(seed=5)
    x = jnp.asarray(G.random_features(g, seed=3))
    runs = []
    for eng in (_engine(), _engine(overlap=False)):
        prog = eng.compile("b3", g)
        for _ in range(2):
            events = []
            eng._executor.liveness_hook = \
                lambda ev, lid, live, events=events: events.append(
                    (ev, lid, live))
            eng.run(prog, x)
            runs.append((events, eng.exec_stats.peak_live_outputs,
                         eng.exec_stats.peak_live_bytes))
    assert _passes(eng) == (0, 0)
    traced, replayed, eager, _ = runs
    assert [e for e in traced[0] if e[0] == "free"]
    assert traced == replayed == eager

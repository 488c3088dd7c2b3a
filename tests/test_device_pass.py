"""The device-resident pass as one jitted executable per layer.

``Engine.run`` on concrete features traces each layer's tile loop once
per program and argument shapes, then replays the compiled layers.  The
replay must give the bits of per-tile dispatch (``overlap=False``) and
of host streaming, take new weights and rebound tiles of unchanged
shapes as arguments without a new trace, compile once more when a shape
changes, free outputs as the eager pass does, and name each layer's
executable after its ACK mode, whose scope it keeps, or an edge-valued
activation after what it computes, counting the pass's scatters into
the global edge array.
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
from repro.engine import Engine
from repro.engine.executor import (_KERNEL_MODES, device_tiles,
                                   reads_edges)
from repro.livegraph import (GraphDelta, GraphVersionStore,
                             LiveGraphServer, as_graph_data)
from repro.obs import NullTracer, tracing

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
    gd = device_tiles(pg, edges=reads_edges(plan))
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
        ex._layers(prog, plan, x, device_tiles(prog.pgraph),
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

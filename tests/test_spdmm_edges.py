"""SpDMM over a slice's edge list (``ELLTile.edges``).

SUM and MEAN aggregation read each ELL slice's real edges in destination
order, not its n1 x width padded slots.  The list is short (a power of
two, at most twice the slice's edges or 128), its pad entries add
exactly 0, and the kernel over it agrees with the ELL kernel on the same
slice to float32 rounding.  A sampled bucket carries each slice's list at
one length per bucket.  MAX/MIN aggregation, the Pallas backend and
tiles that carry no list keep the ELL kernel, and a program places on
the device only the arrays its pass reads.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ack
from repro.core import gnn_builders as B
from repro.core import graph as G
from repro.core.ir import AggOp, LayerType
from repro.core.passes.partition import (EDGE_LIST_MIN, PartitionConfig,
                                         partition_graph)
from repro.engine import Engine
from repro.engine.executor import device_tiles, select_tiles, tile_fields
from repro.livegraph import as_graph_data
from repro.sampling import bucket_for, layout_graph, sample_ego

GEOM = PartitionConfig(n1=32, n2=8)


def _graph(kind: str) -> G.Graph:
    """``random``: uniform ends; ``powerlaw``: hub rows; ``capped``:
    hub rows wider than the width cap, sliced; ``sparse``: mostly empty
    rows (isolated vertices)."""
    if kind == "random":
        g = G.random_graph(90, 400, seed=0, dedupe=True)
    elif kind in ("powerlaw", "capped"):
        g = G.random_graph(90, 900, seed=5, degree="powerlaw", alpha=1.3)
    else:
        g = G.random_graph(100, 30, seed=13)
    g = g.gcn_normalized()
    g.feat_dim, g.n_classes = 12, 4
    return g


def _geom(kind: str) -> PartitionConfig:
    return (PartitionConfig(n1=32, n2=8, width_cap=8) if kind == "capped"
            else GEOM)


def _slices(kind: str):
    pg = partition_graph(_graph(kind), _geom(kind))
    return pg, [t for ts in pg.tiles.values() for t in ts]


@pytest.mark.parametrize("kind", ["random", "powerlaw", "capped", "sparse"])
def test_edge_list_is_short_and_its_pads_add_nothing(kind):
    pg, slices = _slices(kind)
    if kind == "capped":        # the cap did slice some hub rows
        assert any(len(ts) > 1 for ts in pg.tiles.values())
    n1 = pg.config.n1
    for t in slices:
        e = t.edges
        m = e["dst"].shape[0]
        assert m <= max(2 * t.nnz, EDGE_LIST_MIN)
        assert m >= EDGE_LIST_MIN and m & (m - 1) == 0
        assert {k: a.dtype for k, a in e.items()} == {
            "dst": np.int32, "src": np.int32, "val": np.float32,
            "eid": np.int32}
        assert np.all(np.diff(e["dst"]) >= 0)           # sorted by row
        # the real entries are the ELL's real slots, row-major
        r, c = np.nonzero(t.edge_pos >= 0)
        assert t.nnz == r.size
        np.testing.assert_array_equal(e["dst"][:t.nnz], r)
        np.testing.assert_array_equal(e["src"][:t.nnz], t.cols[r, c])
        np.testing.assert_array_equal(e["val"][:t.nnz], t.vals[r, c])
        np.testing.assert_array_equal(e["eid"][:t.nnz], t.edge_pos[r, c])
        # pads: value 0 and no edge id, on the last row
        assert np.all(e["val"][t.nnz:] == 0)
        assert np.all(e["eid"][t.nnz:] == -1)
        assert np.all(e["dst"][t.nnz:] == n1 - 1)
        assert t.edges is e                             # derived once
    # a list's pads add exactly 0: an all-pad list leaves acc's bits
    h = jnp.asarray(np.random.default_rng(0).normal(size=(n1, 8)),
                    jnp.float32)
    acc = jnp.asarray(np.random.default_rng(1).normal(size=(n1, 8)),
                      jnp.float32)
    pad = np.zeros(EDGE_LIST_MIN, np.int32)
    out = ack._spdmm_edges_xla(h, pad + n1 - 1, pad,
                               np.zeros(EDGE_LIST_MIN, np.float32), acc)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(acc))


@pytest.mark.parametrize("kind,weighted", [
    ("random", False), ("random", True), ("capped", False),
    ("capped", True), ("sparse", False)])
def test_edge_kernel_matches_the_ell_kernel_per_slice(kind, weighted):
    """Static weights and per-edge weights gathered by edge id, on
    slices with hub rows over several slices and with empty rows."""
    pg, slices = _slices(kind)
    rng = np.random.default_rng(3)
    n1 = pg.config.n1
    ew = rng.normal(size=pg.n_edges).astype(np.float32)
    for t in slices:
        h = jnp.asarray(rng.normal(size=(n1, 8)), jnp.float32)
        acc = jnp.asarray(rng.normal(size=(n1, 8)), jnp.float32)
        e = t.edges
        if weighted:
            vals = np.where(t.edge_pos >= 0,
                            ew[np.maximum(t.edge_pos, 0)], 0)
            val = np.where(e["eid"] >= 0, ew[np.maximum(e["eid"], 0)], 0)
        else:
            vals, val = t.vals, e["val"]
        want, _ = ack._spdmm_xla(h, t.cols, vals.astype(np.float32), None,
                                 acc, jnp.zeros((n1,), bool), "sum")
        got = ack._spdmm_edges_xla(h, e["dst"], e["src"],
                                   val.astype(np.float32), acc)
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=1e-6 * np.abs(want).max())


def _without_lists(gd: dict) -> dict:
    return {"tiles": {k: {f: a for f, a in d.items() if f != "edges"}
                      for k, d in gd["tiles"].items()},
            "inv_in_degree": gd["inv_in_degree"]}


@pytest.mark.parametrize("name", ["b1", "b3", "b6"])   # SUM, MEAN, GAT
@pytest.mark.parametrize("overlap", [True, False])
def test_a_pass_over_edge_lists_matches_one_over_ell_slots(name, overlap):
    """The same program over the same tiles, once with their edge lists
    and once without (ELL slots), jitted and tile by tile: 1e-6 of the
    output's scale apart, and only the list run gathers fewer rows."""
    g = _graph("capped")
    x = jnp.asarray(G.random_features(g, seed=2))
    eng = Engine(geometry=_geom("capped"), n_pes=4, overlap=overlap)
    prog = eng.compile(name, g)
    gd = as_graph_data(prog.pgraph)
    y_list = np.asarray(eng.run(prog, x, graph_data=gd))
    rows_list = eng.exec_stats.spdmm_rows
    y_ell = np.asarray(eng.run(prog, x, graph_data=_without_lists(gd)))
    rows_ell = eng.exec_stats.spdmm_rows
    np.testing.assert_allclose(y_list, y_ell, rtol=0,
                               atol=1e-6 * np.abs(y_ell).max())
    assert 0 < rows_list < rows_ell


def _spdmm_keys():
    return {k for k in ack.counter_snapshot() if k[0] == "spdmm"}


def test_max_min_pallas_and_listless_tiles_keep_the_ell_kernel():
    g = _graph("random")
    x = jnp.asarray(G.random_features(g, seed=4))
    eng = Engine(geometry=GEOM, n_pes=4, overlap=False)

    def ell_only(prog, **kw):
        ack.reset_counter()
        eng.run(prog, x, **kw)
        keys = _spdmm_keys()
        assert keys and not any("edges" in k for k in keys)
        slots = sum(prog.pgraph.tiles[(tp.out_j, ins.args[1])][
            ins.args[3] >> 1].cols.size
            for lp in prog.plan().layers
            if lp.layer_type == LayerType.AGGREGATE
            for tp in lp.tiles for ins in tp.compute)
        assert eng.exec_stats.spdmm_rows == slots

    for op in (AggOp.MAX, AggOp.MIN):
        m = B.build_gcn(g, 8, 2)
        for layer in m.layers.values():
            if layer.layer_type == LayerType.AGGREGATE:
                layer.agg_op = op
        ell_only(eng.compile(m, g))
    prog = eng.compile("b1", g)
    ell_only(prog, graph_data=_without_lists(as_graph_data(prog.pgraph)))
    ack.reset_counter()
    eng.run(prog, x)                          # its own tiles: the lists
    assert _spdmm_keys() and all("edges" in k for k in _spdmm_keys())
    pallas = Engine(geometry=GEOM, n_pes=4, backend="pallas",
                    interpret=True)
    small = G.random_graph(64, 200, seed=0).gcn_normalized()
    small.feat_dim, small.n_classes = 8, 4
    ack.reset_counter()
    pallas.run(pallas.compile("b1", small),
               jnp.asarray(G.random_features(small, seed=1)))
    keys = _spdmm_keys()
    assert keys and all(k[-1] == "pallas" and "edges" not in k
                        for k in keys)


def _gcn_max(g):
    m = B.build_gcn(g, 8, 2)
    for layer in m.layers.values():
        if layer.layer_type == LayerType.AGGREGATE:
            layer.agg_op = AggOp.MAX
    return m


ELL = ("cols", "vals", "epos")
LIST = ("edges.dst", "edges.src")


@pytest.mark.parametrize("model,backend,fields", [
    ("b1", "xla", LIST + ("edges.val",)),               # GCN: lists only
    ("b3", "xla", LIST + ("edges.val",)),               # SAGE (MEAN)
    ("b6", "xla", ("cols", "epos") + LIST + ("edges.eid",)),  # GAT
    ("max", "xla", ELL),
    ("b1", "pallas", ELL)])
def test_a_program_places_only_the_tile_arrays_it_reads(model, backend,
                                                        fields):
    g = _graph("random")
    eng = Engine(geometry=GEOM, n_pes=4, backend=backend, interpret=True)
    prog = eng.compile(_gcn_max(g) if model == "max" else model, g)
    assert tile_fields(prog.plan(), backend) == fields
    pg = prog.pgraph
    tile = device_tiles(pg, fields)["tiles"]["0:0:0"]
    assert sorted(tile) == sorted(
        [f for f in fields if f in ELL]
        + (["edges"] if fields[-1].startswith("edges.") else []))
    assert sorted(f"edges.{f}" for f in tile.get("edges", {})) == sorted(
        f for f in fields if f.startswith("edges."))
    # the budget gate counts the same bytes
    static, _, _ = eng._executor._live_profile(prog)
    rest = static - pg.tile_bytes(fields)
    assert rest == sum(np.asarray(w).nbytes for w in prog.weights.values()
                       ) + pg.inv_in_degree.nbytes
    # a graph_data tile without a list keeps its ELL planes
    gd = select_tiles(_without_lists(as_graph_data(pg)), fields)
    assert {"cols", "vals"} <= set(gd["tiles"]["0:0:0"])


def test_a_bucket_carries_the_subgraph_slices_lists_at_one_length():
    parent = G.random_graph(300, 3000, seed=3, degree="powerlaw",
                            dedupe=True)
    sub = sample_ego(parent, [5, 90, 200], (6, 4), seed=7).graph \
        .gcn_normalized()
    bucket = bucket_for(sub, GEOM)
    length = bucket.list_length(GEOM.n1)
    gd = layout_graph(sub, bucket, GEOM)
    own = partition_graph(sub, GEOM)
    for key, d in gd["tiles"].items():
        e = d["edges"]
        assert {f: a.shape for f, a in e.items()} == {
            f: (length,) for f in ("dst", "src", "val", "eid")}
        j, k, _ = map(int, key.split(":"))
        mine = own.tiles.get((j, k), [])
        assert len(mine) <= 1
        m = mine[0].nnz if mine else 0
        for f in ("dst", "src", "val", "eid"):
            if mine:
                np.testing.assert_array_equal(e[f][:m],
                                              mine[0].edges[f][:m])
        assert np.all(e["val"][m:] == 0) and np.all(e["eid"][m:] == -1)
        assert np.all(e["dst"][m:] == GEOM.n1 - 1)

"""GAT (b6) through ``Engine.run`` against the benchmark's independent
plain reference (``perfbench/references/gat.py``: dense single-head GAT,
the GraphAGILE paper's Eq. 4, which imports nothing of the program).

The graph is small, but it holds what the tiled edge path must get
right: a destination row whose edges span three source blocks and, in
one of them, three ELL slices (width cap 8), so its edge softmax
reduces across tiles and slices; and a vertex whose only edge is its
self loop.  The program takes the reference's seeded weights in its
builder's order (``program_leaves``).
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import Graph
from repro.core.passes.partition import PartitionConfig
from repro.engine import Engine

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "perfbench"))
from harness import system  # noqa: E402
from harness.correct import rel_err  # noqa: E402
from references import gat  # noqa: E402

N, F, C = 90, 12, 3
HUB, LONE = 5, 40                   # the wide row; the self loop alone
GEOM = PartitionConfig(n1=32, n2=8, width_cap=8)
CFG = {"arch": "gat", "program_model": "b6", "feat_dim": F, "hidden": 64,
       "n_layers": 2, "n_classes": C}
# Both sides compute in fp32; they differ in summation order (tile and
# slice order of the two-pass softmax and the ELL aggregation against
# dense segment sums), which moves each of the at most 64-term sums by
# a few units of fp32 rounding (6e-8), about 1e-7 of the output's scale
# here.  1e-5 leaves two orders of magnitude above that; the bfloat16
# reference misses it by more than two orders (about 5e-3).
TOL = 1e-5


def _graph(rng):
    src, dst = rng.integers(0, N, 300), rng.integers(0, N, 300)
    keep = (dst != LONE) & (src != dst)
    # 20 sources in HUB's own block (three slices), 5 and 4 in the others
    hub = np.r_[6:26, 33:38, 70:74]
    src = np.r_[src[keep], hub, np.arange(N)]
    dst = np.r_[dst[keep], np.full(hub.size, HUB), np.arange(N)]
    src, dst = np.unique(np.stack([src, dst], 1), axis=0).T.astype(np.int32)
    return Graph(n_vertices=N, src=src, dst=dst,
                 weight=np.ones(src.size, np.float32), feat_dim=F,
                 n_classes=C, name="gat-ref")


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(7)
    g = _graph(rng)
    params = {}
    for name, shape, kind in gat.param_leaves(CFG):
        z = rng.standard_normal(shape).astype(np.float32)
        params[name] = z / np.sqrt(shape[0]) if kind == "weight" else 0.1 * z
    x = jnp.asarray(0.1 * rng.standard_normal((N, F)), jnp.float32)
    gd = {"src": jnp.asarray(g.src), "dst": jnp.asarray(g.dst)}
    y_ref = gat.forward(params, gd, x, N, precision="highest")
    return g, params, x, gd, y_ref


def test_graph_spans_tiles_and_slices(case):
    g = case[0]
    prog = Engine(geometry=GEOM, n_pes=4).compile("b6", g)
    row = {k: len(ts) for (j, k), ts in prog.pgraph.tiles.items()
           if j == HUB // GEOM.n1}
    assert len(row) == 3 and row[HUB // GEOM.n1] == 3
    assert g.dst.tolist().count(LONE) == 1


@pytest.mark.parametrize("overlap", [True, False], ids=["jitted", "eager"])
def test_b6_matches_the_plain_reference(case, overlap):
    g, params, x, _, y_ref = case
    eng = Engine(geometry=GEOM, n_pes=4, overlap=overlap)
    prog = eng.compile(system.model_ir(CFG, g,
                                       gat.program_leaves(params, CFG)), g)
    y = eng.run(prog, x)
    assert eng.exec_stats.pass_compiles == int(overlap)
    assert rel_err(y, y_ref) <= TOL


def test_bfloat16_reference_misses_the_tolerance(case):
    _, params, x, gd, y_ref = case
    y_bf16 = gat.forward(params, gd, x, N, dtype=jnp.bfloat16,
                         precision="default")
    assert rel_err(y_bf16, y_ref) > 100 * TOL

"""Binary-driven overlay execution vs. the pure-jnp reference (the paper's
correctness claim: same results, no reconfiguration across models/graphs).

All execution goes through ``repro.engine.Engine`` — i.e. every check here
exercises the decode-the-128-bit-binary path, not in-memory IR walking.
"""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ack
from repro.core import gnn_builders as B
from repro.core import graph as G
from repro.core import reference as R
from repro.core.ir import AggOp
from repro.core.passes.partition import PartitionConfig
from repro.engine import Engine

GEOM = PartitionConfig(n1=32, n2=8)


def _engine(backend="xla", **kw) -> Engine:
    return Engine(geometry=GEOM, n_pes=4, backend=backend, **kw)


def _g(nv=90, ne=400, f=12, c=4, seed=0, degree="uniform", norm="gcn"):
    g = G.random_graph(nv, ne, seed=seed, degree=degree)
    if norm == "gcn":
        g = g.gcn_normalized()
    g.feat_dim, g.n_classes = f, c
    return g


def _check(name, g, engine=None, **compile_kw):
    x = jnp.asarray(G.random_features(g, seed=2))
    m = B.build(name, g)
    y_ref = R.run_reference(m, g, x)
    eng = engine or _engine()
    prog = eng.compile(m, g, **compile_kw)
    y = eng.run(prog, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)
    return prog


@pytest.mark.parametrize("name", list(B.BENCHMARKS))
def test_all_benchmarks_match_reference(name):
    _check(name, _g())


@pytest.mark.parametrize("name", ["b1", "b3", "b6"])
def test_powerlaw_graphs(name):
    _check(name, _g(nv=150, ne=1200, degree="powerlaw", seed=5))


def test_no_opt_path_matches():
    g = _g(seed=7)
    _check("b5", g, order_opt=False, fusion=False)


def test_overlap_off_matches():
    _check("b2", _g(seed=3), engine=_engine(overlap=False))


def test_pallas_backend_matches():
    eng = _engine(backend="pallas", interpret=True)
    _check("b1", _g(nv=64, ne=200, f=8), engine=eng)
    assert eng.exec_stats.pallas_fallbacks == 0
    _check("b6", _g(nv=64, ne=200, f=8), engine=eng)
    # GAT's pair-sum scores have no Pallas kernel: counted, not hidden
    assert eng.exec_stats.pallas_fallbacks > 0


def test_max_min_aggregation():
    g = _g(seed=9)
    x = jnp.asarray(G.random_features(g, seed=4))
    eng = _engine()
    for op in (AggOp.MAX, AggOp.MIN):
        m = B.build_gcn(g, 8, 2)
        for l in m.layers.values():
            if l.layer_type.name == "AGGREGATE":
                l.agg_op = op
        y_ref = R.run_reference(m, g, x)
        prog = eng.compile(m, g)
        y = eng.run(prog, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)


def test_overlay_property_no_recompile_across_models():
    """Changing model/graph must not grow the jit cache when tile shapes
    are unchanged — the FPGA 'no reconfiguration' claim, XLA edition."""
    g1 = _g(seed=11)
    g2 = _g(nv=120, ne=700, f=12, c=4, seed=12)
    eng = _engine()
    x1 = jnp.asarray(G.random_features(g1, seed=1))
    x2 = jnp.asarray(G.random_features(g2, seed=1))

    eng.run(eng.compile(B.build("b2", g1), g1), x1)
    ack.reset_counter()
    # same tile geometry, different model AND different graph:
    eng.run(eng.compile(B.build("b3", g2), g2), x2)
    counts = ack.counter_snapshot()
    gemm_keys = {k for k in counts if k[0] == "gemm"}
    spdmm_keys = {k for k in counts if k[0] == "spdmm"}
    # tile geometry is fixed by (n1, n2): one gemm variant, spdmm variants
    # only differ in ELL width (graph-dependent, lane-quantized).
    assert len(gemm_keys) <= 1
    assert all(k[1] == (32, 8) for k in gemm_keys | spdmm_keys)


def test_executor_handles_isolated_vertices():
    g = _g(nv=100, ne=30, seed=13)  # most vertices have no edges
    _check("b1", g)
    _check("b5", g)


def test_deprecated_shims_still_work():
    """compile_model + OverlayExecutor must keep working (and warn)."""
    from repro.core.compiler import CompileOptions, compile_model
    from repro.core.executor import OverlayExecutor

    g = _g(seed=17)
    x = jnp.asarray(G.random_features(g, seed=2))
    m = B.build("b1", g)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        cr = compile_model(m, g, CompileOptions(partition=GEOM, n_pes=4))
        ex = OverlayExecutor()
        assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    y = ex.run(cr.program, x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(R.run_reference(m, g, x)),
                               rtol=2e-4, atol=2e-5)

"""The benchmark's data: the deployed graph, features and weights.

The graph generator starts from the paper-statistics synthesizer of the
program (Zipf-distributed endpoints for the power-law graphs, uniform
ones for the citation graphs), copied here so that a change to the
program cannot change the graph it is measured on; without
``"undirected"`` it builds the same edges as
``repro.core.graph.synthesize`` from the same graph seed.  A
configuration that states ``"undirected": true`` gets a simple,
symmetric graph with the Zipf exponent it states, as the published
benchmark graphs are.

The graph is the deployment and is fixed by the configuration's
``graph_seed``; features and weights come from the run's ``--seed``, in
fp32 (the type they are served in): the weights in one jitted call on
the device, the features in that call or in host memory.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import seed32


def _simple_undirected(cfg: dict, rng) -> Tuple[np.ndarray, np.ndarray]:
    """``n_edges / 2`` distinct vertex pairs with distinct ends, each end
    drawn Zipf(``alpha``) by vertex id, kept in the order first drawn,
    then mirrored: ``n_edges`` directed edges, no multi-edges, no self
    loops, every edge in both directions."""
    nv, want = cfg["n_vertices"], cfg["n_edges"] // 2
    cdf = np.cumsum(np.arange(1, nv + 1, dtype=np.float64) ** -cfg["alpha"])
    cdf /= cdf[-1]
    keys = np.zeros(0, np.int64)
    while keys.shape[0] < want:
        m = 2 * (want - keys.shape[0]) + 1024
        u = np.minimum(np.searchsorted(cdf, rng.random(m)), nv - 1)
        v = np.minimum(np.searchsorted(cdf, rng.random(m)), nv - 1)
        ok = u != v
        pair = np.minimum(u, v)[ok].astype(np.int64) * nv \
            + np.maximum(u, v)[ok]
        k = np.concatenate([keys, pair])
        _, first = np.unique(k, return_index=True)
        keys = k[np.sort(first)][:want]
    a, b = (keys // nv).astype(np.int32), (keys % nv).astype(np.int32)
    return np.concatenate([a, b]), np.concatenate([b, a])


def synth_edges(cfg: dict) -> Tuple[np.ndarray, np.ndarray]:
    """``(src, dst)`` int32 edge arrays of the configuration's graph,
    before self loops.  With ``"undirected": true`` the graph is simple
    and symmetric (``_simple_undirected``); otherwise every endpoint is
    an independent draw, as the program's own synthesizer makes it."""
    nv, ne = cfg["n_vertices"], cfg["n_edges"]
    rng = np.random.default_rng(cfg["graph_seed"])
    if cfg.get("undirected"):
        if cfg["degree"] != "powerlaw":
            raise ValueError("an undirected graph draws Zipf endpoints")
        return _simple_undirected(cfg, rng)
    if cfg["degree"] == "powerlaw":
        ranks = np.arange(1, nv + 1, dtype=np.float64)
        p = ranks ** -cfg["alpha"]
        p /= p.sum()
        dst = rng.choice(nv, size=ne, p=p).astype(np.int32)
        src = rng.choice(nv, size=ne, p=p).astype(np.int32)
    elif cfg["degree"] == "uniform":
        src = rng.integers(0, nv, ne, dtype=np.int32)
        dst = rng.integers(0, nv, ne, dtype=np.int32)
    else:
        raise ValueError(f"unknown degree profile {cfg['degree']!r}")
    return src, dst


def add_self_loops(n: int, src: np.ndarray, dst: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    v = np.arange(n, dtype=np.int32)
    return np.concatenate([src, v]), np.concatenate([dst, v])


def gcn_weights(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """``1 / sqrt(deg(src) deg(dst))`` with in-degrees counted over the
    given edges (self loops included by the caller)."""
    deg = np.maximum(np.bincount(dst, minlength=n).astype(np.float64), 1.0)
    inv = 1.0 / np.sqrt(deg)
    return (inv[src] * inv[dst]).astype(np.float32)


def edge_weights(norm: str, n: int, src: np.ndarray, dst: np.ndarray
                 ) -> np.ndarray:
    """Edge weights by normalization: ``gcn`` symmetric
    (``gcn_weights``), ``mean`` ``1 / in-degree(dst)``, ``none`` 1."""
    if norm == "gcn":
        return gcn_weights(n, src, dst)
    if norm == "mean":
        deg = np.maximum(np.bincount(dst, minlength=n), 1)
        return (1.0 / deg[dst]).astype(np.float32)
    if norm == "none":
        return np.ones(src.shape[0], np.float32)
    raise ValueError(f"unknown norm {norm!r}")


def deployed_graph(cfg: dict) -> Dict[str, np.ndarray]:
    """The graph a full-graph pass runs on: self loops and edge
    weights as the configuration states them."""
    n = cfg["n_vertices"]
    src, dst = synth_edges(cfg)
    if cfg["self_loops"]:
        src, dst = add_self_loops(n, src, dst)
    return {"n": n, "src": src, "dst": dst,
            "weight": edge_weights(cfg["norm"], n, src, dst)}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, feat_shape: Optional[Tuple[int, int]],
          leaves: Tuple[Tuple[str, Tuple[int, ...], str], ...]):
    keys = jax.random.split(key, len(leaves) + 1)
    x = None if feat_shape is None else \
        0.1 * jax.random.normal(keys[0], feat_shape, jnp.float32)
    params = {}
    for k, (name, shape, kind) in zip(keys[1:], leaves):
        z = jax.random.normal(k, shape, jnp.float32)
        params[name] = z / np.sqrt(shape[0]) if kind == "weight" \
            else 0.1 * z
    return x, params


def make_inputs(seed: int, n_vertices: int, feat_dim: int,
                leaves: List[Tuple[str, Tuple[int, ...], str]],
                features: str = "device"):
    """Features ``[V, F]`` and the named parameters.  ``leaves`` lists
    ``(name, shape, "weight" | "bias")``; weights are N(0, 1/fan_in),
    biases and features N(0, 0.01).  The parameters are made on the
    default device; the features there too, or, with ``features="host"``,
    in host memory (where a service that gathers rows per request keeps
    them), so they never take device memory."""
    key = jax.random.key(seed32(seed, 0))
    leaves = tuple((n, tuple(s), k) for n, s, k in leaves)
    if features == "device":
        return _make(key, (n_vertices, feat_dim), leaves)
    if features != "host":
        raise ValueError(f"features on {features!r}")
    _, params = _make(key, None, leaves)
    rng = np.random.default_rng([seed, 4])
    x = rng.standard_normal((n_vertices, feat_dim), dtype=np.float32)
    x *= np.float32(0.1)
    return x, params

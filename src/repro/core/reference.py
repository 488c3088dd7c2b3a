"""Pure-jnp reference execution of a ModelIR on a graph.

This is (1) the correctness oracle for the compiled overlay executor and
(2) the stand-in for the framework baseline (PyG/DGL-style whole-graph
execution) in the benchmarks: every layer materializes full |V|xF
intermediates with no partitioning, fusion, or reordering.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph
from .ir import Activation, AggOp, LayerIR, LayerType, ModelIR


def apply_activation(x: jnp.ndarray, act: Activation) -> jnp.ndarray:
    if act == Activation.NONE:
        return x
    if act == Activation.RELU:
        return jax.nn.relu(x)
    if act == Activation.LRELU:
        return jax.nn.leaky_relu(x, 0.2)
    if act == Activation.PRELU:
        return jnp.where(x >= 0, x, 0.25 * x)
    if act in (Activation.SWISH, Activation.SILU):
        return x * jax.nn.sigmoid(x)
    if act == Activation.EXP:
        return jnp.exp(x)
    if act == Activation.SIGMOID:
        return jax.nn.sigmoid(x)
    if act == Activation.GELU:
        return jax.nn.gelu(x)
    raise ValueError(f"activation {act} must be handled by caller")


def edge_softmax(ew: jnp.ndarray, dst: jnp.ndarray, n: int) -> jnp.ndarray:
    """Softmax of edge scores over incoming edges of each destination."""
    mx = jax.ops.segment_max(ew, dst, num_segments=n)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    ex = jnp.exp(ew - mx[dst])
    den = jax.ops.segment_sum(ex, dst, num_segments=n)
    return ex / jnp.maximum(den[dst], 1e-12)


def aggregate(
    x: jnp.ndarray, g: Graph, op: AggOp, edge_w: Optional[jnp.ndarray]
) -> jnp.ndarray:
    """out[dst] = AggOp_{e=(src,dst)} (w_e * x[src])   (paper Eq. 5)."""
    src = jnp.asarray(g.src)
    dst = jnp.asarray(g.dst)
    w = jnp.asarray(g.weight) if edge_w is None else edge_w
    msg = x[src] * w[:, None]
    n = g.n_vertices
    if op == AggOp.SUM:
        return jax.ops.segment_sum(msg, dst, num_segments=n)
    if op == AggOp.MEAN:
        s = jax.ops.segment_sum(msg, dst, num_segments=n)
        deg = jax.ops.segment_sum(jnp.ones_like(w), dst, num_segments=n)
        return s / jnp.maximum(deg, 1.0)[:, None]
    if op == AggOp.MAX:
        m = jax.ops.segment_max(msg, dst, num_segments=n)
        return jnp.where(jnp.isfinite(m), m, 0.0)
    if op == AggOp.MIN:
        m = jax.ops.segment_min(msg, dst, num_segments=n)
        return jnp.where(jnp.isfinite(m), m, 0.0)
    raise ValueError(op)


def run_reference(
    model: ModelIR, g: Graph, x: jnp.ndarray,
    weights: Optional[Dict[str, np.ndarray]] = None,
) -> jnp.ndarray:
    """Execute the IR layer by layer; returns the final layer's output."""
    weights = weights if weights is not None else model.weights
    vals: Dict[int, jnp.ndarray] = {}

    def inp(lid_or_input: int) -> jnp.ndarray:
        return x if lid_or_input == -1 else vals[lid_or_input]

    out_id = None
    for lid in model.topo_order():
        l: LayerIR = model.layers[lid]
        feat_parents = [p for p in l.parent_ids
                        if p != l.attrs.get("edge_weight_layer")]
        h = vals[feat_parents[0]] if feat_parents else x

        if l.layer_type == LayerType.AGGREGATE:
            ewl = l.attrs.get("edge_weight_layer")
            ew = vals[ewl] if ewl is not None else None
            y = aggregate(h, g, l.agg_op, ew)
        elif l.layer_type == LayerType.LINEAR:
            W = jnp.asarray(weights[l.attrs["W"]])
            y = h @ W
            if "b" in l.attrs:
                y = y + jnp.asarray(weights[l.attrs["b"]])
        elif l.layer_type == LayerType.VECTOR_INNER:
            src, dst = jnp.asarray(g.src), jnp.asarray(g.dst)
            if l.attrs.get("mode") == "pair_sum":
                y = h[src, 0] + h[dst, 1]
            else:
                y = jnp.sum(h[src] * h[dst], axis=-1)
        elif l.layer_type == LayerType.VECTOR_ADD:
            a, b = l.attrs["operands"]
            y = l.attrs["alpha"] * inp(a) + l.attrs["beta"] * inp(b)
        elif l.layer_type == LayerType.ACTIVATION:
            if l.act == Activation.EDGE_SOFTMAX:
                y = edge_softmax(h, jnp.asarray(g.dst), g.n_vertices)
            else:
                y = apply_activation(h, l.act)
        elif l.layer_type == LayerType.BATCHNORM:
            p = {k: jnp.asarray(weights[l.attrs[k]])
                 for k in ("mu", "sigma", "gamma", "beta")}
            eps = l.attrs.get("eps", 1e-5)
            y = (h - p["mu"]) / jnp.sqrt(p["sigma"] ** 2 + eps)
            y = y * p["gamma"] + p["beta"]
        else:
            raise ValueError(l.layer_type)

        # Fused epilogues (set by the fusion pass): scale/shift then act.
        if "fused_scale" in l.attrs:
            y = (y * jnp.asarray(weights[l.attrs["fused_scale"]])
                 + jnp.asarray(weights[l.attrs["fused_shift"]]))
        if "fused_act" in l.attrs:
            fa = Activation(l.attrs["fused_act"])
            if fa == Activation.EDGE_SOFTMAX:
                y = edge_softmax(y, jnp.asarray(g.dst), g.n_vertices)
            else:
                y = apply_activation(y, fa)
        vals[lid] = y
        out_id = lid
    # Output = last layer in topo order with no children.
    sinks = [i for i, l in model.layers.items() if not l.child_ids]
    return vals[sinks[-1]] if sinks else vals[out_id]


def run_reference_fp32(
    model: ModelIR, g: Graph, x: jnp.ndarray,
    weights: Optional[Dict[str, np.ndarray]] = None,
) -> jnp.ndarray:
    """:func:`run_reference` with every matmul at full fp32 precision
    (``"highest"``) — the oracle the engine is held to on any backend.
    A TPU's default fp32 matmul rounds its operands to bf16, so an
    oracle at default precision would carry errors of its own."""
    with jax.default_matmul_precision("highest"):
        return run_reference(model, g, x, weights)


def max_errors(y, y_ref) -> Tuple[float, float]:
    """(max |y - y_ref|, the same over max |y_ref|): the absolute and
    the scale-relative agreement a tolerance is stated against."""
    err = float(jnp.max(jnp.abs(jnp.asarray(y) - jnp.asarray(y_ref))))
    scale = float(jnp.max(jnp.abs(jnp.asarray(y_ref))))
    return err, err / max(scale, 1e-30)

"""Plain single-head GAT (Velickovic et al., arXiv:1710.10903; the
GraphAGILE paper's Eq. 4), the reference for the ``gat``
configurations.  Per layer:

    Z = H W + b
    s = Z A                          (A: [F, 2], no bias)
    e_ji = LeakyReLU_0.2(s[j, 0] + s[i, 1])   for each edge j -> i
    alpha_ji = softmax of e_ji over the edges into i
    H' = act(sum_j alpha_ji Z_j),    act = ReLU except after the last

Dense ``jax.numpy`` only; it imports nothing of the program.  ``dtype``
and ``precision`` are the storage type and the matmul precision: fp32
at the configuration's precision is the reference, bfloat16 the
lower-precision control.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from ._common import dims, matmul


def param_leaves(cfg: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    out = []
    for l, (f_in, f_out) in enumerate(dims(cfg)):
        out += [(f"W{l}", (f_in, f_out), "weight"),
                (f"b{l}", (f_out,), "bias"),
                (f"A{l}", (f_out, 2), "weight")]
    return out


def program_leaves(params: dict, cfg: dict) -> List[tuple]:
    """The weights of each linear layer in the order the program's
    builder creates them: the projection ``(W, b)``, then the score
    matrix ``(A,)``, per GAT layer."""
    out = []
    for l in range(len(dims(cfg))):
        out += [(params[f"W{l}"], params[f"b{l}"]), (params[f"A{l}"],)]
    return out


def work(cfg: dict, v: int, e: int) -> dict:
    """Algorithmic work of one pass (``harness.work``): per layer the
    projection and the two attention scores (GEMMs), the pair-sum SDDMM,
    and the aggregation at ``f_out``, since GAT projects first by
    definition."""
    from harness import work as w
    out = {"gemm": [], "sddmm": [], "spdmm": []}
    for f_in, f_out in dims(cfg):
        out["gemm"] += [w.gemm(v, f_in, f_out), w.gemm(v, f_out, 2)]
        out["sddmm"].append(w.sddmm_pair(v, e))
        out["spdmm"].append(w.spdmm(v, e, f_out))
    return out


def edge_softmax(e, dst, n: int):
    mx = jax.ops.segment_max(e, dst, num_segments=n)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0).astype(e.dtype)
    ex = jnp.exp(e - mx[dst])
    den = jax.ops.segment_sum(ex, dst, num_segments=n)
    return ex / den[dst]


def forward(params: dict, g: dict, x, n: int, dtype=jnp.float32,
            precision: str = "highest"):
    """Logits ``[n, classes]``; ``g`` holds ``src`` and ``dst``."""
    n_layers = len([k for k in params if k.startswith("W")])
    src, dst = g["src"], g["dst"]
    h = x.astype(dtype)
    for l in range(n_layers):
        z = matmul(h, params[f"W{l}"].astype(dtype), dtype, precision) \
            + params[f"b{l}"].astype(dtype)
        s = matmul(z, params[f"A{l}"].astype(dtype), dtype, precision)
        e = jax.nn.leaky_relu(s[src, 0] + s[dst, 1], 0.2)
        alpha = edge_softmax(e, dst, n)
        h = jax.ops.segment_sum(z[src] * alpha[:, None], dst,
                                num_segments=n)
        if l < n_layers - 1:
            h = jax.nn.relu(h)
    return h

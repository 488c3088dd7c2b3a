"""Plain GCN (Kipf & Welling, arXiv:1609.02907), the reference for the
``gcn`` configurations.

    H_{l+1} = act(A_hat H_l W_l [+ b_l]),   act = ReLU except after the last

The published layer has no bias (``"bias": false`` in the
configuration); with ``"bias": true`` a bias is added after the
aggregation.  ``A_hat`` is the graph as given: edges ``(src, dst)``
with weights (symmetric normalization over the self-looped graph,
computed by the benchmark).  Dense ``jax.numpy`` only, no kernels,
tiles or batching; it imports nothing of the program.  ``dtype`` and
``precision`` are the storage type and the matmul precision: fp32 at
the configuration's precision is the reference, bfloat16 the
lower-precision control.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ._common import dims, matmul


def param_leaves(cfg: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    out = []
    for l, (f_in, f_out) in enumerate(dims(cfg)):
        out.append((f"W{l}", (f_in, f_out), "weight"))
        if cfg["bias"]:
            out.append((f"b{l}", (f_out,), "bias"))
    return out


def program_leaves(params: dict, cfg: dict) -> List[tuple]:
    """The weights of each linear layer, in the order the program's
    builder creates its linear layers: ``(W, b)`` per GCN layer, ``b``
    zero where the configuration has no bias."""
    return [(params[f"W{l}"],
             params[f"b{l}"] if cfg["bias"] else np.zeros(f_out, np.float32))
            for l, (_, f_out) in enumerate(dims(cfg))]


def work(cfg: dict, v: int, e: int) -> dict:
    """Algorithmic work of one pass (``harness.work``): per layer a GEMM
    and an aggregation at ``min(f_in, f_out)``, since ``A (X W)`` and
    ``(A X) W`` are the same layer and the smaller width is the least
    work."""
    from harness import work as w
    out = {"gemm": [], "spdmm": []}
    for f_in, f_out in dims(cfg):
        out["gemm"].append(w.gemm(v, f_in, f_out))
        out["spdmm"].append(w.spdmm(v, e, min(f_in, f_out)))
    return out


def forward(params: dict, g: dict, x, n: int, dtype=jnp.float32,
            precision: str = "highest"):
    """Logits ``[n, classes]``; ``g`` holds ``src``, ``dst``, ``weight``."""
    n_layers = len([k for k in params if k.startswith("W")])
    h = x.astype(dtype)
    w = g["weight"].astype(dtype)
    for l in range(n_layers):
        hw = matmul(h, params[f"W{l}"].astype(dtype), dtype, precision)
        h = jax.ops.segment_sum(hw[g["src"]] * w[:, None], g["dst"],
                                num_segments=n)
        if f"b{l}" in params:
            h = h + params[f"b{l}"].astype(dtype)
        if l < n_layers - 1:
            h = jax.nn.relu(h)
    return h

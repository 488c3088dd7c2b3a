"""How the benchmark hands its data to the system under test: the
program's ``Graph``, and the program's own model builder with the
benchmark's weights put in place of the builder's."""
from __future__ import annotations

import importlib
from typing import List, Optional

import numpy as np

from repro.core import gnn_builders
from repro.core.graph import Graph
from repro.core.ir import LayerType, ModelIR
from repro.core.passes.partition import PartitionConfig


def reference_module(cfg: dict):
    """The plain reference of the configuration's architecture."""
    return importlib.import_module(f"references.{cfg['arch']}")


def program_graph(cfg: dict, n: int, src, dst, weight, name: str) -> Graph:
    return Graph(n_vertices=n, src=np.asarray(src, np.int32),
                 dst=np.asarray(dst, np.int32),
                 weight=np.asarray(weight, np.float32),
                 feat_dim=cfg["feat_dim"], n_classes=cfg["n_classes"],
                 name=name)


def model_ir(cfg: dict, graph: Graph, leaves: List[tuple]) -> ModelIR:
    """The program's builder for ``cfg["program_model"]`` with every
    linear layer's weights replaced, in layer order, by ``leaves``
    (``(W,)`` or ``(W, b)`` each, as the reference lists them)."""
    m = gnn_builders.build(cfg["program_model"], graph, 0)
    linear = [m.layers[i] for i in sorted(m.layers)
              if m.layers[i].layer_type == LayerType.LINEAR]
    if len(linear) != len(leaves):
        raise ValueError(f"{cfg['program_model']} has {len(linear)} linear "
                         f"layers, the reference {len(leaves)}")
    for layer, leaf in zip(linear, leaves):
        keys = [layer.attrs["W"]] + (
            [layer.attrs["b"]] if "b" in layer.attrs else [])
        if len(keys) != len(leaf):
            raise ValueError(f"layer {layer.layer_id}: {len(keys)} weight "
                             f"arrays in the program, {len(leaf)} given")
        for k, a in zip(keys, leaf):
            a = np.asarray(a, np.float32)
            if a.shape != m.weights[k].shape:
                raise ValueError(f"{k}: shape {a.shape}, program expects "
                                 f"{m.weights[k].shape}")
            m.weights[k] = a
    return m


def geometry(spec: Optional[dict]) -> Optional[PartitionConfig]:
    """An explicit tile geometry, or ``None`` for the program's own."""
    return None if spec is None else PartitionConfig(**spec)

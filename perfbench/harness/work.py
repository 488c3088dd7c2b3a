"""Algorithmic work of one full-graph pass, from graph and model shapes.

The counts never look at ELL slots, padded tiles or how a kernel is
implemented, so a roofline share reads the same work whatever runs it.
Every value is fp32 (4 bytes), the type the configurations serve.

Per layer of ``f_in -> f_out`` on a graph of ``V`` vertices and ``E``
edges (self loops included):

* GEMM: ``2 V f_in f_out`` FLOPs; bytes read the input and the weights
  once and write the output once.
* SpDMM (aggregation at width ``F``): ``2 E F`` FLOPs; the least bytes
  read the source features once, the edge list once (a source id and a
  value per edge, ``V + 1`` row offsets) and write the output once.
* SDDMM, GAT's pair-sum form (``e_ij = s_l[j] + s_r[i]``): ``E`` FLOPs;
  bytes read the two per-vertex scores and the edge list (source and
  destination ids) once and write one score per edge.

Which of these a pass runs, at which widths, is the architecture's:
each plain reference (``references/<arch>.py``) counts its own pass in
``work(cfg, v, e)`` from these pieces, so a new architecture brings its
count with its reference.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

F32 = 4
Work = Dict[str, List[Tuple[float, float]]]   # mode -> [(flops, bytes)]


def gemm(v: int, f_in: int, f_out: int) -> Tuple[float, float]:
    return (2.0 * v * f_in * f_out,
            F32 * (v * f_in + f_in * f_out + v * f_out))


def spdmm(v: int, e: int, f: int) -> Tuple[float, float]:
    return (2.0 * e * f, F32 * (2 * v * f + 2 * e + v + 1))


def sddmm_pair(v: int, e: int) -> Tuple[float, float]:
    return (1.0 * e, F32 * (2 * v + 2 * e + e))


def pass_work(cfg: dict, n_vertices: int, n_edges: int) -> Work:
    """Per-mode work of one pass of ``cfg``'s model over a graph of
    ``n_vertices`` and ``n_edges`` (the graph as run, self loops in),
    as the architecture's reference counts it."""
    ref = importlib.import_module(f"references.{cfg['arch']}")
    out: Work = {"gemm": [], "spdmm": [], "sddmm": []}
    for mode, calls in ref.work(cfg, n_vertices, n_edges).items():
        out.setdefault(mode, []).extend(calls)
    return out


def pass_flops(work: Work) -> float:
    return sum(f for calls in work.values() for f, _ in calls)

"""Adaptive Computation Kernel (paper §5.4) — the unified compute engine.

One module executes every GNN kernel by mode switching: GEMM mode,
SpDMM mode, SDDMM mode, vector-addition mode, plus the activation /
affine epilogues of the Activation Unit.

Backends:
  * ``xla``    — jnp tile ops (vectorized gathers / dots), the production
                 path on CPU and the GSPMD path on TPU.
  * ``pallas`` — the hand-written Pallas kernels in ``repro.kernels``
                 (VMEM BlockSpec tiling, lowered through Mosaic;
                 ``interpret=True`` runs them on the CPU).  MAX/MIN
                 SpDMM and pair-sum SDDMM have no Pallas kernel and run
                 the xla tile op instead; ``ACK.fallbacks`` counts those
                 calls so a "pallas" run shows how much of it was not.

Every tile function is jit-compiled once per *tile shape* and cached —
never per model or per graph.  This is the overlay property: changing the
GNN model or the input graph changes the instruction stream only, exactly
like the FPGA overlay avoids reconfiguration.  ``compile_counter`` exposes
the cache behaviour to the tests/benchmarks.
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .ir import Activation
from .reference import apply_activation

# Tile-shape-keyed kernel instantiation counts.  The runtime's per-overlay
# worker threads all funnel through _count, so every mutation (and the
# reset) holds _counter_lock; readers that only iterate a snapshot should
# call ``counter_snapshot``.
compile_counter: Dict[Tuple, int] = {}
_counter_lock = threading.Lock()


def _count(key: Tuple) -> None:
    with _counter_lock:
        compile_counter[key] = compile_counter.get(key, 0) + 1


def reset_counter() -> None:
    """Clear the kernel-instantiation counter (tests/benchmarks)."""
    with _counter_lock:
        compile_counter.clear()


def counter_snapshot() -> Dict[Tuple, int]:
    """Consistent copy of the counter, safe to iterate while serving."""
    with _counter_lock:
        return dict(compile_counter)


def _mode(name: str):
    """Put a tile function's body under ``jax.named_scope("ack.<name>")``
    inside its jit: the scope costs nothing per call, and it names the
    mode's operations in every executable that inlines the function
    (the jitted batched pass), where a device trace finds them by
    scope.  ``functools.wraps`` keeps the function's name, so its own
    executable keeps its name too (``jit__spdmm_xla``, ...)."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(f"ack.{name}"):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# --------------------------------------------------------------------------- #
# GEMM mode: output-stationary blocked matmul (Algorithm 1).
# --------------------------------------------------------------------------- #
@jax.jit
@_mode("gemm")
def _gemm_xla(h: jnp.ndarray, w: jnp.ndarray, acc: jnp.ndarray) -> jnp.ndarray:
    return acc + jnp.dot(h, w, preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------- #
# SpDMM mode: blocked-ELL scatter-gather (Algorithms 2 & 4).
#   out[r] (+)= reduce_k vals[r,k] * h_src[cols[r,k]]
# SUM/MEAN never read the row flags (pad slots carry val 0), so their
# callers may pass ``mask=None`` and get ``flag`` back untouched.
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("op",))
@_mode("spdmm")
def _spdmm_xla(h_src, cols, vals, mask, acc, flag, op: str):
    gathered = h_src[cols]                       # [n1, w, n2]
    if op in ("sum", "mean"):
        msg = gathered * vals[..., None]
        out = acc + jnp.sum(msg, axis=1)
        return out, flag if mask is None else flag | mask.any(axis=1)
    big = jnp.float32(3.4e38)
    msg = gathered * vals[..., None]
    if op == "max":
        msg = jnp.where(mask[..., None], msg, -big)
        return jnp.maximum(acc, jnp.max(msg, axis=1)), flag | mask.any(axis=1)
    if op == "min":
        msg = jnp.where(mask[..., None], msg, big)
        return jnp.minimum(acc, jnp.min(msg, axis=1)), flag | mask.any(axis=1)
    raise ValueError(op)


# SpDMM over a slice's edge list (``ELLTile.edges``): SUM and MEAN read
# the real edges only, in destination order, instead of the n1 x width
# ELL slots.  The gathered rows are [m, n2] for a list of m entries, and
# pad entries carry val 0 on the last row, so they add exactly 0.  The
# scatter takes no sorted-indices hint: a caller's list is not checked.
@jax.jit
@_mode("spdmm")
def _spdmm_edges_xla(h_src, dst, src, vals, acc):
    msg = h_src[src] * vals[:, None]             # [m, n2]
    return acc.at[dst].add(msg)


# --------------------------------------------------------------------------- #
# Dense-aggregate GEMM: densified SpDMM for remapped high-density tiles
# (Dynasparse-style sparsity-adaptive mode switch).  The ELL tile is
# scattered into an (n1, n1_src) dense adjacency block and dispatched as
# a matmul on the systolic-array path.  Pad slots carry cols == 0 /
# vals == 0, so scatter-add deposits zeros harmlessly; duplicate cols
# sum, matching SpDMM's per-edge accumulation.
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("n_src",))
@_mode("densify")
def densify_tile(cols, vals, n_src: int) -> jnp.ndarray:
    """Scatter an ELL slice into its (n1, n_src) dense adjacency block.
    Executors cache the result per (j, k, s) so one densification feeds
    every output fiber's GEMM dispatch."""
    rows = jnp.arange(cols.shape[0])[:, None]
    return jnp.zeros((cols.shape[0], n_src),
                     jnp.float32).at[rows, cols].add(vals)


@jax.jit
@_mode("gemm_agg")
def _gemm_agg_xla(cols, vals, h_src, acc):
    rows = jnp.arange(cols.shape[0])[:, None]
    dense = jnp.zeros((cols.shape[0], h_src.shape[0]),
                      jnp.float32).at[rows, cols].add(vals)
    return acc + jnp.dot(dense, h_src, preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------- #
# SDDMM mode: per-edge inner products (Algorithm 3).
#   score[r, k] = <h_dst[r], h_src[cols[r, k]]>
# --------------------------------------------------------------------------- #
@jax.jit
@_mode("sddmm")
def _sddmm_xla(h_dst, h_src, cols, mask, acc):
    gathered = h_src[cols]                       # [n1, w, n2]
    part = jnp.einsum("rwf,rf->rw", gathered, h_dst)
    return acc + jnp.where(mask, part, 0.0)


@jax.jit
@_mode("sddmm")
def _sddmm_pair_xla(h_dst, h_src, cols, mask, acc):
    """GAT pair scores: score[r,k] = h_src[cols[r,k], 0] + h_dst[r, 1]."""
    part = h_src[cols][:, :, 0] + h_dst[:, 1][:, None]
    return acc + jnp.where(mask, part, 0.0)


# The coefficients are static: per-tile dispatch and a layer traced into
# one executable then fold the same constants (a 1.0 drops its multiply)
# and contract the same multiply into an FMA, so both give the same
# bits.
@functools.partial(jax.jit, static_argnames=("alpha", "beta"))
@_mode("vadd")
def _vadd_xla(a, b, alpha: float, beta: float):
    return jnp.float32(alpha) * a + jnp.float32(beta) * b


@functools.partial(jax.jit, static_argnames=("act",))
@_mode("act")
def _act_xla(x, act: int):
    return apply_activation(x, Activation(act))


@jax.jit
@_mode("affine")
def _affine_xla(x, scale, shift):
    return x * scale + shift


class ACK:
    """Mode-switched compute engine; see module docstring."""

    def __init__(self, backend: str = "xla", interpret: bool = False) -> None:
        assert backend in ("xla", "pallas")
        self.backend = backend
        self.interpret = interpret
        self.fallbacks = 0      # pallas-backend calls served by xla ops
        if backend == "pallas":
            from repro.kernels import ops as kops  # local import: optional
            self._kops = kops

    # -- GEMM ----------------------------------------------------------- #
    def gemm(self, h, w, acc):
        _count(("gemm", h.shape, w.shape, self.backend))
        if self.backend == "pallas":
            return acc + self._kops.gemm(h, w, interpret=self.interpret)
        return _gemm_xla(h, w, acc)

    # -- Dense-aggregate GEMM (remapped SpDMM tiles) --------------------- #
    def gemm_agg(self, cols, vals, h_src, acc):
        """Aggregate a remapped ELL tile by densifying it and running the
        GEMM datapath.  Always the xla scatter+dot path — densification is
        a gather-free matmul feed, which is exactly what the pallas GEMM
        kernel would see anyway."""
        _count(("gemm_agg", h_src.shape, cols.shape, self.backend))
        return _gemm_agg_xla(cols, vals, h_src, acc)

    # -- SpDMM ---------------------------------------------------------- #
    def spdmm(self, h_src, cols, vals, mask, acc, flag, op: str = "sum"):
        _count(("spdmm", h_src.shape, cols.shape, op, self.backend))
        if self.backend == "pallas":
            if op in ("sum", "mean"):
                out = acc + self._kops.spdmm(cols, vals, h_src,
                                             interpret=self.interpret)
                return out, (flag if mask is None
                             else flag | mask.any(axis=1))
            self.fallbacks += 1
        return _spdmm_xla(h_src, cols, vals, mask, acc, flag, op)

    def spdmm_edges(self, h_src, dst, src, vals, acc):
        """SUM/MEAN SpDMM over an edge list (xla backend only: a Pallas
        executor aggregates ELL slices)."""
        _count(("spdmm", h_src.shape, dst.shape, "edges", self.backend))
        return _spdmm_edges_xla(h_src, dst, src, vals, acc)

    # -- SDDMM ---------------------------------------------------------- #
    def sddmm(self, h_dst, h_src, cols, mask, acc, pair_sum: bool = False):
        _count(("sddmm", h_dst.shape, cols.shape, pair_sum, self.backend))
        if pair_sum:
            if self.backend == "pallas":
                self.fallbacks += 1
            return _sddmm_pair_xla(h_dst, h_src, cols, mask, acc)
        if self.backend == "pallas":
            return acc + jnp.where(
                mask, self._kops.sddmm(h_dst, h_src, cols,
                                       interpret=self.interpret), 0.0)
        return _sddmm_xla(h_dst, h_src, cols, mask, acc)

    # -- Vector addition / epilogues ------------------------------------ #
    def vadd(self, a, b, alpha: float, beta: float):
        _count(("vadd", a.shape, self.backend))
        return _vadd_xla(a, b, alpha=float(alpha), beta=float(beta))

    def act(self, x, act: Activation):
        _count(("act", x.shape, int(act)))
        return _act_xla(x, int(act))

    def affine(self, x, scale, shift):
        _count(("affine", x.shape))
        return _affine_xla(x, scale, shift)

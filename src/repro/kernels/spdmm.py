"""SpDMM-mode Pallas kernel (ACK SpDMM mode, paper Alg. 2/4).

Blocked-ELL sparse x dense:   out[r, :] = sum_k vals[r, k] * h[cols[r, k], :]

TPU adaptation (DESIGN.md §2): the compiler delivers each adjacency
sub-shard as a dst-sorted ELL tile, so each output row is owned by exactly
one kernel lane group — the FPGA's RAW-reorder hardware becomes a compile
time sort.  The banked-SRAM shuffle becomes a one-hot MXU gather: the
kernel scatters a row block's ELL slots into a dense (bm, n_src)
adjacency block in VMEM (one lane-compare + select per ELL column, all
plain VPU work Mosaic lowers) and multiplies it by the source sub-fiber
on the systolic array.  Pad slots (val 0) add nothing; duplicate source
columns sum, as the per-edge accumulation would.

Grid: (row blocks, feature fibers).  The adjacency block depends only on
the row block, so it is built on the first fiber and reused by the rest
(the fiber axis runs innermost and in order).  The source-feature tile
for one fiber is held whole in VMEM ((n_src, bf) — bounded by the
partition pass's VMEM budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _spdmm_kernel(cols_ref, vals_ref, h_ref, o_ref, adj_ref, *, width: int):
    @pl.when(pl.program_id(1) == 0)
    def _build_adjacency():
        # ELL columns are picked by lane masks and compared in f32 (exact
        # for indices < 2**24): only float reductions and compares.
        cols = cols_ref[...].astype(jnp.float32)
        vals = vals_ref[...].astype(jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
        src = jax.lax.broadcasted_iota(
            jnp.int32, adj_ref.shape, 1).astype(jnp.float32)
        adj_ref[...] = jnp.zeros_like(adj_ref)

        def body(k, carry):
            sel = lane == k
            c = jnp.sum(jnp.where(sel, cols, 0.0), axis=1, keepdims=True)
            v = jnp.sum(jnp.where(sel, vals, 0.0), axis=1, keepdims=True)
            adj_ref[...] += jnp.where(src == c, v, 0.0)
            return carry

        jax.lax.fori_loop(0, width, body, 0)

    o_ref[...] = jnp.dot(
        adj_ref[...], h_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bf", "width", "interpret", "out_dtype"))
def spdmm(
    cols: jnp.ndarray,       # [n1, w] int32 local src indices (0 padded)
    vals: jnp.ndarray,       # [n1, w] f32 edge weights (0 padded)
    h: jnp.ndarray,          # [n_src, f] source feature tile
    *,
    bm: int = 128,
    bf: int = 128,
    width: int | None = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """``width`` is the number of leading ELL columns that can hold edges
    (default: all ``w``); columns past it are lane padding and skipped."""
    n1, w = cols.shape
    n_src, f = h.shape
    assert n1 % bm == 0 and f % bf == 0, (cols.shape, h.shape)
    grid = (n1 // bm, f // bf)
    return pl.pallas_call(
        functools.partial(_spdmm_kernel, width=w if width is None
                          else width),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, w), lambda i, j: (i, 0)),
            pl.BlockSpec((n_src, bf), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n1, f), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, n_src), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cols, vals, h)

"""SDDMM-mode Pallas kernel (ACK SDDMM mode, paper Alg. 3).

Blocked-ELL sampled dense-dense:
  score[r, k] = < h_dst[r, :], h_src[cols[r, k], :] >

Grid: (row blocks, feature fibers); partial inner products accumulate
over the fiber axis in a VMEM f32 scratch of shape (bm, width) and flush
on the last fiber.  Each cell first scores its row block against EVERY
source row on the systolic array (one (bm, bf) x (n_src, bf)^T matmul),
then samples the ELL slots out of that (bm, n_src) block with the same
one-hot lane compare the SpDMM kernel uses — the multiply-adder tree of
the paper's UR pipeline becomes the MXU, the gather a VPU select.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sddmm_kernel(cols_ref, hd_ref, hs_ref, o_ref, acc_ref,
                  *, width: int, f_steps: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    scores = jax.lax.dot_general(
        hd_ref[...].astype(jnp.float32), hs_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # [bm, n_src]
    cols = cols_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    src = jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1).astype(jnp.float32)

    def body(k, carry):
        sel = lane == k
        c = jnp.sum(jnp.where(sel, cols, 0.0), axis=1, keepdims=True)
        s = jnp.sum(jnp.where(src == c, scores, 0.0), axis=1,
                    keepdims=True)
        acc_ref[...] += jnp.where(sel, s, 0.0)
        return carry

    jax.lax.fori_loop(0, width, body, 0)

    @pl.when(pl.program_id(1) == f_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bf", "width", "interpret", "out_dtype"))
def sddmm(
    h_dst: jnp.ndarray,      # [n1, f] destination feature tile
    h_src: jnp.ndarray,      # [n_src, f] source feature tile
    cols: jnp.ndarray,       # [n1, w] int32 local src indices
    *,
    bm: int = 128,
    bf: int = 128,
    width: int | None = None,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """``width`` as in :func:`repro.kernels.spdmm.spdmm`: columns past it
    are lane padding, left zero."""
    n1, f = h_dst.shape
    n_src, f2 = h_src.shape
    assert f == f2 and cols.shape[0] == n1
    assert n1 % bm == 0 and f % bf == 0
    w = cols.shape[1]
    grid = (n1 // bm, f // bf)
    return pl.pallas_call(
        functools.partial(_sddmm_kernel, width=w if width is None
                          else width, f_steps=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, bf), lambda i, j: (i, j)),
            pl.BlockSpec((n_src, bf), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, w), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n1, w), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cols, h_dst, h_src)

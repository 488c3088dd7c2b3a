"""Jit'd public wrappers around the Pallas kernels.

These pad arbitrary shapes up to block multiples, invoke the kernel, and
slice back — so the ACK can call them with the compiler's native tile
shapes.  ELL widths and source-row counts are padded to whole 128-lane
vregs (pad slots are zero-valued, pad rows never indexed).  Kernels lower
through Mosaic by default; ``interpret=True`` runs the kernel body on the
CPU instead (how the tests run them without a TPU).  Each wrapper's body
sits under the named scope of its ACK mode (``ack.gemm``, ``ack.spdmm``,
``ack.sddmm``), as the xla tile functions' do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import gemm as _gemm
from . import sddmm as _sddmm
from . import spdmm as _spdmm

_LANE = 128


def _pad_to(x: jnp.ndarray, mults) -> jnp.ndarray:
    pads = []
    for dim, mult in zip(x.shape, mults):
        target = (dim + mult - 1) // mult * mult
        pads.append((0, target - dim))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


@functools.partial(jax.jit, static_argnames=("interpret", "bm", "bk", "bn"))
def gemm(x, w, *, interpret: bool = False, bm: int = 128, bk: int = 128,
         bn: int = 128):
    m, n = x.shape[0], w.shape[1]
    bm_, bk_, bn_ = (min(bm, _ceil(x.shape[0])), min(bk, _ceil(x.shape[1])),
                     min(bn, _ceil(w.shape[1])))
    with jax.named_scope("ack.gemm"):
        xp = _pad_to(x, (bm_, bk_))
        wp = _pad_to(w, (bk_, bn_))
        out = _gemm.gemm(xp, wp, bm=bm_, bk=bk_, bn=bn_,
                         interpret=interpret)
        return out[:m, :n]


def _ceil(d: int, base: int = 8) -> int:
    """Smallest multiple of ``base`` >= d, capped to 128 for block picks."""
    t = (d + base - 1) // base * base
    return min(t, 128)


@functools.partial(jax.jit, static_argnames=("interpret", "bm", "bf"))
def spdmm(cols, vals, h, *, interpret: bool = False, bm: int = 128,
          bf: int = 128):
    n1, f = cols.shape[0], h.shape[1]
    bm_, bf_ = min(bm, _ceil(n1)), min(bf, _ceil(f))
    with jax.named_scope("ack.spdmm"):
        colsp = _pad_to(cols, (bm_, _LANE))
        valsp = _pad_to(vals, (bm_, _LANE))
        hp = _pad_to(h, (_LANE, bf_))
        out = _spdmm.spdmm(colsp, valsp, hp, bm=bm_, bf=bf_,
                           width=cols.shape[1], interpret=interpret)
        return out[:n1, :f]


@functools.partial(jax.jit, static_argnames=("interpret", "bm", "bf"))
def sddmm(h_dst, h_src, cols, *, interpret: bool = False, bm: int = 128,
          bf: int = 128):
    n1, w = cols.shape
    f = h_dst.shape[1]
    bm_, bf_ = min(bm, _ceil(n1)), min(bf, _ceil(f))
    with jax.named_scope("ack.sddmm"):
        hd = _pad_to(h_dst, (bm_, bf_))
        hs = _pad_to(h_src, (_LANE, bf_))
        colsp = _pad_to(cols, (bm_, _LANE))
        out = _sddmm.sddmm(hd, hs, colsp, bm=bm_, bf=bf_, width=w,
                           interpret=interpret)
        return out[:n1, :w]

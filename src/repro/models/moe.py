"""Mixture-of-Experts layer (kimi-k2, deepseek-v3).

Two implementations sharing one parameter layout:

* ``dense``  — oracle: computes every expert for every token and combines
  with router weights.  O(E/topk) extra FLOPs; used for smoke tests and as
  the correctness reference for the sharded path.

* ``a2a``    — production path: GShard-style expert parallelism inside
  ``jax.shard_map``.  Tokens are locally dispatched into per-expert
  capacity buffers, exchanged with the expert owners over the ``model``
  mesh axis with ``all_to_all``, processed, and returned.  Capacity-based
  token dropping (capacity_factor) gives static shapes; dropped tokens
  fall back to the residual stream (standard Switch behaviour).

The GraphAGILE view (DESIGN.md §4): the routing matrix is a sparse
adjacency A (tokens -> experts, top-k nonzeros per row) and this layer is
the paper's *Aggregate* executed in SpDMM mode, with the partition pass's
load balancing reappearing as the router's aux loss + capacity factor.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from .layers import Params, dense_init


def moe_init(key, d: int, f: int, n_experts: int, dtype,
             n_shared: int = 0) -> Params:
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(kr, d, n_experts, jnp.float32, std=0.02),
        "wi": dense_init(k1, d, (n_experts, f), dtype),   # stored (d,E,f)
        "wg": dense_init(k2, d, (n_experts, f), dtype),
        "wo": (dense_init(k3, f, (n_experts, d), dtype)),  # (f,E,d)
    }
    if n_shared:
        from .layers import swiglu_init
        p["shared"] = swiglu_init(ks, d, f * n_shared, dtype)
    return p


def _router(p: Params, x: jnp.ndarray, top_k: int):
    """x [N, d] -> (weights [N, k], ids [N, k], aux_loss)."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch aux loss: E * sum_e f_e * p_e
    e = logits.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    return w, ids, aux


# --------------------------------------------------------------------------- #
def moe_dense(p: Params, x: jnp.ndarray, top_k: int) -> Tuple[jnp.ndarray,
                                                              jnp.ndarray]:
    """Oracle: every expert on every token.  x [B, T, d]."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    w, ids, aux = _router(p, xf, top_k)
    e = p["router"].shape[-1]
    # combine weight per expert [N, E]
    cw = jnp.zeros((b * t, e), jnp.float32)
    cw = cw.at[jnp.arange(b * t)[:, None], ids].add(w)
    h = jnp.einsum("nd,def->nef", xf, p["wi"])
    g = jnp.einsum("nd,def->nef", xf, p["wg"])
    h = h * jax.nn.sigmoid(g.astype(jnp.float32)).astype(h.dtype)
    out = jnp.einsum("nef,fed->ned", h, p["wo"])
    out = jnp.einsum("ned,ne->nd", out.astype(jnp.float32), cw)
    y = out.reshape(b, t, d).astype(x.dtype)
    if "shared" in p:
        from .layers import swiglu
        y = y + swiglu(p["shared"], x)
    return y, aux


# --------------------------------------------------------------------------- #
def _dispatch_local(xf, w, ids, n_experts: int, cap: int):
    """Scatter local tokens into per-expert capacity buffers.

    Returns (buf [E, C, d], combine [N, k] weight, slot [N, k] in [-1, C)).
    """
    n, k = ids.shape
    flat_e = ids.reshape(-1)                                   # [N*k]
    # position of each (token, slot) within its expert, in arrival order
    oh = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)    # [N*k, E]
    pos = jnp.cumsum(oh, axis=0) - oh                          # prior count
    slot = jnp.sum(pos * oh, axis=-1)                          # [N*k]
    keep = slot < cap
    slot = jnp.where(keep, slot, -1)
    d = xf.shape[-1]
    buf = jnp.zeros((n_experts, cap, d), xf.dtype)
    tok_idx = jnp.repeat(jnp.arange(n), k)
    buf = buf.at[flat_e, jnp.maximum(slot, 0)].add(
        jnp.where(keep[:, None], xf[tok_idx], 0).astype(xf.dtype))
    return buf, slot.reshape(n, k), keep.reshape(n, k)


def moe_local(p: Params, x: jnp.ndarray, top_k: int, cap_factor: float,
              mesh, batch_axes=("pod", "data"), expert_axis: str = "model"
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Decode-path expert parallelism WITHOUT all-to-all.

    When tokens are replicated over the expert axis (decode: t == 1, too
    few tokens to sequence-shard), the a2a formulation makes every expert
    column redundantly dispatch identical tokens and exchange them —
    16x wasted expert FLOPs on a 16-way axis (EXPERIMENTS.md §Perf,
    kimi decode_32k).  Here each column filters the routing table to ITS
    local experts, computes only those, and a psum over the expert axis
    combines — collective volume = one [n, d] reduce instead of two
    [E, cap, d] exchanges.
    """
    b, t, d = x.shape
    e = p["router"].shape[-1]
    ax_size = mesh.shape[expert_axis]
    e_loc = e // ax_size
    w, ids, aux = _router(p, x.reshape(b * t, d), top_k)
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    bsz = 1
    for a in batch_axes:
        bsz *= mesh.shape[a]
    spec_x = P(batch_axes if b % bsz == 0 and bsz > 1 else None, None,
               None)
    spec_f = P(spec_x[0], None)

    def body(xl, wl, idsl, wi, wg, wo):
        bl, tl, _ = xl.shape
        n = bl * tl
        xf = xl.reshape(n, d)
        col = jax.lax.axis_index(expert_axis)
        loc = idsl.reshape(n, top_k) - col * e_loc
        mine = (loc >= 0) & (loc < e_loc)
        wl_ = jnp.where(mine, wl.reshape(n, top_k), 0.0)
        loc = jnp.where(mine, loc, 0)
        cap = max(1, -(-int(n * top_k * cap_factor) // e))
        buf, slot, keep = _dispatch_local(
            xf, wl_, jnp.where(mine, loc, e_loc), e_loc + 1, cap)
        buf = buf[:e_loc]                       # drop the spill expert
        h = jnp.einsum("ecd,def->ecf", buf, wi)
        g = jnp.einsum("ecd,def->ecf", buf, wg)
        h = h * jax.nn.sigmoid(g.astype(jnp.float32)).astype(h.dtype)
        out = jnp.einsum("ecf,fed->ecd", h, wo)
        fe = loc.reshape(-1)
        fs = jnp.maximum(slot.reshape(-1), 0)
        ok = keep.reshape(-1) & mine.reshape(-1)
        got = out[fe, fs] * ok[:, None]
        got = got * wl_.reshape(-1)[:, None].astype(got.dtype)
        tok = jnp.repeat(jnp.arange(n), top_k)
        y = jax.ops.segment_sum(got.astype(jnp.float32), tok,
                                num_segments=n)
        y = jax.lax.psum(y, expert_axis)
        return y.reshape(bl, tl, d).astype(xl.dtype)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_x, spec_f, spec_f, P(None, expert_axis, None),
                  P(None, expert_axis, None), P(None, expert_axis, None)),
        out_specs=spec_x,
    )(x, w.reshape(b, t * top_k), ids.reshape(b, t * top_k),
      p["wi"], p["wg"], p["wo"])
    if "shared" in p:
        from .layers import swiglu
        out = out + swiglu(p["shared"], x)
    return out, aux


def moe_a2a(p: Params, x: jnp.ndarray, top_k: int, cap_factor: float,
            mesh, batch_axes=("pod", "data"), seq_axis: str = "model",
            expert_axis: str = "model") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE.  Experts sharded over ``expert_axis``; tokens
    dispatched from shards of (batch over ``batch_axes``, seq over
    ``seq_axis`` when it divides).  Routing runs outside the shard_map
    (GSPMD land) so the aux loss reduces globally for free."""
    b, t, d = x.shape
    e = p["router"].shape[-1]
    ax_size = mesh.shape[expert_axis]
    e_loc = e // ax_size
    w, ids, aux = _router(p, x.reshape(b * t, d), top_k)
    w = w.reshape(b, t, top_k)
    ids = ids.reshape(b, t, top_k)

    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    bsz = 1
    for a in batch_axes:
        bsz *= mesh.shape[a]
    use_batch = b % bsz == 0 and bsz > 1
    use_seq = (seq_axis in mesh.axis_names
               and t % mesh.shape[seq_axis] == 0 and t > 1)
    spec_x = P(batch_axes if use_batch else None,
               seq_axis if use_seq else None, None)

    def body(xl, wl, idsl, wi, wg, wo):
        # xl [bl, tl, d]; wi/wg [d, e_loc, f]; wo [f, e_loc, d]
        bl, tl, _ = xl.shape
        n = bl * tl
        xf = xl.reshape(n, d)
        cap = max(4, -(-int(n * top_k * cap_factor) // e))  # ceil, min 4
        buf, slot, keep = _dispatch_local(
            xf, wl.reshape(n, top_k), idsl.reshape(n, top_k), e, cap)
        # exchange: dim0 indexes the destination expert shard
        buf = buf.reshape(ax_size, e_loc, cap, d)
        buf = jax.lax.all_to_all(buf, expert_axis, split_axis=0,
                                 concat_axis=0, tiled=False)
        # now dim0 = source token shard, dim1 = my local experts
        h = jnp.einsum("secd,def->secf", buf, wi)
        g = jnp.einsum("secd,def->secf", buf, wg)
        h = h * jax.nn.sigmoid(g.astype(jnp.float32)).astype(h.dtype)
        out = jnp.einsum("secf,fed->secd", h, wo)
        out = jax.lax.all_to_all(out, expert_axis, split_axis=0,
                                 concat_axis=0, tiled=False)
        out = out.reshape(e, cap, d)     # dim0 back to global expert id
        fe = idsl.reshape(-1)
        fs = jnp.maximum(slot.reshape(-1), 0)
        got = out[fe, fs] * keep.reshape(-1)[:, None]
        got = got * wl.reshape(-1)[:, None].astype(got.dtype)
        tok = jnp.repeat(jnp.arange(n), top_k)
        y = jax.ops.segment_sum(got.astype(jnp.float32), tok,
                                num_segments=n)
        y = y.reshape(bl, tl, d).astype(xl.dtype)
        if not use_seq:
            # tokens were replicated over the expert axis: every column
            # computed the same y; mark it replicated for check_vma.
            y = jax.lax.pmean(y, expert_axis)
        return y

    spec_w = P(batch_axes, seq_axis if use_seq else None, None)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_x, spec_w, spec_w, P(None, expert_axis, None),
                  P(None, expert_axis, None), P(None, expert_axis, None)),
        out_specs=spec_x,
    )(x, w, ids, p["wi"], p["wg"], p["wo"])
    if "shared" in p:
        from .layers import swiglu
        out = out + swiglu(p["shared"], x)
    return out, aux

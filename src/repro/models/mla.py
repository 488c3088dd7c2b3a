"""Multi-head Latent Attention (DeepSeek-V3, arXiv:2412.19437).

Train/prefill: queries from a low-rank q projection (q_lora), K/V expanded
from the compressed latent c_kv (kv_lora) plus a shared RoPE key (qk_rope).

Decode: the *absorbed* formulation — only (c_kv, k_rope) of size
(kv_lora + qk_rope) per token is cached; per-head K expansion weights are
absorbed into the query (q~ = q_nope @ W_uk^T) and V expansion into the
output, so a decode step never materializes per-head K/V for the history.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from .config import ModelConfig
from .layers import Params, dense_init, rms_norm, rope


def _constrain(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """Soft sharding constraint: applied only for axes present in the
    ambient mesh and divisible dims; no-op on a single device.  Used to
    pin the MLA einsum chain to (batch->data, heads->model) — without it
    GSPMD picks contraction splits that all-reduce score-sized tensors
    inside the chunk loop (EXPERIMENTS.md §Perf, deepseek train_4k)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    fixed = []
    for dim, ax in zip(x.shape, spec):
        if ax is None:
            fixed.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        axes = tuple(a for a in axes if a in mesh.axis_names)
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        fixed.append((axes if len(axes) > 1 else (axes[0] if axes else
                                                  None))
                     if axes and dim % max(total, 1) == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def mla_init(key, cfg: ModelConfig, dtype) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 8)
    return {
        "w_dq": dense_init(ks[0], d, cfg.q_lora, dtype),
        "q_norm": jnp.zeros((cfg.q_lora,), dtype),
        "w_uq": dense_init(ks[1], cfg.q_lora,
                           (h, cfg.qk_nope + cfg.qk_rope), dtype),
        "w_dkv": dense_init(ks[2], d, cfg.kv_lora + cfg.qk_rope, dtype),
        "kv_norm": jnp.zeros((cfg.kv_lora,), dtype),
        "w_uk": dense_init(ks[3], cfg.kv_lora, (h, cfg.qk_nope), dtype),
        "w_uv": dense_init(ks[4], cfg.kv_lora, (h, cfg.v_head_dim), dtype),
        "wo": dense_init(ks[5], h * cfg.v_head_dim, d, dtype),
    }


def _project_q(p, cfg, x, positions):
    q = jnp.einsum("btd,dr->btr", x, p["w_dq"])
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("btr,rhe->bthe", q, p["w_uq"])
    q_nope, q_rope = q[..., :cfg.qk_nope], q[..., cfg.qk_nope:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _latent_kv(p, cfg, x, positions):
    ckv = jnp.einsum("btd,dr->btr", x, p["w_dkv"])
    c, k_rope = ckv[..., :cfg.kv_lora], ckv[..., cfg.kv_lora:]
    c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c, k_rope


def mla_attention(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray, chunk: int = 0) -> jnp.ndarray:
    """Full-sequence (train/prefill) MLA, causal; query-chunked online
    softmax when ``chunk`` divides T (bounded memory)."""
    b, t, d = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _project_q(p, cfg, x, positions)
    c, k_rope = _latent_kv(p, cfg, x, positions)
    k_nope = jnp.einsum("btr,rhe->bthe", c, p["w_uk"])
    v = jnp.einsum("btr,rhe->bthe", c, p["w_uv"])
    bt = ("pod", "data")
    q_nope = _constrain(q_nope, bt, None, "model", None)
    q_rope = _constrain(q_rope, bt, None, "model", None)
    k_nope = _constrain(k_nope, bt, None, "model", None)
    v = _constrain(v, bt, None, "model", None)
    scale = (cfg.qk_nope + cfg.qk_rope) ** -0.5
    kpos = positions

    # Perf (EXPERIMENTS.md §Perf, deepseek train_4k iterations): keep the
    # T-wide tensors in bf16 (f32 accumulation in the dots + f32 softmax
    # stats) — halves the score-chain HBM traffic AND the GSPMD reshard
    # collectives that live inside this chunk loop.  NOTE: rematerializing
    # this body was tried and REFUTED — recompute re-runs the in-loop
    # reshard collectives in backward (+23% collective term).
    def chunk_attn(qn, qr, pq):
        s = jnp.einsum("bqhe,bkhe->bhqk", qn, k_nope,
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bqhe,bke->bhqk", qr, k_rope,
                           preferred_element_type=jnp.float32)
        s = s * scale
        mask = pq[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, -2e38)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        den = jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhe->bqhe", e.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o / jnp.maximum(den.swapaxes(1, 2), 1e-30)  # [b,q,h,1]

    if chunk and t > chunk and t % chunk == 0:
        nc = t // chunk
        qn_c = q_nope.reshape(b, nc, chunk, h, -1).swapaxes(0, 1)
        qr_c = q_rope.reshape(b, nc, chunk, h, -1).swapaxes(0, 1)
        pq_c = positions.reshape(nc, chunk)
        o = jax.lax.map(lambda a: chunk_attn(*a), (qn_c, qr_c, pq_c))
        o = o.swapaxes(0, 1).reshape(b, t, h, -1)
    else:
        o = chunk_attn(q_nope, q_rope, positions)
    o = o.astype(x.dtype)
    return jnp.einsum("bthe,hed->btd", o,
                      p["wo"].reshape(h, cfg.v_head_dim, d))


def mla_cache_init(batch: int, max_len: int, cfg: ModelConfig, dtype
                   ) -> Dict[str, jnp.ndarray]:
    return {
        "c": jnp.zeros((batch, max_len, cfg.kv_lora), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope), dtype),
    }


def mla_decode_step(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                    cache: Dict[str, jnp.ndarray], pos: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Absorbed decode.  x [B,1,D]; cache c [B,S,kv_lora]."""
    b, _, d = x.shape
    h = cfg.n_heads
    posv = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope = _project_q(p, cfg, x, posv)
    c_new, kr_new = _latent_kv(p, cfg, x, posv)
    s_len = cache["c"].shape[1]
    cc = jax.lax.dynamic_update_slice_in_dim(
        cache["c"], c_new.astype(cache["c"].dtype), pos, axis=1)
    kr = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos, axis=1)
    cache = {"c": cc, "k_rope": kr}
    # absorb: q~ [B,1,H,kv_lora]
    q_abs = jnp.einsum("bthe,rhe->bthr", q_nope, p["w_uk"])
    s = jnp.einsum("bthr,bsr->bhts", q_abs.astype(jnp.float32),
                   cc.astype(jnp.float32))
    s = s + jnp.einsum("bthe,bse->bhts", q_rope.astype(jnp.float32),
                       kr.astype(jnp.float32))
    s = s * ((cfg.qk_nope + cfg.qk_rope) ** -0.5)
    valid = jnp.arange(s_len) <= pos
    s = jnp.where(valid[None, None, None, :], s, -2e38)
    pr = jax.nn.softmax(s, axis=-1)
    # attend over the latent, then expand through W_uv (absorbed output)
    o_lat = jnp.einsum("bhts,bsr->bthr", pr, cc.astype(jnp.float32))
    o = jnp.einsum("bthr,rhe->bthe", o_lat, p["w_uv"].astype(jnp.float32))
    o = o.astype(x.dtype)
    return jnp.einsum("bthe,hed->btd", o,
                      p["wo"].reshape(h, cfg.v_head_dim, d)), cache

"""Multi-head latent attention (DeepSeek-V2/V3, arXiv:2405.04434 §2.1),
without query compression, as Moonlight-16B-A3B uses it.

Per token: ``q = x W_q`` gives each head a ``nope`` part and a rotary part;
``x W_kva`` gives a latent ``c`` (RMS-normed by ``kv_norm``) and one rotary
key ``k_pe`` shared by all heads; ``W_kvb`` expands ``c`` into each head's
``k_nope`` and ``v``. Scores are ``(q_nope·k_nope + q_pe·k_pe) /
sqrt(nope + rope)``.

The forward pass (training, the CPU tests) decompresses K and V. Decode
keeps only ``c`` and ``k_pe`` in the cache (``kv_lora_rank + rope`` values a
token a layer) and absorbs ``W_UK`` into the query and ``W_UV`` into the
output, so it never expands the cache into heads.

Parameters: ``wq [D, H*(nope+rope)]`` (per head ``[nope | rope]``),
``wkv_a [D, r+rope]`` (``[c | k_pe]``), ``kv_norm`` ``[r]``, ``wkv_b [r,
H*(nope+v)]`` (per head ``[k_nope | v]``), ``wo [H*v, D]``: the layout of
``modeling_deepseek.py``'s projections, transposed.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

from .layers import apply_norm, apply_rope, apply_rope_interleaved, init_norm


def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    d, h, m = cfg.d_model, cfg.n_heads, cfg.mla
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    r = m.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": jax.random.normal(ks[0], (d, h * qk), dtype) / math.sqrt(d),
        "wkv_a": jax.random.normal(ks[1], (d, r + m.qk_rope_head_dim),
                                   dtype) / math.sqrt(d),
        "kv_norm": init_norm(r, "rmsnorm", dtype),
        "wkv_b": jax.random.normal(
            ks[2], (r, h * (m.qk_nope_head_dim + m.v_head_dim)),
            dtype) / math.sqrt(r),
        "wo": jax.random.normal(ks[3], (h * m.v_head_dim, d),
                                dtype) / math.sqrt(h * m.v_head_dim),
    }


def _rope(cfg: ModelConfig, x, positions):
    if cfg.rope_interleave:
        return apply_rope_interleaved(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta)


def _project(p: dict, cfg: ModelConfig, x: jax.Array, positions):
    """x [B,S,D] → q_nope [B,S,H,nope], q_pe [B,S,H,rope] (rotated),
    c [B,S,r] (normed), k_pe [B,S,rope] (rotated)."""
    b, s, _ = x.shape
    h, m = cfg.n_heads, cfg.mla
    dn, r = m.qk_nope_head_dim, m.kv_lora_rank
    q = (x @ p["wq"]).reshape(b, s, h, dn + m.qk_rope_head_dim)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    kv_a = x @ p["wkv_a"]
    c = apply_norm(p["kv_norm"], kv_a[..., :r], "rmsnorm", cfg.norm_eps)
    k_pe = kv_a[..., r:]
    q_pe = _rope(cfg, q_pe, positions)
    k_pe = _rope(cfg, k_pe[:, :, None, :], positions)[:, :, 0, :]
    return q_nope, q_pe, c, k_pe


def _scale(cfg: ModelConfig) -> float:
    return (cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim) ** -0.5


def mla_attention(p: dict, cfg: ModelConfig, x: jax.Array,
                  positions) -> jax.Array:
    """Causal full-sequence MLA with K and V decompressed. x [B,S,D]."""
    with jax.named_scope("mla"):
        b, s, _ = x.shape
        h, m = cfg.n_heads, cfg.mla
        q_nope, q_pe, c, k_pe = _project(p, cfg, x, positions)
        kv = (c @ p["wkv_b"]).reshape(b, s, h,
                                      m.qk_nope_head_dim + m.v_head_dim)
        k_nope, v = kv[..., :m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
        f32 = jnp.float32
        logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope.astype(f32),
                             k_nope.astype(f32))
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe.astype(f32),
                               k_pe.astype(f32))) * _scale(cfg)
        causal = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(causal, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return o.reshape(b, s, h * m.v_head_dim) @ p["wo"]


# ----------------------------------------------------------------------------
# decode — one token against the latent cache
# ----------------------------------------------------------------------------
def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                   n_layers: int) -> dict:
    """Per layer and token: the normed latent and the rotated shared key."""
    m = cfg.mla
    return {"c_kv": jnp.zeros((n_layers, batch, max_len, m.kv_lora_rank),
                              dtype),
            "k_pe": jnp.zeros((n_layers, batch, max_len, m.qk_rope_head_dim),
                              dtype)}


def decode_mla(p: dict, cfg: ModelConfig, x: jax.Array, c_cache, pe_cache,
               cache_len, positions) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token absorbed MLA. x [B,1,D]; caches [B,Smax,r] and
    [B,Smax,rope]. Scores are ``(q_nope W_UK^T)·c + q_pe·k_pe``; the
    output is ``(p·c) W_UV``, then ``wo``. → (out [B,1,D], caches)."""
    with jax.named_scope("mla"):
        b = x.shape[0]
        h, m = cfg.n_heads, cfg.mla
        dn, r = m.qk_nope_head_dim, m.kv_lora_rank
        q_nope, q_pe, c, k_pe = _project(p, cfg, x, positions)
        c_cache = jax.lax.dynamic_update_slice_in_dim(
            c_cache, c.astype(c_cache.dtype), cache_len, axis=1)
        pe_cache = jax.lax.dynamic_update_slice_in_dim(
            pe_cache, k_pe.astype(pe_cache.dtype), cache_len, axis=1)
        w_kvb = p["wkv_b"].reshape(r, h, dn + m.v_head_dim)
        w_uk, w_uv = w_kvb[..., :dn], w_kvb[..., dn:]
        f32, dt = jnp.float32, c_cache.dtype
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk,
                           preferred_element_type=f32)
        logits = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(dt), c_cache,
                             preferred_element_type=f32)
                  + jnp.einsum("bhd,bsd->bhs", q_pe[:, 0].astype(dt),
                               pe_cache, preferred_element_type=f32)
                  ) * _scale(cfg)
        smax = c_cache.shape[1]
        valid = jnp.arange(smax)[None, None, :] <= cache_len
        probs = jax.nn.softmax(jnp.where(valid, logits, -1e30), axis=-1)
        o_lat = jnp.einsum("bhs,bsr->bhr", probs.astype(dt), c_cache,
                           preferred_element_type=f32)
        o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(dt), w_uv,
                       preferred_element_type=f32).astype(x.dtype)
        out = o.reshape(b, 1, h * m.v_head_dim) @ p["wo"]
        return out, c_cache, pe_cache

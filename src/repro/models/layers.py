"""Shared model layers: norms, rotary embeddings (incl. M-RoPE), MLPs.

Pure functions over explicit parameter pytrees (no framework dependency);
initializers return dicts of jnp arrays so param trees stay transparent
for the sharding rule engine in ``repro.dist.sharding``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# ----------------------------------------------------------------------------
# normalization
# ----------------------------------------------------------------------------
def init_norm(d: int, kind: str, dtype) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: dict, x: jax.Array, kind: str, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        scale = jnp.asarray(p["scale"], jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * scale).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * jnp.asarray(p["scale"], jnp.float32) + jnp.asarray(p["bias"], jnp.float32)
    return y.astype(x.dtype)


# ----------------------------------------------------------------------------
# rotary position embeddings
# ----------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B,S,H,D]; positions: [B,S] int32. Half-split (NeoX) convention."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs     # [B,S,d/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           theta: float) -> jax.Array:
    """x: [B,S,H,D]; positions: [B,S]. Rotates the interleaved pairs
    ``(x[2i], x[2i+1])`` by ``positions * theta^(-2i/D)`` and returns them
    de-interleaved (rotated evens, then rotated odds), as DeepSeek's
    ``apply_rotary_pos_emb`` does; q and k take the same permutation, so
    their dot products are those of the pairs rotated in place."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs     # [B,S,d/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_m_rope(x: jax.Array, positions3: jax.Array, theta: float,
                 sections: Tuple[int, int, int]) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL, arXiv:2409.12191).

    ``positions3`` is [3, B, S] — temporal / height / width position ids.
    The head-dim frequency bands are partitioned into ``sections`` (pairs),
    each rotated by its own position stream.
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = rope_freqs(d, theta)                                  # (d/2,)
    # section id per frequency pair: [d/2] in {0,1,2}
    sec_ids = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                         total_repeat_length=d // 2)
    # pick the matching position stream per pair
    pos = jnp.take(positions3, sec_ids, axis=0)                   # [d/2, B, S]
    angles = jnp.moveaxis(pos, 0, -1).astype(jnp.float32) * freqs  # [B,S,d/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    """Whisper-style fixed sinusoidal table [n, d]."""
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    angle = pos / (10000.0 ** (2 * dim / d))
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


# ----------------------------------------------------------------------------
# MLPs
# ----------------------------------------------------------------------------
def init_mlp(key, d: int, f: int, kind: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    p = {"w_out": jax.random.normal(k3, (f, d), dtype) * s_out}
    if kind in ("swiglu", "geglu"):
        p["w_gate"] = jax.random.normal(k1, (d, f), dtype) * s_in
        p["w_up"] = jax.random.normal(k2, (d, f), dtype) * s_in
    else:
        p["w_up"] = jax.random.normal(k1, (d, f), dtype) * s_in
    return p


def apply_mlp(p: dict, x: jax.Array, kind: str) -> jax.Array:
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif kind == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif kind == "gelu":
        h = jax.nn.gelu(x @ p["w_up"])
    elif kind == "relu2":  # squared ReLU (Primer; Nemotron-4)
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
    else:
        raise ValueError(kind)
    from repro.dist import api as dist_api
    h = dist_api.hint_named(h, "mlp_hidden")
    return h @ p["w_out"]


# ----------------------------------------------------------------------------
def init_embedding(key, vocab: int, d: int, dtype) -> jax.Array:
    return jax.random.normal(key, (vocab, d), dtype) * 0.02


def init_linear(key, d_in: int, d_out: int, dtype, bias: bool = False) -> dict:
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) / math.sqrt(d_in)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def apply_linear(p: dict, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y

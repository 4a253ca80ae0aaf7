"""Plain forward pass of a dense decoder LM with grouped-query attention,
qk-norm, rotary embeddings and a gated SiLU MLP (the Qwen3 layer), in
float32 under "highest" matmul precision, for whole sequences at once.

Layer equations (Qwen3 model card / ``modeling_qwen3.py``): ``x = embed[t]``;
per layer ``h = rms(x) * attn_norm``, ``q, k, v = h wq, h wk, h wv`` split
into heads, ``q = rms(q) * q_norm``, ``k = rms(k) * k_norm``, rotary
embedding (half-split, ``theta``) on ``q`` and ``k``, causal softmax
attention with query head ``j`` reading K/V head ``j // (H / Hkv)``, ``x +=
attn wo``; ``h = rms(x) * mlp_norm``, ``x += (silu(h w_gate) * h w_up)
w_down``; logits ``= rms(x) * final_norm @ embed^T`` (tied head). RMS norm
uses ``eps = rms_norm_eps``.

The weights are the benchmark's own (``bench/systems/serve_lm.py`` makes
them from the seed), as a flat dict of arrays stacked over layers; the
layers run one at a time under a scan, each cast to float32 in turn.

``quant="fp8"`` is the control: every weight matmul takes its operands
fake-quantized to float8 e4m3 (a scale per activation row and per weight
column), one step below the bfloat16 the configuration states.

Nothing here imports the program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fake_quant(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant: Optional[str]):
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        a = _fake_quant(a, -1)
        w = _fake_quant(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [B, S, heads, D], positions 0..S-1, half-split pairs."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(w: dict, sizes: dict, tokens, quant: Optional[str] = None):
    """tokens [B, S] int32 → logits [B, S, V] float32."""
    eps = float(sizes["rms_norm_eps"])
    theta = float(sizes["rope_theta"])
    h, hkv = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
    hd = int(sizes["head_dim"])
    b, s = tokens.shape
    g = h // hkv
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        y = _rms(x, lw["attn_norm"], eps)
        q = _mm(y, lw["wq"], quant).reshape(b, s, h, hd)
        k = _mm(y, lw["wk"], quant).reshape(b, s, hkv, hd)
        v = _mm(y, lw["wv"], quant).reshape(b, s, hkv, hd)
        q = _rope(_rms(q, lw["q_norm"], eps), theta)
        k = _rope(_rms(k, lw["k_norm"], eps), theta)
        q = q.reshape(b, s, hkv, g, hd)
        att = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                         precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
        att = jnp.where(causal, att, -jnp.inf)
        p = jax.nn.softmax(att, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HIGHEST)
        x = x + _mm(o.reshape(b, s, h * hd), lw["wo"], quant)
        y = _rms(x, lw["mlp_norm"], eps)
        y = jax.nn.silu(_mm(y, lw["w_gate"], quant)) * _mm(y, lw["w_up"], quant)
        return x + _mm(y, lw["w_down"], quant), None

    layers = {k: w[k] for k in LAYER_KEYS}
    x, _ = jax.lax.scan(layer, x, layers)
    x = _rms(x, w["final_norm"], eps)
    return _mm(x, w["embed"].T, quant)


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "mlp_norm", "w_gate", "w_up", "w_down")


@functools.lru_cache(maxsize=None)
def _gap_fn(sizes_items: tuple, quant: Optional[str]):
    sizes = dict(sizes_items)

    @jax.jit
    def fn(w, tokens, targets, valid):
        ref = forward(w, sizes, tokens)
        best = jnp.max(ref, axis=-1)
        if quant is None:
            pick = targets
        else:
            pick = jnp.argmax(forward(w, sizes, tokens, quant), axis=-1)
        got = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
        return jnp.where(valid, best - got, 0.0)

    return fn


def logit_gaps(w: dict, sizes: dict, tokens, targets, valid,
               quant: Optional[str] = None):
    """Per position, how far the reference's logit of the chosen token
    lies below the reference's best logit. The chosen token is
    ``targets`` (what was served), or, with ``quant``, the token that the
    reference computed at that lower precision puts first."""
    key = tuple(sorted((k, v) for k, v in sizes.items()
                       if isinstance(v, (int, float, str, bool))))
    return _gap_fn(key, quant)(w, tokens, targets, valid)

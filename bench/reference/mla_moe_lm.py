"""Plain forward pass of a DeepSeek-V3 decoder (Moonlight-16B-A3B): latent
attention with the K/V decompressed, a leading dense SwiGLU layer, then
expert layers with a sigmoid router, a correction bias for the choice,
routed and shared SwiGLU experts. float32 under "highest" matmul
precision, whole sequences at once, no kernel, no cache, no absorption.

Layer equations (``modeling_deepseek.py``, ``model_type`` deepseek_v3, no
q-LoRA): ``x = embed[t]``; per layer ``h = rms(x) * attn_norm``; ``q = h
wq`` split per head into ``q_nope`` (``qk_nope_head_dim``) and ``q_pe``
(``qk_rope_head_dim``); ``[c | k_pe] = h wkv_a``, ``c = rms(c) * kv_norm``;
``[k_nope | v] = c wkv_b`` per head; rotary on ``q_pe`` and ``k_pe`` (one key
shared by all heads) on interleaved pairs ``(2i, 2i+1)`` at ``theta``,
written back evens then odds; ``k = [k_nope | k_pe]``, ``q = [q_nope |
q_pe]``, causal softmax of ``q k / sqrt(nope + rope)``, ``x += (p v) wo``.
Then ``h = rms(x) * mlp_norm`` and ``x += silu(h w_gate) * (h w_up) w_down``
in the dense layers; in the expert layers ``s = sigmoid(h router)``, the
top ``num_experts_per_tok`` of ``s + e_score_correction_bias`` are chosen,
their weights are their ``s`` renormalised and times
``routed_scaling_factor``, and ``x += sum over chosen held e of weight_e *
SwiGLU_e(h) + SwiGLU_shared(h)``. Logits ``= rms(x) * final_norm @
lm_head``. RMS norms use ``rms_norm_eps``.

Departures from ``modeling_deepseek.py``: only the experts held here
(``held_experts_from`` .. ``+ n_routed_experts``) add their part of the
routed sum, the chip's share that the program computes too; the router
still scores all ``router_experts``. With ``n_group`` and ``topk_group`` 1
the group choice of ``noaux_tc`` selects every expert and is left out. No
YaRN (``rope_scaling`` is null). ``q_pe``/``k_pe`` keep the pair rotation
in place of the de-interleave: dot products are the same.

The weights are the benchmark's own (``bench/systems/serve_mla_moe.py``
makes them from the seed), a flat dict of arrays stacked over the layers of
each group: ``d_*`` for the dense layers, ``m_*`` for the expert layers.

``quant="fp8"`` is the control: every weight matmul (router, experts and
head included) takes its operands fake-quantized to float8 e4m3 (a scale
per activation row and per weight column), one step below the bfloat16
the configuration states. ``quant="bf16"`` is the witness: it rounds the
same operands, and the residual stream after each sublayer, to bfloat16,
the precision the program runs at, so its gaps are what that rounding
alone gives.

Nothing here imports the program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0

ATTN_KEYS = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo",
             "mlp_norm")
DENSE_KEYS = ATTN_KEYS + ("w_gate", "w_up", "w_down")
MOE_KEYS = ATTN_KEYS + ("router", "router_bias", "w_gate", "w_up", "w_down",
                        "s_gate", "s_up", "s_down")


def _fake_quant(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _round(x, quant: Optional[str]):
    """The residual stream as the program keeps it: bfloat16 at "bf16"."""
    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _mm(a, w, quant: Optional[str]):
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        a = _fake_quant(a, -1)
        w = _fake_quant(w, 0)
    elif quant == "bf16":
        a = _round(a, quant)
        w = _round(w, quant)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope_pairs(x, theta):
    """x [B, S, heads, D], positions 0..S-1, interleaved pairs."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def forward(w: dict, sizes: dict, tokens, quant: Optional[str] = None):
    """tokens [B, S] int32 → logits [B, S, V] float32."""
    eps = float(sizes["rms_norm_eps"])
    theta = float(sizes["rope_theta"])
    h = int(sizes["num_attention_heads"])
    dn = int(sizes["qk_nope_head_dim"])
    dr = int(sizes["qk_rope_head_dim"])
    dv = int(sizes["v_head_dim"])
    r = int(sizes["kv_lora_rank"])
    k_top = int(sizes["num_experts_per_tok"])
    scale = float(sizes["routed_scaling_factor"])
    lo = int(sizes["held_experts_from"])
    n_held = int(sizes["n_routed_experts"])
    b, s = tokens.shape
    x = _round(jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32),
               quant)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def attention(x, lw):
        y = _rms(x, lw["attn_norm"], eps)
        q = _mm(y, lw["wq"], quant).reshape(b, s, h, dn + dr)
        kv_a = _mm(y, lw["wkv_a"], quant)
        c = _rms(kv_a[..., :r], lw["kv_norm"], eps)
        k_pe = _rope_pairs(kv_a[..., None, r:], theta)
        kv = _mm(c, lw["wkv_b"], quant).reshape(b, s, h, dn + dv)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(k_pe, (b, s, h, dr))], -1)
        q = jnp.concatenate([q[..., :dn], _rope_pairs(q[..., dn:], theta)],
                            -1)
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                         precision=HIGHEST) / jnp.sqrt(jnp.float32(dn + dr))
        att = jnp.where(causal, att, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1),
                       kv[..., dn:], precision=HIGHEST)
        return _round(x + _mm(o.reshape(b, s, h * dv), lw["wo"], quant),
                      quant)

    def dense_layer(x, lw):
        x = attention(x, lw)
        y = _rms(x, lw["mlp_norm"], eps)
        return _round(x + _swiglu(y, lw["w_gate"], lw["w_up"],
                                  lw["w_down"], quant), quant), None

    def moe_layer(x, lw):
        x = attention(x, lw)
        y = _rms(x, lw["mlp_norm"], eps)
        score = jax.nn.sigmoid(_mm(y, lw["router"], quant))
        _, idx = jax.lax.top_k(score + lw["router_bias"].astype(jnp.float32),
                               k_top)
        wt = jnp.take_along_axis(score, idx, axis=-1)
        wt = wt / jnp.sum(wt, axis=-1, keepdims=True) * scale
        out = _swiglu(y, lw["s_gate"], lw["s_up"], lw["s_down"], quant)

        def expert(out, ew):
            e, wg, wu, wd = ew
            gate = jnp.sum(jnp.where(idx == lo + e, wt, 0.0), -1,
                           keepdims=True)
            return out + gate * _swiglu(y, wg, wu, wd, quant), None

        out, _ = jax.lax.scan(expert, out, (jnp.arange(n_held), lw["w_gate"],
                                            lw["w_up"], lw["w_down"]))
        return _round(x + out, quant), None

    x, _ = jax.lax.scan(dense_layer, x, {k: w["d_" + k] for k in DENSE_KEYS})
    x, _ = jax.lax.scan(moe_layer, x, {k: w["m_" + k] for k in MOE_KEYS})
    x = _rms(x, w["final_norm"], eps)
    return _mm(x, w["lm_head"], quant)


@functools.lru_cache(maxsize=None)
def _gap_fn(sizes_items: tuple, quants: tuple):
    sizes = dict(sizes_items)

    @jax.jit
    def fn(w, tokens, targets, valid):
        ref = forward(w, sizes, tokens)
        best = jnp.max(ref, axis=-1)

        def gaps(pick):
            got = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
            return jnp.where(valid, best - got, 0.0)

        return [gaps(targets)] + [
            gaps(jnp.argmax(forward(w, sizes, tokens, q), axis=-1))
            for q in quants]

    return fn


def _sizes_key(sizes: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float, str, bool))))


def logit_gaps(w: dict, sizes: dict, tokens, targets, valid,
               quants: tuple = ()):
    """Per position, how far the reference's logit of a chosen token lies
    below the reference's best logit. → one ``[B, S]`` array for the
    chosen tokens ``targets`` (what was served), then one for each of
    ``quants``, whose chosen token is the one the reference computed at
    that precision puts first. The float32 forward runs once."""
    return _gap_fn(_sizes_key(sizes), tuple(quants))(w, tokens, targets,
                                                      valid)

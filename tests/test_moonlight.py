"""Moonlight-16B-A3B (DeepSeek-V3 block: MLA + sigmoid-routed experts with
shared experts, a leading dense layer) against a plain float32 reference,
at ``smoke_config`` sizes on seeded random weights.

The reference below is written from the layer equations of
``modeling_deepseek.py`` in plain ``jax.numpy`` at "highest" matmul
precision: K and V decompressed, no absorption, no kernel, no cache, every
held expert run on every token. It computes the same share as the program
(the experts ``moe.held`` names, plus the shared experts). Departures from
``modeling_deepseek.py``: only the held experts' part of the routed sum
(the chip's share; the router still scores every expert); no YaRN
(``rope_scaling`` is null); no auxiliary loss (inference).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import Model
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod

ARCH = "moonlight-16b-a3b"
HIGHEST = jax.lax.Precision.HIGHEST


# -- the reference -----------------------------------------------------------
def _mm(a, w):
    return jnp.matmul(a.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope_pairs(x, theta):
    """x [B, S, heads, D]: pair (2i, 2i+1) turned by pos * theta^(-2i/D),
    written back as evens then odds (``modeling_deepseek.py``)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def ref_attention(p, cfg, x):
    b, s, _ = x.shape
    h, m, eps = cfg.n_heads, cfg.mla, cfg.norm_eps
    dn, dr, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.kv_lora_rank
    q = _mm(x, p["wq"]).reshape(b, s, h, dn + dr)
    kv_a = _mm(x, p["wkv_a"])
    c = _rms(kv_a[..., :r], p["kv_norm"]["scale"], eps)
    k_pe = _rope_pairs(kv_a[..., None, r:], cfg.rope_theta)
    kv = _mm(c, p["wkv_b"]).reshape(b, s, h, dn + m.v_head_dim)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (b, s, h, dr))], -1)
    q = jnp.concatenate([q[..., :dn], _rope_pairs(q[..., dn:],
                                                  cfg.rope_theta)], -1)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                     precision=HIGHEST) / jnp.sqrt(jnp.float32(dn + dr))
    att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, -1), kv[..., dn:],
                   precision=HIGHEST)
    return _mm(o.reshape(b, s, -1), p["wo"])


def _swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"])) * _mm(x, p["w_up"]),
               p["w_out"])


def ref_choice(p, cfg, x):
    """→ (chosen experts [.., k], their weights): sigmoid scores, the bias
    added for the choice only, renormalised, scaled."""
    scores = jax.nn.sigmoid(_mm(x, p["router"]))
    _, idx = jax.lax.top_k(scores + p["router_bias"], cfg.moe.top_k)
    w = jnp.take_along_axis(scores, idx, -1)
    return idx, w / jnp.sum(w, -1, keepdims=True) * cfg.moe.routed_scaling


def ref_routed(p, cfg, x):
    """The held experts' part of the routed sum."""
    lo, hi = cfg.moe.held_range()
    idx, w = ref_choice(p, cfg, x)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(lo, hi):
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        ex = {n: p[n][e - lo] for n in ("w_gate", "w_up", "w_out")}
        y = y + gate * _swiglu(ex, x)
    return y


def ref_forward(params, cfg, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    eps = cfg.norm_eps
    for gp in params["groups"]:
        block = gp[0]
        for li in range(block["norm1"]["scale"].shape[0]):
            lp = jax.tree.map(lambda a: a[li], block)
            x = x + ref_attention(lp["attn"], cfg,
                                  _rms(x, lp["norm1"]["scale"], eps))
            h = _rms(x, lp["norm2"]["scale"], eps)
            if "moe" in lp:
                x = x + ref_routed(lp["moe"], cfg, h) + _swiglu(
                    lp["moe"]["shared"], h)
            else:
                x = x + _swiglu(lp["mlp"], h)
    x = _rms(x, params["final_norm"]["scale"], eps)
    return _mm(x, params["head"])


# -- helpers -----------------------------------------------------------------
def _setup(seed=0):
    cfg = configs.get_smoke_config(ARCH)
    model = Model(cfg)
    params = model.init(jax.random.key(seed))
    # a correction bias of the size of the score spread, so that it moves
    # choices
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: (a * 10.0 if "router_bias" in jax.tree_util.keystr(
            path) else a), params)
    return cfg, model, params


def test_smoke_config_is_the_published_block_cut_small():
    full = configs.get_config(ARCH)
    assert (full.n_layers, full.d_model, full.vocab_size) == (27, 2048,
                                                              163840)
    assert full.moe.held == (0, 16) and full.moe.n_experts == 64
    assert full.param_count() == pytest.approx(15.96e9, rel=1e-3)
    smoke = configs.get_smoke_config(ARCH)
    assert smoke.uses_mla and smoke.moe.router == "sigmoid"


def test_latent_cache_decode_matches_reference_forward():
    cfg, model, params = _setup(seed=1)
    seq = 9
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, seq)), jnp.int32)
    want = ref_forward(params, cfg, tokens)
    cache = model.init_cache(2, seq + 3)
    got, held = [], 0
    for t in range(seq):
        lg, cache = model.decode_step(params, tokens[:, t:t + 1], cache)
        got.append(lg[:, 0])
        held += int(cache["moe_counts"][0])
    got = jnp.stack(got, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # the held experts (2 of 8, top-3) did work in the decode steps
    assert held > 0
    # the teacher-forced forward pass agrees too
    fwd, _ = model.forward(params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(fwd), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_absorbed_decode_matches_decompressed_attention():
    cfg, _, params = _setup(seed=2)
    p = jax.tree.map(lambda a: a[0], params["groups"][1][0]["attn"])
    b, s = 2, 7
    x = jax.random.normal(jax.random.key(5), (b, s, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    want = mla_mod.mla_attention(p, cfg, x, pos)
    cache = mla_mod.init_mla_cache(cfg, b, s, jnp.float32, 1)
    c_kv, k_pe = cache["c_kv"][0], cache["k_pe"][0]
    for t in range(s):
        out, c_kv, k_pe = mla_mod.decode_mla(p, cfg, x[:, t:t + 1], c_kv,
                                             k_pe, jnp.int32(t),
                                             pos[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(want[:, t]), rtol=1e-4,
                                   atol=1e-5)
    # the cache holds the latent and the rotary key: r + rope per token
    assert c_kv.shape[-1] + k_pe.shape[-1] == (cfg.mla.kv_lora_rank
                                               + cfg.mla.qk_rope_head_dim)


def test_four_expert_shares_add_up_to_the_uncut_layer():
    """Each chip of an expert-parallel deployment computes its held
    experts' part plus the shared experts; the four parts, with the shared
    experts counted once, give the uncut reference layer."""
    cfg, _, _ = _setup()
    e = cfg.moe.n_experts
    uncut = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                             held=(0, e)))
    p = moe_mod.init_moe(jax.random.key(7), uncut, jnp.float32)
    p["router_bias"] = p["router_bias"] * 10.0
    x = jax.random.normal(jax.random.key(8), (2, 5, cfg.d_model))
    want = ref_routed(p, uncut, x) + _swiglu(p["shared"], x)
    share = e // 4
    total = jnp.zeros_like(x)
    for i in range(4):
        held = (i * share, (i + 1) * share)
        cut = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                               held=held))
        ps = dict(p, **{n: p[n][held[0]:held[1]]
                        for n in ("w_gate", "w_up", "w_out")})
        y, _ = moe_mod.apply_moe_dropless(ps, cut, x)
        total = total + y
    total = total - 3 * _swiglu(p["shared"], x)   # shared counted once
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # and one share alone is the reference of that share
    cut = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                           held=(0, share)))
    ps = dict(p, **{n: p[n][:share] for n in ("w_gate", "w_up", "w_out")})
    y, counts = moe_mod.apply_moe_dropless(ps, cut, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref_routed(ps, cut, x)
                                  + _swiglu(p["shared"], x)),
        rtol=1e-4, atol=1e-5)
    idx, _ = ref_choice(p, cfg, x.reshape(-1, cfg.d_model))
    assert int(counts[0]) == int(jnp.sum(idx < share))
    assert int(counts[1]) == len(set(np.asarray(idx).ravel()) &
                                 set(range(share)))


def test_correction_bias_moves_the_choice_not_the_weight():
    cfg, _, _ = _setup()
    p = moe_mod.init_moe(jax.random.key(9), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(10), (64, cfg.d_model))
    p0 = dict(p, router_bias=jnp.zeros_like(p["router_bias"]))
    # expert 5 strongly favoured for the choice
    p1 = dict(p, router_bias=p0["router_bias"].at[5].set(10.0))
    idx0, w0 = moe_mod.route(p0, cfg, x)
    idx1, w1 = moe_mod.route(p1, cfg, x)
    assert bool(jnp.all(jnp.any(idx1 == 5, axis=-1)))
    assert not bool(jnp.all(jnp.any(idx0 == 5, axis=-1)))
    # the weights are the unbiased sigmoid scores of the chosen experts,
    # renormalised and scaled
    scores = jax.nn.sigmoid(_mm(x, p["router"]))
    for idx, w in ((idx0, w0), (idx1, w1)):
        s = jnp.take_along_axis(scores, idx, -1)
        np.testing.assert_allclose(
            np.asarray(w), np.asarray(s / s.sum(-1, keepdims=True)
                                      * cfg.moe.routed_scaling),
            rtol=1e-5)
    ref_idx, ref_w = ref_choice(p1, cfg, x)
    np.testing.assert_array_equal(np.sort(np.asarray(idx1), -1),
                                  np.sort(np.asarray(ref_idx), -1))


@pytest.mark.parametrize("arch", ["phi3.5-moe-42b-a6.6b", ARCH])
def test_decode_is_dropless(arch):
    """Every token's choices are computed at decode, however many tokens
    pick one expert: eight tokens with the same choices get the same
    output, and the kernel agrees with every expert run on every token."""
    cfg = configs.get_smoke_config(arch)
    p = moe_mod.init_moe(jax.random.key(11), cfg, jnp.float32)
    x = jnp.broadcast_to(jax.random.normal(jax.random.key(12),
                                           (1, 1, cfg.d_model)),
                         (8, 1, cfg.d_model))      # 8 tokens, same choices
    y, counts = moe_mod.apply_moe_dropless(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        jnp.broadcast_to(y[:1], y.shape)), rtol=1e-5, atol=1e-6)
    y_ref, _ = moe_mod.apply_moe_dropless(p, cfg, x, impl="ref")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.abs(y).max()) > 0

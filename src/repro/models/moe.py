"""Top-k routed mixture-of-experts (GShard/Switch-style dense dispatch).

TPU-native formulation: routing becomes one-hot dispatch/combine einsums
so GSPMD lowers expert exchange to all-to-all/reduce-scatter collectives.
Experts are sharded on the ``model`` mesh axis (expert parallelism); the
dispatch tensor [T, E, C] carries the expert axis so its per-device slice
stays small (DESIGN.md §6).

Capacity-based token dropping keeps shapes static (dropped tokens pass
through the residual); an auxiliary load-balancing loss (Switch, eq. 4)
discourages imbalance. That path (:func:`apply_moe`) is the training
forward of the softmax-routed configs (dbrx, phi-3.5-MoE).

:func:`apply_moe_dropless` keeps every token's choices: it is what decode
(serving) runs for every expert layer, and the forward of a layer that
holds a share of its experts (``MoEConfig.held``). The router scores all
``n_experts``; only the held experts' part is computed (decode: the
``moe_held_experts`` Pallas kernel over tokens grouped by expert; forward:
every held expert on every token, masked by the routing weights), plus the
shared experts, which every chip of an expert-parallel deployment applies.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops

from .layers import apply_mlp, init_mlp


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.expert_width, cfg.moe.n_experts
    lo, hi = cfg.moe.held_range()
    ks = jax.random.split(key, 4)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    sigmoid = cfg.moe.router == "sigmoid"
    p = {
        "router": jax.random.normal(ks[0], (d, e), dtype if sigmoid
                                    else jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (hi - lo, d, f), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (hi - lo, d, f), dtype) * s_in,
        "w_out": jax.random.normal(ks[3], (hi - lo, f, d), dtype) * s_out,
    }
    if sigmoid:
        p["router_bias"] = 0.01 * jax.random.normal(
            jax.random.fold_in(key, 4), (e,), jnp.float32)
    if cfg.moe.n_shared:
        p["shared"] = init_mlp(jax.random.fold_in(key, 5), d,
                               cfg.moe.n_shared * f, "swiglu", dtype)
    return p


def route(p: dict, cfg: ModelConfig, x: jax.Array):
    """x [T, D] → (expert ids [T, k], routing weights [T, k] float32),
    over all ``n_experts``. The router runs in float32 at full precision:
    a choice is discrete, and a rounded score can flip it."""
    k = cfg.moe.top_k
    logits = jnp.matmul(x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.moe.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                               k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx, w * cfg.moe.routed_scaling
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True)


def apply_moe_dropless(p: dict, cfg: ModelConfig, x: jax.Array, *,
                       layer=None, impl: str = "auto"
                       ) -> Tuple[jax.Array, jax.Array]:
    """x [B,S,D] → (y [B,S,D], int32 [2]: the (token, choice) pairs routed
    to held experts, and the held experts at least one token chose).

    ``impl`` "auto" runs the held experts in the ``moe_held_experts`` kernel
    (decode); "ref" runs each on every token (the differentiable forward).
    With ``layer`` (an int32 scalar), ``p``'s routed-expert weights are
    stacked over the layers of a group and that layer's are used."""
    if cfg.mlp != "swiglu":
        raise ValueError(f"dropless experts are SwiGLU, not {cfg.mlp!r}")
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    with jax.named_scope("moe"):
        idx, w = route(p, cfg, x2)
        lo, _ = cfg.moe.held_range()
        y, n_held, touched = ops.moe_held_experts(
            x2, idx, w, p["w_gate"], p["w_up"], p["w_out"], lo, layer=layer,
            impl=impl)
        y = y.reshape(b, s, d)
        if "shared" in p:
            y = y + apply_mlp(p["shared"], x, "swiglu")
    return y, jnp.stack([n_held, touched])


def apply_moe(p: dict, cfg: ModelConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [B,S,D] → (y [B,S,D], aux_loss scalar).

    GShard *grouped* dispatch: tokens are routed in fixed-size groups
    (≤ ``group_size``), so the dispatch tensor is [G, g, E, C] with
    per-group capacity C = ⌈k·g/E·cf⌉. C is independent of the global
    token count and of sequence length (a per-sequence group would make
    dispatch quadratic in S at 32k prefill), and the per-device slice
    under (G→data, E→model) sharding stays O(g·C/E) — the property that
    keeps pod-scale MoE lowerable (DESIGN.md §6)."""
    b_in, s_in, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    g = min(getattr(cfg.moe, "group_size", 4096), s_in)
    assert s_in % g == 0, (s_in, g)
    x = x.reshape(b_in * (s_in // g), g, d)
    b, s, _ = x.shape

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                      # [B,S,k]
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)       # renormalize

    capacity = max(int(math.ceil(k * s / e * cfg.moe.capacity_factor)), 1)

    onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)       # [B,S,k,E]
    # position of each (token, choice) within its expert queue (per group);
    # priority: earlier tokens first, then lower k
    flat = onehot.reshape(b, s * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat                     # exclusive
    pos = pos.reshape(b, s, k, e)
    pos_idx = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)   # [B,S,k]
    keep = jnp.any((pos < capacity) & (onehot > 0), axis=-1)     # [B,S,k]

    cap_onehot = jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32)
    disp = jnp.einsum("bske,bskc->bsec", onehot * keep[..., None], cap_onehot)
    comb = jnp.einsum("bske,bskc->bsec",
                      onehot * (topv * keep)[..., None], cap_onehot)

    cd = x.dtype
    expert_in = jnp.einsum("bsec,bsd->becd", disp.astype(cd), x)    # [B,E,C,D]
    if cfg.mlp in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("becd,edf->becf", expert_in, p["w_gate"])) * \
            jnp.einsum("becd,edf->becf", expert_in, p["w_up"])
    else:
        h = jax.nn.gelu(jnp.einsum("becd,edf->becf", expert_in, p["w_up"]))
    expert_out = jnp.einsum("becf,efd->becd", h, p["w_out"])        # [B,E,C,D]
    y = jnp.einsum("bsec,becd->bsd", comb.astype(cd), expert_out)

    # Switch aux loss: E * Σ_e fraction_tokens(e) * mean_prob(e)
    frac = jnp.mean(onehot.sum(axis=2), axis=(0, 1))                 # [E]
    mean_prob = jnp.mean(probs, axis=(0, 1))                         # [E]
    aux = e * jnp.sum(frac * mean_prob) * cfg.moe.aux_loss_weight
    return y.reshape(b_in, s_in, d), aux.astype(jnp.float32)

"""Decoder-only LM assembly: heterogeneous layer groups + scan-over-layers.

A model is a list of *groups*; each group is a repeating *unit* of block
kinds (e.g. ``("attn",)`` for dense, ``("rec","rec","attn")`` for
RecurrentGemma's 1:2 hybrid pattern). Unit parameters are stacked along a
leading ``count`` dimension and the group runs as one ``jax.lax.scan`` —
HLO size stays O(#groups), not O(#layers), which keeps 96-layer/512-device
dry-run compiles tractable (DESIGN.md §6).

The same group structure drives the decode path: caches are stacked per
group and scanned alongside the parameters.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

from . import attention as attn_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .layers import (apply_mlp, apply_norm, init_embedding, init_mlp,
                     init_norm)

Params = Dict[str, Any]


# ----------------------------------------------------------------------------
# structure
# ----------------------------------------------------------------------------
def apply_remat(body, remat: str):
    """Remat policy for the scan body (the §Perf lever set):

    * ``none`` — no remat: everything the backward needs is saved.
    * ``full`` — recompute everything (max memory savings; re-runs the
      tensor-parallel collectives in the backward pass).
    * ``dots`` — save contraction outputs (``dots_saveable``): activations
      that sit *after* the TP all-reduces are kept, so the backward never
      re-pays fwd collectives — at ~4× the saved-activation footprint.
    """
    if remat == "none":
        return body
    if remat == "full":
        return jax.checkpoint(body)
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_saveable)
    if remat == "dots_nobatch":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    raise ValueError(remat)


#: block kinds made of attention and a feed-forward: "attn" (experts in an
#: moe model, else a dense MLP) and "dense" (a dense MLP of ``d_ff``: the
#: leading layers of a model with ``moe.first_dense``)
ATTN_KINDS = ("attn", "dense")


def layer_groups(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    if cfg.family == "moe" and cfg.moe.first_dense:
        return [(("dense",), cfg.moe.first_dense),
                (("attn",), cfg.n_layers - cfg.moe.first_dense)]
    if cfg.family in ("dense", "moe", "vlm"):
        return [(("attn",), cfg.n_layers)]
    if cfg.family == "ssm":
        return [(("ssm",), cfg.n_layers)]
    if cfg.family == "hybrid":
        pat = tuple(cfg.hybrid.pattern)
        full, rem = divmod(cfg.n_layers, len(pat))
        groups: List[Tuple[Tuple[str, ...], int]] = [(pat, full)]
        if rem:
            groups.append((pat[:rem], 1))
        return groups
    raise ValueError(cfg.family)


def _init_block(key, cfg: ModelConfig, kind: str, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ATTN_KINDS:
        p = {"norm1": init_norm(cfg.d_model, cfg.norm, dtype),
             "attn": (mla_mod.init_mla(k1, cfg, dtype) if cfg.uses_mla
                      else attn_mod.init_attention(k1, cfg, dtype)),
             "norm2": init_norm(cfg.d_model, cfg.norm, dtype)}
        if cfg.family == "moe" and kind == "attn":
            p["moe"] = moe_mod.init_moe(k2, cfg, dtype)
        else:
            p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
        return p
    if kind == "ssm":
        return {"norm1": init_norm(cfg.d_model, cfg.norm, dtype),
                "ssm": ssm_mod.init_ssm(k1, cfg, dtype)}
    if kind == "rec":
        return {"norm1": init_norm(cfg.d_model, cfg.norm, dtype),
                "rec": rglru_mod.init_rglru(k1, cfg, dtype),
                "norm2": init_norm(cfg.d_model, cfg.norm, dtype),
                "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.mlp, dtype)}
    raise ValueError(kind)


def init_params(key, cfg: ModelConfig, vocab: Optional[int] = None) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    vocab = vocab or cfg.vocab_size
    keys = jax.random.split(key, 8)
    groups = []
    for gi, (unit, count) in enumerate(layer_groups(cfg)):
        stacked_units = []
        for ci in range(count):
            ku = jax.random.fold_in(keys[0], gi * 10_000 + ci)
            unit_params = [
                _init_block(jax.random.fold_in(ku, pi), cfg, kind, dtype)
                for pi, kind in enumerate(unit)
            ]
            stacked_units.append(unit_params)
        groups.append(jax.tree.map(lambda *xs: jnp.stack(xs), *stacked_units))
    params: Params = {
        "embed": init_embedding(keys[1], vocab, cfg.d_model, dtype),
        "groups": groups,
        "final_norm": init_norm(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = init_embedding(keys[2], vocab, cfg.d_model, dtype).T
    return params


# ----------------------------------------------------------------------------
# forward (training / prefill)
# ----------------------------------------------------------------------------
def _apply_unit(unit_params, cfg: ModelConfig, unit: Tuple[str, ...],
                x: jax.Array, positions, aux: jax.Array,
                attn_impl: str) -> Tuple[jax.Array, jax.Array]:
    from repro.dist import api as dist_api
    x = dist_api.hint(x)
    for block, kind in zip(unit_params, unit):
        if kind in ATTN_KINDS:
            window = cfg.hybrid.window if cfg.family == "hybrid" else None
            h = apply_norm(block["norm1"], x, cfg.norm, cfg.norm_eps)
            if cfg.uses_mla:
                x = x + mla_mod.mla_attention(block["attn"], cfg, h,
                                              positions)
            else:
                x = x + attn_mod.attention(block["attn"], cfg, h, positions,
                                           causal=True, window=window,
                                           impl=attn_impl)
            h = apply_norm(block["norm2"], x, cfg.norm, cfg.norm_eps)
            if "moe" in block and cfg.moe.held is not None:
                # a layer holding a share of its experts: dropless, every
                # held expert on every token (no aux loss)
                y, _ = moe_mod.apply_moe_dropless(block["moe"], cfg, h,
                                                  impl="ref")
            elif "moe" in block:
                y, a = moe_mod.apply_moe(block["moe"], cfg, h)
                aux = aux + a
            else:
                y = apply_mlp(block["mlp"], h, cfg.mlp)
            x = x + y
        elif kind == "ssm":
            h = apply_norm(block["norm1"], x, cfg.norm)
            x = x + ssm_mod.apply_ssm(block["ssm"], cfg, h)
        elif kind == "rec":
            h = apply_norm(block["norm1"], x, cfg.norm)
            x = x + rglru_mod.apply_rglru(block["rec"], cfg, h)
            h = apply_norm(block["norm2"], x, cfg.norm)
            x = x + apply_mlp(block["mlp"], h, cfg.mlp)
        else:
            raise ValueError(kind)
    return x, aux


def embed_inputs(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 vision_embeds: Optional[jax.Array]) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    if vision_embeds is not None:
        p = vision_embeds.shape[1]
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x[:, p:, :]], axis=1)
    return x.astype(jnp.dtype(cfg.compute_dtype))


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
            positions: Optional[jax.Array] = None,
            vision_embeds: Optional[jax.Array] = None,
            attn_impl: str = "xla") -> Tuple[jax.Array, jax.Array]:
    """tokens [B,S] → (logits [B,S,V], aux_loss)."""
    b, s = tokens.shape
    if positions is None:
        base = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        positions = jnp.broadcast_to(base, (3, b, s)) if cfg.m_rope else base
    x = embed_inputs(params, cfg, tokens, vision_embeds)
    aux = jnp.zeros((), jnp.float32)

    for gi, (unit, count) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]

        def body(carry, layer_params, unit=unit):
            x, aux = carry
            x, aux = _apply_unit(layer_params, cfg, unit, x, positions, aux,
                                 attn_impl)
            return (x, aux), None

        body = apply_remat(body, cfg.remat)
        if cfg.scan_layers:
            (x, aux), _ = jax.lax.scan(body, (x, aux), gp)
        else:
            for ci in range(count):
                (x, aux), _ = body((x, aux), jax.tree.map(lambda a: a[ci], gp))

    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = x @ head.astype(x.dtype)
    return logits, aux


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            attn_impl: str = "xla") -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mean next-token cross entropy (+ MoE aux). Sharded-vocab-safe: the
    label logit is picked with a fused compare-select-reduce, not a gather."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          positions=batch.get("positions"),
                          vision_embeds=batch.get("vision_embeds"),
                          attn_impl=attn_impl)
    labels = batch["labels"]
    ce = cross_entropy(logits, labels)
    return ce + aux, {"ce": ce, "aux": aux}


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Sharded-vocab-safe mean CE: the [B,S,V] one-hot select is pinned to
    the vocab sharding (dist_api.hint_vocab) so it never replicates V."""
    from repro.dist import api as dist_api
    lf = dist_api.hint_vocab(logits.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    vocab_iota = jnp.arange(lf.shape[-1], dtype=labels.dtype)
    onehot = dist_api.hint_vocab(
        (labels[..., None] == vocab_iota).astype(jnp.float32))
    label_logit = jnp.sum(dist_api.hint_vocab(lf * onehot), axis=-1)
    return jnp.mean(lse - label_logit)


# ----------------------------------------------------------------------------
# decode (one token, cache-carrying)
# ----------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.compute_dtype)
    groups = []
    for unit, count in layer_groups(cfg):
        unit_caches = []
        for kind in unit:
            if kind in ATTN_KINDS and cfg.uses_mla:
                unit_caches.append(mla_mod.init_mla_cache(
                    cfg, batch, max_len, dtype, count))
            elif kind in ATTN_KINDS:
                unit_caches.append(attn_mod.init_kv_cache(
                    cfg, batch,
                    max_len if cfg.family != "hybrid"
                    else min(max_len, cfg.hybrid.window),
                    dtype, count))
            elif kind == "ssm":
                unit_caches.append(ssm_mod.init_ssm_cache(cfg, batch, dtype, count))
            elif kind == "rec":
                unit_caches.append(rglru_mod.init_rglru_cache(cfg, batch, dtype,
                                                              count))
        groups.append(unit_caches)
    cache = {"len": jnp.zeros((), jnp.int32), "groups": groups}
    if cfg.family == "moe":
        # the last step's expert counters, summed over its expert layers:
        # (token, choice) pairs routed to held experts, held experts chosen
        cache["moe_counts"] = jnp.zeros((2,), jnp.int32)
    return cache


#: the routed experts' weights of an expert block
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_out")


def _split_experts(unit_params: list) -> Tuple[list, list]:
    """A unit's parameters without the routed experts' weights, and those
    weights (``None`` for a block without experts). Decode hands the expert
    kernel a group's whole stacks and the layer, not a per-layer slice: a
    slice as a kernel's operand is copied out, every held expert, each
    layer of each step."""
    rest, experts = [], []
    for block in unit_params:
        if "moe" not in block:
            rest.append(block)
            experts.append(None)
            continue
        moe = dict(block["moe"])
        experts.append({k: moe.pop(k) for k in EXPERT_WEIGHTS})
        rest.append(dict(block, moe=moe))
    return rest, experts


def _decode_unit(unit_params, unit_cache, cfg: ModelConfig,
                 unit: Tuple[str, ...], x: jax.Array, cache_len,
                 positions, experts=None, layer=None
                 ) -> Tuple[jax.Array, list, Optional[jax.Array]]:
    """→ (x, the unit's new caches, its expert counters or None).
    ``experts``/``layer``: the group's stacked routed-expert weights from
    :func:`_split_experts`, and this unit's index in the group."""
    new_caches = []
    counts = None
    experts = experts or [None] * len(unit)
    for block, cache, kind, stacks in zip(unit_params, unit_cache, unit,
                                          experts):
        if kind in ATTN_KINDS:
            window = cfg.hybrid.window if cfg.family == "hybrid" else None
            h = apply_norm(block["norm1"], x, cfg.norm, cfg.norm_eps)
            if cfg.uses_mla:
                out, c_kv, k_pe = mla_mod.decode_mla(
                    block["attn"], cfg, h, cache["c_kv"], cache["k_pe"],
                    cache_len, positions)
                new_cache = {"c_kv": c_kv, "k_pe": k_pe}
            else:
                # ring-buffer cache for local attention: slot = len % capacity
                slot = (None if window is None
                        else jnp.remainder(cache_len, cache["k"].shape[1]))
                out, k, v = attn_mod.decode_attention(
                    block["attn"], cfg, h, cache["k"], cache["v"], cache_len,
                    positions, window=None, write_pos=slot)
                new_cache = {"k": k, "v": v}
            x = x + out
            h = apply_norm(block["norm2"], x, cfg.norm, cfg.norm_eps)
            if "moe" in block:
                # serving never drops a token
                p = block["moe"] if stacks is None else dict(block["moe"],
                                                             **stacks)
                y, c = moe_mod.apply_moe_dropless(
                    p, cfg, h, layer=None if stacks is None else layer)
                counts = c if counts is None else counts + c
            else:
                y = apply_mlp(block["mlp"], h, cfg.mlp)
            x = x + y
            new_caches.append(new_cache)
        elif kind == "ssm":
            h = apply_norm(block["norm1"], x, cfg.norm)
            y, state, conv = ssm_mod.decode_ssm(block["ssm"], cfg, h,
                                                cache["state"], cache["conv"])
            x = x + y
            new_caches.append({"state": state, "conv": conv})
        elif kind == "rec":
            h = apply_norm(block["norm1"], x, cfg.norm)
            y, state, conv = rglru_mod.decode_rglru(block["rec"], cfg, h,
                                                    cache["state"], cache["conv"])
            x = x + y
            h = apply_norm(block["norm2"], x, cfg.norm)
            x = x + apply_mlp(block["mlp"], h, cfg.mlp)
            new_caches.append({"state": state, "conv": conv})
    return x, new_caches, counts


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                cache: Dict[str, Any], *,
                positions: Optional[jax.Array] = None,
                vision_embeds: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """tokens [B,1] + cache → (logits [B,1,V], updated cache)."""
    b = tokens.shape[0]
    cache_len = cache["len"]
    if positions is None:
        base = jnp.broadcast_to(cache_len[None, None], (b, 1)).astype(jnp.int32)
        positions = jnp.broadcast_to(base, (3, b, 1)) if cfg.m_rope else base
    x = embed_inputs(params, cfg, tokens, vision_embeds)

    new_groups = []
    moe_counts = None
    for gi, (unit, count) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]
        gc = cache["groups"][gi]
        gp, experts = _split_experts(gp)
        if not any(experts):
            experts = None

        def body(x, inp, unit=unit, experts=experts):
            layer_params, layer_cache, layer = inp
            x, new_cache, counts = _decode_unit(layer_params, layer_cache,
                                                cfg, unit, x, cache_len,
                                                positions, experts, layer)
            return x, (new_cache, counts)

        index = jnp.arange(count, dtype=jnp.int32) if experts else None
        if cfg.scan_layers:
            x, (new_gc, counts) = jax.lax.scan(body, x, (gp, gc, index))
        else:
            outs = []
            for ci in range(count):
                x, nc = body(x, jax.tree.map(lambda a: a[ci],
                                             (gp, gc, index)))
                outs.append(nc)
            new_gc, counts = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        new_groups.append(new_gc)
        if counts is not None:
            counts = jnp.sum(counts, axis=0)
            moe_counts = counts if moe_counts is None else moe_counts + counts

    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = x @ head.astype(x.dtype)
    new_cache = {"len": cache_len + 1, "groups": new_groups}
    if "moe_counts" in cache:
        new_cache["moe_counts"] = moe_counts
    return logits, new_cache

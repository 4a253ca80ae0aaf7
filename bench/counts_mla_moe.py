"""Operations and bytes of a DeepSeek-V3 decoder's decode step (latent
attention, a leading dense layer, routed and shared experts) from its
sizes: the keys of ``bench/configs/moonlight-16b-a3b.json`` (Hugging Face
``config.json`` names, ``n_routed_experts`` being the experts held here).

Copied arithmetic, not imported from the program, so that a change to the
program cannot move the yardstick:

* a matmul costs 2 FLOPs per weight and token. A token multiplies every
  weight outside the routed experts (attention projections, ``wkv_b`` in
  its absorbed form, the dense layer, the router, the shared experts, the
  head; the embedding is a gather), and of the routed experts only those it
  chose: ``2 * expert_params`` for each (token, choice) pair that lands on a
  held expert (the program's counter);
* absorbed latent attention at a context of ``c`` positions costs ``2 * H *
  c * (2 * kv_lora_rank + qk_rope_head_dim)`` FLOPs a layer: scores against
  the latent and the rotary key, then the weighted sum of the latent;
* a decode step reads at least every weight outside the routed experts once
  (the embedding table only in the rows of its tokens), the weights of each
  held expert some token chose, and the latent cache of each request's
  occupied positions.

The correction bias (``router_experts`` float32 values a layer) is routing
state, not a weight: it is left out of the parameter count and its bytes
are counted on their own.
"""
from __future__ import annotations

from typing import Iterable


def _i(sizes: dict, key: str) -> int:
    return int(sizes[key])


def n_moe_layers(sizes: dict) -> int:
    return _i(sizes, "num_hidden_layers") - _i(sizes, "first_k_dense_replace")


def attention_params(sizes: dict) -> int:
    """One layer's latent attention: wq, wkv_a, kv_norm, wkv_b, wo."""
    d, h = _i(sizes, "hidden_size"), _i(sizes, "num_attention_heads")
    dn, dr = _i(sizes, "qk_nope_head_dim"), _i(sizes, "qk_rope_head_dim")
    dv, r = _i(sizes, "v_head_dim"), _i(sizes, "kv_lora_rank")
    return (d * h * (dn + dr) + d * (r + dr) + r + r * h * (dn + dv)
            + h * dv * d)


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down of ``moe_intermediate_size``."""
    return 3 * _i(sizes, "hidden_size") * _i(sizes, "moe_intermediate_size")


def expert_bytes(sizes: dict) -> int:
    return expert_params(sizes) * dtype_bytes(sizes["torch_dtype"])


def dense_layer_params(sizes: dict) -> int:
    d = _i(sizes, "hidden_size")
    return attention_params(sizes) + 2 * d + 3 * d * _i(sizes,
                                                       "intermediate_size")


def moe_layer_params(sizes: dict, experts: int) -> int:
    """One expert layer holding ``experts`` routed experts."""
    d = _i(sizes, "hidden_size")
    shared = 3 * d * _i(sizes, "moe_intermediate_size") * _i(
        sizes, "n_shared_experts")
    router = d * _i(sizes, "router_experts")
    return (attention_params(sizes) + 2 * d + router + shared
            + experts * expert_params(sizes))


def embedding_params(sizes: dict) -> int:
    return _i(sizes, "vocab_size") * _i(sizes, "hidden_size")


def param_count(sizes: dict, uncut: bool = False) -> int:
    """Parameters held: the chip's share (``n_routed_experts`` held), or,
    ``uncut``, the whole model (``router_experts`` routed experts)."""
    experts = _i(sizes, "router_experts" if uncut else "n_routed_experts")
    head = 0 if sizes.get("tie_word_embeddings") else embedding_params(sizes)
    return (_i(sizes, "first_k_dense_replace") * dense_layer_params(sizes)
            + n_moe_layers(sizes) * moe_layer_params(sizes, experts)
            + embedding_params(sizes) + head + _i(sizes, "hidden_size"))


def routed_params(sizes: dict) -> int:
    """The held routed experts of every expert layer."""
    return n_moe_layers(sizes) * _i(sizes, "n_routed_experts") * \
        expert_params(sizes)


def non_expert_matmul_params(sizes: dict) -> int:
    """Weights every token multiplies: all but the routed experts, the
    norms, the latent norm and the embedding table."""
    d = _i(sizes, "hidden_size")
    r = _i(sizes, "kv_lora_rank")
    layers = _i(sizes, "num_hidden_layers")
    norms = layers * (2 * d + r) + d
    return param_count(sizes) - routed_params(sizes) - norms - \
        embedding_params(sizes)


def dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def non_expert_bytes(sizes: dict, tokens: int) -> int:
    """Bytes a step of ``tokens`` tokens reads outside the routed experts:
    every such weight, the correction bias, and ``tokens`` embedding rows."""
    el = dtype_bytes(sizes["torch_dtype"])
    weights = param_count(sizes) - routed_params(sizes) - \
        embedding_params(sizes)
    bias = n_moe_layers(sizes) * _i(sizes, "router_experts") * 4
    return weights * el + bias + tokens * _i(sizes, "hidden_size") * el


def latent_bytes_per_token(sizes: dict) -> int:
    """The cache of one position of one request, all layers: the latent and
    the rotary key."""
    return (_i(sizes, "num_hidden_layers")
            * (_i(sizes, "kv_lora_rank") + _i(sizes, "qk_rope_head_dim"))
            * dtype_bytes(sizes["torch_dtype"]))


def attention_flops(sizes: dict, context: int) -> float:
    """Absorbed latent attention of one token over ``context`` positions,
    all layers."""
    h = _i(sizes, "num_attention_heads")
    return (2.0 * h * context * (2 * _i(sizes, "kv_lora_rank")
                                 + _i(sizes, "qk_rope_head_dim"))
            * _i(sizes, "num_hidden_layers"))


def step_flops(sizes: dict, contexts: Iterable[int]) -> float:
    """FLOPs of a decode step outside the routed experts; ``contexts``
    holds each request's attended positions."""
    contexts = list(contexts)
    return sum(2.0 * non_expert_matmul_params(sizes)
               + attention_flops(sizes, c) for c in contexts)


def routed_flops(sizes: dict, held_choices: int) -> float:
    """The held experts' FLOPs for ``held_choices`` (token, choice) pairs."""
    return 2.0 * expert_params(sizes) * held_choices


def step_bytes(sizes: dict, contexts: Iterable[int],
               touched_experts: float) -> float:
    """Least bytes of a decode step: the weights outside the routed
    experts, the touched experts' weights, the latent cache of each
    request's occupied positions."""
    contexts = list(contexts)
    return (non_expert_bytes(sizes, len(contexts))
            + touched_experts * expert_bytes(sizes)
            + latent_bytes_per_token(sizes) * sum(contexts))

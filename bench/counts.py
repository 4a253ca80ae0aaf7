"""Operations and bytes of a dense decoder LM's decode step, from its sizes.

The sizes are the keys of a configuration file under ``bench/configs``
(Hugging Face ``config.json`` names). The arithmetic follows the model
FLOP accounting of ``repro.roofline.analysis.model_flops_for`` (copied, not
imported, so a change to the program cannot move the yardstick):

* a decoded token costs 2 FLOPs per matmul parameter, the tied output head
  included (the embedding lookup is a gather, not a matmul);
* attention at a context of ``c`` cached positions costs ``4 * c * H * Dh``
  FLOPs per layer (``q k^T`` and ``p v``, 2 FLOPs a multiply-add);
* a decode step reads every parameter once, plus the K/V of the positions
  each request of the batch has occupied.
"""
from __future__ import annotations

from typing import Iterable


def _dims(sizes: dict):
    d = int(sizes["hidden_size"])
    h = int(sizes["num_attention_heads"])
    hkv = int(sizes["num_key_value_heads"])
    hd = int(sizes.get("head_dim") or d // h)
    return d, h, hkv, hd


def layer_matmul_params(sizes: dict) -> int:
    """Matmul parameters of one decoder layer (attention + gated MLP)."""
    d, h, hkv, hd = _dims(sizes)
    f = int(sizes["intermediate_size"])
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    mlp = 3 * d * f
    return attn + mlp


def matmul_params(sizes: dict) -> int:
    """Parameters a decoded token multiplies: every layer and the head."""
    head = int(sizes["vocab_size"]) * int(sizes["hidden_size"])
    return int(sizes["num_hidden_layers"]) * layer_matmul_params(sizes) + head


def param_count(sizes: dict) -> int:
    """All parameters held: layers, norms, embedding (and head if untied)."""
    d, h, hkv, hd = _dims(sizes)
    n_layers = int(sizes["num_hidden_layers"])
    norms = 2 * d + (2 * hd if sizes.get("qk_norm", False) else 0)
    emb = int(sizes["vocab_size"]) * d
    head = 0 if sizes.get("tie_word_embeddings", False) else emb
    return n_layers * (layer_matmul_params(sizes) + norms) + d + emb + head


def dtype_bytes(name: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[name]


def param_bytes(sizes: dict) -> int:
    return param_count(sizes) * dtype_bytes(sizes["torch_dtype"])


def kv_bytes_per_token(sizes: dict) -> int:
    """K and V of one cached position of one request, all layers."""
    d, h, hkv, hd = _dims(sizes)
    return (int(sizes["num_hidden_layers"]) * 2 * hkv * hd
            * dtype_bytes(sizes["torch_dtype"]))


def attention_flops(sizes: dict, context: int) -> float:
    d, h, hkv, hd = _dims(sizes)
    return 4.0 * context * h * hd * int(sizes["num_hidden_layers"])


def decode_token_flops(sizes: dict, context: int) -> float:
    """FLOPs of one decoded token that attends over ``context`` positions
    (its own included)."""
    return 2.0 * matmul_params(sizes) + attention_flops(sizes, context)


def decode_step_flops(sizes: dict, contexts: Iterable[int]) -> float:
    """FLOPs of one batched decode step; ``contexts`` holds each request's
    attended positions."""
    return sum(decode_token_flops(sizes, c) for c in contexts)


def decode_step_bytes(sizes: dict, contexts: Iterable[int]) -> float:
    """Bytes one batched decode step must read at least: the parameters
    once, and the K/V of each request's occupied positions."""
    return param_bytes(sizes) + kv_bytes_per_token(sizes) * sum(contexts)

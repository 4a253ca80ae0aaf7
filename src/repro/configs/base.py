"""Model/arch configuration schema.

Every assigned architecture gets a ``configs/<id>.py`` exporting
``config()`` (the exact published shape) and ``smoke_config()`` (a reduced
same-family config for CPU smoke tests). The registry in
``configs/__init__`` resolves ``--arch <id>``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    #: routing group length; capacity C = ⌈k·g/E·cf⌉ is independent of S
    group_size: int = 4096
    #: routed expert width (``moe_intermediate_size``); 0 → ``d_ff``
    d_expert: int = 0
    #: shared experts, applied to every token as one SwiGLU of width
    #: ``n_shared * d_expert`` (DeepSeek-MoE)
    n_shared: int = 0
    #: "softmax": top-k of the softmax, renormalised; "sigmoid": DeepSeek-V3
    #: ``noaux_tc`` with one group (sigmoid scores, a correction bias added
    #: for the choice only, the chosen scores renormalised and scaled by
    #: ``routed_scaling``)
    router: str = "softmax"
    routed_scaling: float = 1.0
    #: leading dense layers (SwiGLU of ``d_ff``) before the expert layers
    #: (``first_k_dense_replace``)
    first_dense: int = 0
    #: the routed experts this chip holds, ``[lo, hi)`` (expert parallelism):
    #: the router still scores all ``n_experts``; the layer computes only the
    #: held experts' part. None → all of them
    held: Optional[Tuple[int, int]] = None

    def held_range(self) -> Tuple[int, int]:
        return self.held if self.held is not None else (0, self.n_experts)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3) with no query LoRA.
    ``kv_lora_rank`` 0 means the model uses GQA attention instead."""
    kv_lora_rank: int = 0          # latent width of the K/V cache
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64     # rotary key, shared by all heads
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128       # N
    head_dim: int = 64         # P
    expand: int = 2            # d_inner = expand * d_model
    conv_width: int = 4
    chunk: int = 256           # SSD chunk length
    n_groups: int = 1          # B/C groups


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    #: repeating unit of temporal mixers, e.g. ("rec", "rec", "attn")
    pattern: Tuple[str, ...] = ()
    window: int = 2048         # local-attention window
    lru_width: Optional[int] = None
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    n_enc_layers: int = 0
    n_frames: int = 1500       # stub frontend: precomputed frame embeddings


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    # attention details
    qk_norm: bool = False
    attn_bias: bool = False            # qwen1.5 QKV bias
    attn_logit_softcap: Optional[float] = None
    rope_theta: float = 10_000.0
    #: rotary on interleaved pairs (x[2i], x[2i+1]) as DeepSeek applies it;
    #: default half-split (NeoX)
    rope_interleave: bool = False
    norm_eps: float = 1e-6
    m_rope: bool = False               # qwen2-vl 3-axis rotary
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    # mlp
    mlp: str = "swiglu"                # swiglu | geglu | gelu | relu2
    norm: str = "rmsnorm"              # rmsnorm | layernorm
    tie_embeddings: bool = False
    # sub-configs
    moe: MoEConfig = MoEConfig()
    mla: MLAConfig = MLAConfig()
    ssm: SSMConfig = SSMConfig()
    hybrid: HybridConfig = HybridConfig()
    encdec: EncDecConfig = EncDecConfig()
    # vlm stub frontend
    n_vision_tokens: int = 0
    # numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    # distribution knobs (overridable per run)
    remat: str = "full"                # none | full
    scan_layers: bool = True
    #: long-context support class, used to decide long_500k applicability
    subquadratic: bool = False

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def expert_width(self) -> int:
        return self.moe.d_expert or self.d_ff

    @property
    def uses_mla(self) -> bool:
        return self.mla.kv_lora_rank > 0

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs decode (whisper is enc-dec)

    def padded_vocab(self, multiple: int) -> int:
        return ((self.vocab_size + multiple - 1) // multiple) * multiple

    def dtype(self) -> jnp.dtype:
        return jnp.dtype(self.compute_dtype)

    def _mla_params(self) -> int:
        m, d, h = self.mla, self.d_model, self.n_heads
        return (d * h * (m.qk_nope_head_dim + m.qk_rope_head_dim)
                + d * (m.kv_lora_rank + m.qk_rope_head_dim) + m.kv_lora_rank
                + m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim)
                + h * m.v_head_dim * d)

    def param_count(self) -> int:
        """Approximate parameter count (used for MODEL_FLOPS and reporting)."""
        d, f, v, l = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        hd = self.resolved_head_dim
        qkvo = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + \
            self.n_heads * hd * d
        if self.uses_mla:
            qkvo = self._mla_params()
        gated = self.mlp in ("swiglu", "geglu")
        mlp = d * f * (3 if gated else 2)
        dense_mlp = mlp
        if self.family == "moe":
            mlp = d * self.expert_width * (3 if gated else 2) * (
                self.moe.n_experts + self.moe.n_shared)
            mlp += d * self.moe.n_experts  # router
        if self.family == "ssm":
            di = self.ssm.expand * d
            n = self.ssm.state_dim
            nh = di // self.ssm.head_dim
            g = self.ssm.n_groups
            qkvo = d * (2 * di + 2 * g * n + nh) + di * d
            mlp = 0
        if self.family == "hybrid":
            lru = self.hybrid.lru_width or d
            rec = d * lru * 2 + lru * d + 3 * lru  # branches + out + gates
            att = qkvo
            pat = self.hybrid.pattern or ("rec",)
            frac_rec = sum(1 for p in pat if p == "rec") / len(pat)
            qkvo = rec * frac_rec + att * (1 - frac_rec)
        emb = v * d * (1 if self.tie_embeddings else 2)
        enc = 0
        if self.family == "encdec":
            # encoder layers: self-attn + mlp; decoder adds cross-attn
            enc = self.encdec.n_enc_layers * (qkvo + mlp)
            qkvo = 2 * qkvo  # decoder self + cross
        # leading dense layers hold a dense MLP in place of the experts
        first = self.moe.first_dense if self.family == "moe" else 0
        return int(l * (qkvo + mlp) + first * (dense_mlp - mlp) + emb + enc)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k experts)."""
        if self.family != "moe":
            return self.param_count()
        d, l = self.d_model, self.n_layers - self.moe.first_dense
        gated = self.mlp in ("swiglu", "geglu")
        per_expert = d * self.expert_width * (3 if gated else 2)
        total = self.param_count()
        inactive = l * per_expert * (self.moe.n_experts - self.moe.top_k)
        return int(total - inactive)

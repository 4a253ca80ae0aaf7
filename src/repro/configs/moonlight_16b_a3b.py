"""moonlight-16b-a3b [hf:moonshotai/Moonlight-16B-A3B, config.json; hf]
27L d_model=2048 16H MLA (kv_lora_rank 512, q nope 128 + rope 64, v 128, no
q-LoRA), layer 0 dense SwiGLU d_ff=11264, layers 1-26 MoE: 64 routed experts
of 1408 (sigmoid router, noaux_tc, top-6, scale 2.446) + 2 shared; vocab
163840, untied head, rotary on interleaved pairs (theta 50000), RMS eps 1e-5.

``config()`` is one chip's share of a v5e-4 deployment with expert
parallelism 4: attention, the dense layer, the shared experts and the
vocabulary on every chip, and this chip holding routed experts 0-15 of every
MoE layer (``moe.held``). The router still scores all 64 experts."""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

ARCH = "moonlight-16b-a3b"


def config(held=(0, 16)) -> ModelConfig:
    return ModelConfig(
        name=ARCH, family="moe", n_layers=27, d_model=2048, n_heads=16,
        n_kv_heads=16, d_ff=11264, vocab_size=163840, mlp="swiglu",
        rope_theta=50_000.0, rope_interleave=True, norm_eps=1e-5,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                      router="sigmoid", routed_scaling=2.446, first_dense=1,
                      held=held))


def smoke_config(held=(0, 2)) -> ModelConfig:
    return ModelConfig(
        name=ARCH + "-smoke", family="moe", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=256, mlp="swiglu",
        rope_theta=50_000.0, rope_interleave=True, norm_eps=1e-5,
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=MoEConfig(n_experts=8, top_k=3, d_expert=32, n_shared=2,
                      router="sigmoid", routed_scaling=2.446, first_dense=1,
                      held=held),
        param_dtype="float32", compute_dtype="float32")

"""deepseek-v2-236b-ep16 — one chip's share of DeepSeek-V2 served with
16-way expert parallelism [hf:deepseek-ai/DeepSeek-V2 config.json].

The deployment: each layer's 160 routed experts are divided over 16 chips
(a v5e 4x4 slice), 10 a chip; attention, the shared experts, the dense
layer and the vocabulary are replicated on every chip (data-parallel
attention, as in the DeepSeek-V3/R1 inference system overview); the
layers left out lie on further pipeline stages.  This chip holds one
stage: the dense layer and 5 MoE layers, with experts 0-9 of each.

Every width is as published: 128 heads, MLA (q_lora 1536, kv_lora 512,
nope/rope/v head dims 128/64/128), YaRN rope (factor 40 over 4096,
beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707), dense width
12288, expert width 1536, the router's 160 outputs, top-6 in the best 3
of 8 groups, no renormalization and a routed scaling of 16, 2 shared
experts, vocab 102400 untied.
"""
from repro.models import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b-ep16", family="moe",
    n_layers=6, d_model=5120, n_heads=128, n_kv_heads=128,
    d_head=128, d_ff=12288, vocab_size=102400, rope_theta=1e4,
    rope_interleave=True, yarn_factor=40.0, yarn_original_max_len=4096,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    attn_kind="mla", q_lora_rank=1536, kv_lora_rank=512,
    rope_head_dim=64, nope_head_dim=128,
    n_experts=160, n_shared_experts=2, top_k=6, moe_d_ff=1536,
    n_dense_layers=1, n_group=8, topk_group=3, norm_topk_prob=False,
    routed_scaling_factor=16.0, n_experts_held=10, first_expert=0,
)


def reduced() -> ModelConfig:
    """8 experts in 4 groups, 4 of them held; top-2 in the best 2 groups;
    YaRN on."""
    return CONFIG.replace(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
                          d_head=16, d_ff=96, vocab_size=512,
                          q_lora_rank=32, kv_lora_rank=24, rope_head_dim=8,
                          nope_head_dim=16, n_experts=8, n_group=4,
                          topk_group=2, top_k=2, moe_d_ff=32,
                          n_experts_held=4, first_expert=0,
                          compute_dtype="float32")

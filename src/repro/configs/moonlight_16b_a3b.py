"""Moonlight-16B-A3B — DeepSeek-V3 block: latent attention and a dropless
fine-grained MoE (64 routed experts, 6 per token, 2 shared) with sigmoid
routing and a balancing bias, after one leading dense layer.

Published: [hf:moonshotai/Moonlight-16B-A3B config.json, model_type
deepseek_v3] 27 layers (first_k_dense_replace 1), d_model 2048, 16 heads,
no query LoRA, kv_lora_rank 512, qk_nope 128, qk_rope 64, v 128, dense
width 11264, expert width 1408, n_group = topk_group = 1 (noaux_tc is a
plain top-k), norm_topk_prob, routed_scaling_factor 2.446, rope θ 50000,
RMSNorm eps 1e-5, untied head, vocabulary 163840.

Not in the config, assumed from DeepSeek-V3 (arXiv:2412.19437 §2.1.2,
§4.2): the bias update speed γ = 0.001 and the sequence-wise balance loss
α = 1e-4 (the config's ``seq_aux`` is true).  The bias moves by the loads
of each update event's own batch on this device; in an expert-parallel
deployment the loads would be summed over every chip's tokens first.
"""

import dataclasses

from repro.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    block_pattern=("mla_moe",),
    first_k_dense=1,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    top_k=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    routed_scaling=2.446,
    norm_topk_prob=True,
    router_bias_speed=1e-3,
    seq_aux_loss=1e-4,
    router_z_loss=0.0,
    load_balance_loss=0.0,
    rope_theta=50000.0,
    norm_eps=1e-5,
    source="Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B]",
)

SMOKE = dataclasses.replace(
    CONFIG, n_layers=3, n_units=2, d_model=128, n_heads=4, n_kv_heads=4,
    d_ff=256, vocab_size=512, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_experts=4, top_k=2, moe_d_ff=64,
    n_shared_experts=1)

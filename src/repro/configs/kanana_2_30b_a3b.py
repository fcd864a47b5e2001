"""Kanana-2-30B-A3B: DeepSeek-V3 blocks (``model_type`` deepseek_v3):
latent attention without a q low-rank, 128 sigmoid-routed experts (top-6,
``noaux_tc``, one group) with 2 shared experts, the first layer dense.
[hf:kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json; arXiv:2412.19437]"""
import dataclasses
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="kanana-2-30b-a3b", family="moe", n_layers=48, d_model=2048,
    n_heads=32, n_kv_heads=32, d_ff=6144, vocab_size=128256,
    pattern=("attn_moe",), first_dense_layers=1, mlp_type="swiglu",
    n_experts=128, router_experts=128, moe_top_k=6, moe_d_ff=768,
    n_shared_experts=2, router="sigmoid", routed_scaling=2.448,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    rope_theta=1e6, rope_interleave=True, norm_eps=1e-6,
    tie_embeddings=False,
)

# a share of 4 of 16 experts, so the CPU tests route past the held ones
REDUCED = dataclasses.replace(
    CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
    vocab_size=256, n_experts=4, router_experts=16, moe_top_k=3,
    moe_d_ff=32, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
    v_head_dim=16)

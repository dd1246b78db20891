"""deepseek-v2-lite-16b [moe] — 27L d_model=2048 16H; layer 0 a dense
SwiGLU MLP of width 10944, layers 1-26 MoE: a softmax router over 64
routed experts of width 1408, greedy top-6 without renormalisation, and 2
shared experts; MLA (no q-LoRA, kv_lora 512, qk 128 + 64 rope, v 128)
with YaRN rope scaling (factor 40, mscale 0.707 on both sides, original
4096 positions); rms eps 1e-6; vocab 102400, untied head.
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,
    vocab_size=102400,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_original_len=4096,
    mla=True,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    moe=True,
    n_experts=64,
    top_k=6,
    norm_topk=False,
    n_shared_experts=2,
    moe_d_ff=1408,
    n_dense_layers=1,
    capacity_factor=1.25,
    rms_eps=1e-6,
    logit_chunk=512,
)

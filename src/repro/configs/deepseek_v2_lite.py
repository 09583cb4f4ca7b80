"""deepseek-v2-lite [moe]: 27L d=2048 16H, multi-head latent attention
(kv_lora_rank 512, no query compression, qk 128 + 64 RoPE, v 128, YaRN
x40 over 4096), layer 0 a dense SwiGLU of 10944, then 26 layers of 64
routed experts of 1408 (softmax top-6, weights not renormalised) plus 2
shared experts with no gate; vocab 102400, untied.  15.7B parameters.
[hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434]

The serving path holds a share of the routed experts per chip
(``MoEConfig.expert_lo`` / ``n_held``); this published config holds them
all, and drops no token (capacity_factor 0).
"""
from ..core.config import ArchConfig, AttnConfig, MoEConfig

_YARN = dict(yarn_factor=40.0, yarn_original_max=4096, yarn_beta_fast=32.0,
             yarn_beta_slow=1.0, yarn_mscale_all_dim=0.707)

CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab=102400, head_dim=192,
    act="swiglu", norm="rmsnorm", tie_embeddings=False, n_dense_layers=1,
    attn=AttnConfig(kind="full", rope_theta=10000.0, chunk=1024,
                    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                    v_head_dim=128, **_YARN),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_ff_expert=1408,
                  capacity_factor=0.0, norm_topk=False, shared_gate=False),
)

SMOKE = ArchConfig(
    name="deepseek-v2-lite-smoke", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=96, vocab=512, head_dim=24,
    act="swiglu", norm="rmsnorm", tie_embeddings=False, n_dense_layers=1,
    attn=AttnConfig(kind="full", rope_theta=10000.0, chunk=16,
                    kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                    v_head_dim=16, **_YARN),
    moe=MoEConfig(n_experts=8, top_k=2, n_shared=1, d_ff_expert=32,
                  capacity_factor=0.0, norm_topk=False, shared_gate=False,
                  expert_lo=0, n_held=4),
)

"""deepseek-v3 — 61L d_model=7168 128H, latent attention (MLA: q_lora 1536,
kv_lora 512, nope 128 + rope 64 per query/key head, v 128), YaRN rope
(factor 40 over 4096 positions), 3 leading dense layers of width 18432,
then 256 routed SwiGLU experts of width 2048 top-8 by sigmoid scores
limited to 4 of 8 groups, plus 1 shared expert; vocab 129280, untied.
[hf:deepseek-ai/DeepSeek-V3 config.json]

The multi-token-prediction layer (num_nextn_predict_layers 1) is a draft
head that plain decoding never runs, and is left out.  The cache holds
the 512-wide latent and the 64-wide rope key per position and layer.
"""

from repro.models.config import ModelConfig
from repro.configs.base import register

CONFIG = ModelConfig(
    name="deepseek-v3",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,
    d_ff=2048,
    vocab_size=129280,
    num_experts=256,
    experts_per_token=8,
    router="sigmoid",
    router_groups=8,
    router_groups_kept=4,
    routed_scaling=2.5,
    shared_experts=1,
    first_dense_layers=3,
    dense_d_ff=18432,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_factor=40.0,
    rope_original_max=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=1.0,
    yarn_mscale_all_dim=1.0,
    rms_norm_eps=1e-6,
    optimizer="adafactor",
)

# 1 dense layer, then 2 MoE layers of 8 experts in 2 groups of 4 (1 kept),
# top-2, 1 shared expert; latent and rope widths keep the published ratio
SMOKE = ModelConfig(
    name="deepseek-v3-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    d_ff=32,
    vocab_size=256,
    num_experts=8,
    experts_per_token=2,
    router="sigmoid",
    router_groups=2,
    router_groups_kept=1,
    routed_scaling=2.5,
    shared_experts=1,
    first_dense_layers=1,
    dense_d_ff=96,
    q_lora_rank=48,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    rope_theta=10000.0,
    rope_factor=40.0,
    rope_original_max=64,
    flash_threshold=64,
)

register(CONFIG, SMOKE, "hf:deepseek-ai/DeepSeek-V3; hf")

"""deepseek-v2-lite — MLA (no q-LoRA, latent 512 + rope 64, YaRN) with one
leading dense layer, then 26 MoE layers of 64 routed experts (top-6,
softmax, gates not renormalised) and 2 shared experts.
[hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434]"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,                      # the leading dense layer's MLP
    vocab_size=102400,
    norm_eps=1e-6,
    rope_theta=10_000.0,
    rope_scaling=YarnConfig(factor=40.0,
                            original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    first_k_dense=1,
    # RL fine-tunes a pretrained router: no balance or z-loss here
    moe=MoEConfig(num_experts=64, top_k=6, d_ff=1408, router_z_coef=0.0,
                  load_balance_coef=0.0, norm_topk_prob=False,
                  num_shared_experts=2),
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)

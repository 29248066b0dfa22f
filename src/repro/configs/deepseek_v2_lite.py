"""deepseek-v2-lite — MoE with Multi-head Latent Attention, 15.7B total /
2.4B active [hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434].

27L (the first dense: MLP of 10944), d_model=2048, 16H MLA with no q
compression, kv_lora=512, nope 128 / rope 64 / v 128; YaRN rope (factor
40 over 4096 original positions, mscale = mscale_all_dim = 0.707); MoE:
2 shared + 64 routed experts of 1408, greedy top-6 of a softmax router,
gates not renormalised, per-sequence balance loss (alpha 0.001, an
assumption: the published config.json copy read here leaves it out);
vocab 102400, untied.
"""

from repro.configs.base import ModelConfig, YaRN

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    first_k_dense=1,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    norm_eps=1e-6,
    rope_theta=10000.0,
    rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    # MLA
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=0,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    # MoE
    num_experts=64,
    num_shared_experts=2,
    experts_per_token=6,
    moe_d_ff=1408,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    seq_aux=True,
    router_aux_coef=0.001,
    act="silu",
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)

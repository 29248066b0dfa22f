"""deepseek-v2-236b — MoE with Multi-head Latent Attention
[arXiv:2405.04434].

60L (the first dense: MLP of 12288), d_model=5120, 128H, MLA kv_lora=512
(+64 rope), q_lora=1536; MoE: 2 shared + 160 routed experts, top-6,
expert d_ff=1536, gates not renormalised and scaled by 16, per-sequence
balance loss; vocab=102400. Routing is greedy top-6 over all 160
experts: the published device-limited routing (top-6 within the best 3
of 8 expert groups) is not modelled, nor the YaRN rope.
"""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    num_layers=60,
    first_k_dense=1,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,
    d_ff=12288,
    vocab_size=102400,
    # MLA
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=1536,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    # MoE
    num_experts=160,
    num_shared_experts=2,
    experts_per_token=6,
    moe_d_ff=1536,
    norm_topk_prob=False,
    routed_scaling_factor=16.0,
    seq_aux=True,
    router_aux_coef=0.003,
    act="silu",
    source="arXiv:2405.04434",
)

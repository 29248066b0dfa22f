"""Config system: a single frozen dataclass covering every assigned
architecture family (dense / MoE / SSM / hybrid / enc-dec / VLM / CNN).

Every named config lives in its own ``configs/<id>.py`` module citing its
source; ``configs/__init__.py`` is the registry (``--arch <id>``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rope scaling (Peng et al., arXiv:2309.00071) as DeepSeek-V2
    publishes it under ``rope_scaling``: rotary frequencies blended
    between the original and the ``factor``-stretched ones over the
    band that ``beta_fast`` .. ``beta_slow`` rotations mark within
    ``original_max_position_embeddings``; cos/sin scaled by
    m(mscale) / m(mscale_all_dim) and the softmax by m(mscale_all_dim)^2,
    where m(s) = 0.1 s ln(factor) + 1."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm | cnn
    num_layers: int
    d_model: int
    vocab_size: int
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0

    # --- attention flavor ---
    qkv_bias: bool = False         # qwen2
    qk_norm: bool = False          # qwen3
    use_rope: bool = True          # whisper: sinusoidal only
    rope_theta: float = 10000.0
    rope_scaling: Optional[YaRN] = None
    sliding_window: int = 0        # 0 = full attention; >0 = window size
    attn_logit_softcap: float = 0.0

    # --- MoE ---
    num_experts: int = 0
    num_shared_experts: int = 0    # deepseek-v2
    experts_per_token: int = 0
    moe_d_ff: int = 0              # per-expert hidden dim
    router_aux_coef: float = 0.01  # balance-loss weight (DeepSeek's alpha)
    seq_aux: bool = False          # DeepSeek's per-sequence balance loss
                                   # (else Switch's, over the whole batch)
    norm_topk_prob: bool = True    # renormalise the top-k gates to sum 1
    routed_scaling_factor: float = 1.0
    experts_held: int = 0          # routed experts this layer holds (0: all)
    expert_offset: int = 0         # id of the first expert held
    first_k_dense: int = 0         # leading dense layers (MLP of d_ff)
                                   # ahead of the MoE layers (deepseek)

    # --- MLA (deepseek-v2) ---
    use_mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # --- SSM ---
    ssm_variant: str = ""          # "mamba1" | "mamba2"
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64         # mamba2
    ssm_groups: int = 1            # mamba2 B/C groups
    ssm_dt_rank: int = 0           # mamba1 (0 => ceil(d_model/16))

    # --- hybrid (zamba2) ---
    attn_every: int = 0            # shared attention block every k layers

    # --- enc-dec (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0           # stub audio frames (post conv frontend)

    # --- VLM (paligemma) ---
    num_image_tokens: int = 0      # stub SigLIP patch embeddings

    # --- misc ---
    loss_chunk: int = 0            # 0 = whole-sequence logits; >0 = scan
                                   # the vocab matmul+NLL over seq chunks
                                   # (checkpointed — O(B*c*V) live logits)
    act: str = "silu"
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    remat: bool = True
    source: str = ""               # citation

    # ------------------------------------------------------------- derived
    @property
    def attn_dims(self) -> tuple[int, int, int]:
        hd = self.head_dim or (self.d_model // max(self.num_heads, 1))
        return self.num_heads, (self.num_kv_heads or self.num_heads), hd

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.d_model // 16)

    def reduced(self, *, max_layers: int = 2, max_d_model: int = 256,
                max_experts: int = 4, max_vocab: int = 512) -> "ModelConfig":
        """CPU-smoke-test variant of the same family (assignment spec:
        <=2 layers, d_model<=512, <=4 experts)."""
        n_h, n_kv, _ = self.attn_dims
        shrink = max(1, self.d_model // max_d_model)
        d_model = max(self.d_model // shrink, 64)
        heads = max(min(self.num_heads, 4), 1) if self.num_heads else 0
        kv = max(min(self.num_kv_heads, heads), 1) if self.num_kv_heads else heads
        if heads and kv and heads % kv:
            kv = 1
        hd = d_model // heads if heads else 0
        changes = dict(
            name=self.name + "-reduced",
            num_layers=min(self.num_layers, max_layers),
            d_model=d_model,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=hd,
            d_ff=min(self.d_ff, 4 * d_model) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, max_vocab),
            dtype="float32",
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
        )
        if self.num_experts:
            changes.update(
                num_experts=min(self.num_experts, max_experts),
                experts_per_token=min(self.experts_per_token,
                                      min(self.num_experts, max_experts)),
                num_shared_experts=min(self.num_shared_experts, 1),
                moe_d_ff=min(self.moe_d_ff, 2 * d_model),
                experts_held=0, expert_offset=0,
                first_k_dense=min(self.first_k_dense,
                                  changes["num_layers"] - 1))
        if self.use_mla:
            changes.update(kv_lora_rank=min(self.kv_lora_rank, 64),
                           q_lora_rank=0,
                           qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32,
                           head_dim=0)
        if self.ssm_variant:
            changes.update(ssm_state=min(self.ssm_state, 16),
                           ssm_head_dim=min(self.ssm_head_dim, 32))
        if self.encoder_layers:
            changes.update(encoder_layers=min(self.encoder_layers, max_layers),
                           encoder_seq=min(self.encoder_seq, 64))
        if self.num_image_tokens:
            changes.update(num_image_tokens=min(self.num_image_tokens, 16))
        if self.attn_every:
            changes.update(attn_every=min(self.attn_every, 2))
        return dataclasses.replace(self, **changes)


def param_count(cfg: ModelConfig) -> tuple[int, int]:
    """(total, active) parameter counts, analytic."""
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    H, Hkv, hd = cfg.attn_dims

    def attn_params() -> int:
        if cfg.use_mla:
            q_dim = H * (cfg.qk_nope_dim + cfg.qk_rope_dim)
            p = d * q_dim if not cfg.q_lora_rank else (
                d * cfg.q_lora_rank + cfg.q_lora_rank * q_dim)
            p += d * (cfg.kv_lora_rank + cfg.qk_rope_dim)     # down + k_rope
            p += cfg.kv_lora_rank * H * (cfg.qk_nope_dim + cfg.v_head_dim)
            p += H * cfg.v_head_dim * d                        # out proj
            return p
        p = d * H * hd + 2 * d * Hkv * hd + H * hd * d
        if cfg.qkv_bias:
            p += (H + 2 * Hkv) * hd
        return p

    def mlp_params(ff: int) -> int:
        gated = cfg.act in ("silu", "swiglu", "geglu")
        return d * ff * (3 if gated else 2)

    def ssm_params() -> int:
        din = cfg.ssm_d_inner
        N = cfg.ssm_state
        if cfg.ssm_variant == "mamba1":
            return (d * 2 * din + cfg.ssm_conv * din
                    + din * (cfg.dt_rank + 2 * N) + cfg.dt_rank * din
                    + din * N + din + din * d)
        heads = din // cfg.ssm_head_dim
        dxbc = din + 2 * cfg.ssm_groups * N
        return (d * (2 * din + 2 * cfg.ssm_groups * N + heads)
                + cfg.ssm_conv * dxbc + heads + heads + din * d)

    total = active = 0
    embed = V * d * (1 if cfg.tie_embeddings else 2)
    total += embed
    active += embed

    if cfg.family in ("dense", "vlm"):
        per = attn_params() + mlp_params(cfg.d_ff)
        total += L * per
        active += L * per
    elif cfg.family == "moe":
        attn = attn_params()
        expert = mlp_params(cfg.moe_d_ff)
        shared = cfg.num_shared_experts * expert
        router = d * cfg.num_experts
        dense = cfg.first_k_dense * (attn + mlp_params(cfg.d_ff))
        Lm = L - cfg.first_k_dense
        held = cfg.experts_held or cfg.num_experts
        total += dense + Lm * (attn + router + shared + held * expert)
        active += dense + Lm * (attn + router + shared
                                + cfg.experts_per_token * expert)
    elif cfg.family == "ssm":
        per = ssm_params()
        total += L * per
        active += L * per
    elif cfg.family == "hybrid":
        per = ssm_params()
        total += L * per
        active += L * per
        shared_attn = attn_params() + mlp_params(cfg.d_ff)
        total += shared_attn
        # shared block runs L//attn_every times but params counted once;
        # active-compute accounting handled in flops, not here
        active += shared_attn
    elif cfg.family == "encdec":
        enc = cfg.encoder_layers * (attn_params() + mlp_params(cfg.d_ff))
        dec = L * (2 * attn_params() + mlp_params(cfg.d_ff))
        total += enc + dec
        active += enc + dec
    return total, active

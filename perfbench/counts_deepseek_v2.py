"""Operations of DeepSeek-V2's layers (MLA, a leading dense layer, shared
and routed experts), computed from the configuration's shapes.

Yardsticks, as in ``counts.py``: model FLOPs of the mathematics (causal
attention, no recomputation), never what a kernel happens to do. The
routed experts count at the routing's expectation: each token applies
k experts of E, so k * E_held / E of the experts held here.

``cfg`` is a benchmark configuration dict (Hugging Face key names, as
``perfbench/configs/deepseek-v2-lite-ep8.json``).
"""

from __future__ import annotations

from perfbench.counts import attn_pairs_causal


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
            "ff": cfg["intermediate_size"],
            "ff_e": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"],
            "E": cfg["router_experts"], "E_held": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"],
            "L": cfg["num_hidden_layers"],
            "L_dense": cfg["first_k_dense_replace"], "V": cfg["vocab_size"]}


def mla_params(cfg: dict) -> int:
    """Weights of one MLA block (no q compression)."""
    m = dims(cfg)
    qk = m["nope"] + m["rope"]
    return (m["d"] * m["h"] * qk + m["d"] * (m["r"] + m["rope"])
            + m["r"] * m["h"] * (m["nope"] + m["v"]) + m["h"] * m["v"] * m["d"])


def expert_params(cfg: dict) -> int:
    """Weights of one routed expert (gated: gate, up, down)."""
    m = dims(cfg)
    return 3 * m["d"] * m["ff_e"]


def token_matmul_params(cfg: dict) -> float:
    """Matmul weights a token passes through, routed experts at their
    expectation (layers + LM head)."""
    m = dims(cfg)
    dense = mla_params(cfg) + 3 * m["d"] * m["ff"]
    moe = (mla_params(cfg) + m["d"] * m["E"]
           + m["shared"] * expert_params(cfg)
           + m["k"] * m["E_held"] / m["E"] * expert_params(cfg))
    return (m["L_dense"] * dense + (m["L"] - m["L_dense"]) * moe
            + m["d"] * m["V"])


def attn_flops(cfg: dict, pairs: int) -> int:
    """Forward FLOPs of q.k (nope + rope dims) and p.v over ``pairs``."""
    m = dims(cfg)
    return 2 * m["L"] * m["h"] * (m["nope"] + m["rope"] + m["v"]) * pairs


def train_step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one optimizer step over ``batch`` rows of ``seq``
    tokens: forward + backward (3x forward), no recomputation."""
    fwd = 2 * token_matmul_params(cfg) * batch * seq \
        + attn_flops(cfg, batch * attn_pairs_causal(seq))
    return int(3 * fwd)


def expert_rows_flops(cfg: dict, rows: float) -> float:
    """FLOPs of the held experts' grouped matmuls over ``rows`` (token,
    expert) rows, forward + backward: 2 x 3 x d x ff x rows x 3."""
    m = dims(cfg)
    return 2.0 * 3 * m["d"] * m["ff_e"] * rows * 3


def param_count(cfg: dict) -> int:
    """Every parameter held here, norm scales included."""
    m = dims(cfg)
    norms = m["L"] * (2 * m["d"] + m["r"]) + m["d"]
    dense = m["L_dense"] * (mla_params(cfg) + 3 * m["d"] * m["ff"])
    moe = (m["L"] - m["L_dense"]) * (
        mla_params(cfg) + m["d"] * m["E"]
        + (m["shared"] + m["E_held"]) * expert_params(cfg))
    return norms + dense + moe + 2 * m["V"] * m["d"]

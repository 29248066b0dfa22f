"""Operations and bytes that the algorithms need, computed from shapes.

These are yardsticks: they count what the mathematics of a step requires
(model FLOPs with causal attention, no recomputation; bytes an ideal pass
must move), never what a particular kernel happens to read. A later
change to the kernels does not change them.

``cfg`` is a benchmark configuration dict (Hugging Face key names).
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "ff": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"]}


def layer_matmul_params(cfg: dict) -> int:
    """Weights that one decoder layer multiplies each token by."""
    m = dims(cfg)
    attn = m["d"] * m["h"] * m["hd"] * 2 + m["d"] * m["kv"] * m["hd"] * 2
    mlp = 3 * m["d"] * m["ff"]            # gated: gate, up, down
    return attn + mlp


def head_params(cfg: dict) -> int:
    m = dims(cfg)
    return m["d"] * m["V"]


def matmul_params(cfg: dict) -> int:
    """All matmul weights a token passes through (layers + LM head)."""
    return dims(cfg)["L"] * layer_matmul_params(cfg) + head_params(cfg)


def param_count(cfg: dict) -> int:
    """Every parameter, embedding and norm scales included."""
    m = dims(cfg)
    norms = m["L"] * 2 * m["d"] + m["d"]
    if cfg.get("qk_norm"):
        norms += m["L"] * 2 * m["hd"]
    emb = m["V"] * m["d"]
    head = 0 if cfg.get("tie_word_embeddings") else m["V"] * m["d"]
    return m["L"] * layer_matmul_params(cfg) + norms + emb + head


def attn_pairs_causal(n: int) -> int:
    """(query, key) pairs of ``n`` queries attending causally to every
    position up to their own."""
    return n * (n + 1) // 2


def attn_flops(cfg: dict, pairs: int) -> int:
    """Forward FLOPs of q.k and p.v over ``pairs`` (query, key) pairs."""
    m = dims(cfg)
    return 4 * m["L"] * m["h"] * m["hd"] * pairs


def train_step_flops(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one optimizer step over ``batch`` rows of ``seq``
    tokens: forward + backward (3x forward) of every matmul and of causal
    attention; recomputation under remat is not counted."""
    fwd = 2 * matmul_params(cfg) * batch * seq \
        + attn_flops(cfg, batch * attn_pairs_causal(seq))
    return 3 * fwd


def lars_update_bytes(n_elems: int, param_elems: int,
                      param_bytes: int = 2) -> int:
    """Bytes of the two passes momentum LARS needs per step, for a packed
    f32 layout of ``n_elems`` elements (master weights, gradient and
    momentum each one buffer of that size):

    * norms pass: read master weights and gradient (2 x 4 B);
    * apply pass: read gradient, momentum and master weights (3 x 4 B),
      write momentum and master weights (2 x 4 B), and write the
      ``param_elems`` compute-dtype parameters (``param_bytes`` each).
    """
    return 4 * n_elems * (2 + 3 + 2) + param_bytes * param_elems


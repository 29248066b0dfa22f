"""The program under test, as the benchmark builds it: a configuration
file (Hugging Face key names) turned into the program's ``ModelConfig``,
and the parameter tree the program expects."""

from __future__ import annotations

import jax

from perfbench.counts import dims


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    if cfg.get("attention_bias"):
        raise ValueError("attention biases are not part of this decoder")
    m = dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=m["L"], d_model=m["d"],
        num_heads=m["h"], num_kv_heads=m["kv"], head_dim=m["hd"],
        d_ff=m["ff"], vocab_size=m["V"], qk_norm=bool(cfg.get("qk_norm")),
        rope_theta=float(cfg["rope_theta"]), act=cfg["hidden_act"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        source=cfg["source"])


def check_tree(model, params) -> None:
    """The benchmark's weights have the program's tree and shapes."""
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.eval_shape(lambda: params)
    ws, gs = (jax.tree_util.tree_structure(t) for t in (want, got))
    if ws != gs:
        raise ValueError(f"weight tree differs from the program's:\n{gs}\n"
                         f"vs\n{ws}")
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        if a.shape != b.shape:
            raise ValueError(f"weight shape {b.shape} vs program {a.shape}")

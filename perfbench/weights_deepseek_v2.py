"""Weights of the DeepSeek-V2 cut model made from ``--seed``, on the
device, in one jitted call.

The layout is the program's: an embedding, the leading dense layers and
the MoE layers each stacked on a leading layer axis, the routed experts
held here stacked (layer, expert, ...), a final norm and an output head.
Keys are those of ``weights.py`` (``seed_key``, ``WEIGHTS``, ``BATCHES``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.counts_deepseek_v2 import dims
from perfbench.weights import _normal, _scale


def _mla(m: dict, ks, dtype) -> dict:
    h, qk = m["h"], m["nope"] + m["rope"]
    return {"wq": _normal(ks[0], (m["d"], h * qk), m["d"] ** -0.5, dtype),
            "kv_down": _normal(ks[1], (m["d"], m["r"] + m["rope"]),
                               m["d"] ** -0.5, dtype),
            "kv_norm": _scale(ks[2], m["r"]),
            "k_up": _normal(ks[3], (m["r"], h * m["nope"]), m["r"] ** -0.5,
                            dtype),
            "v_up": _normal(ks[4], (m["r"], h * m["v"]), m["r"] ** -0.5,
                            dtype),
            "wo": _normal(ks[5], (h * m["v"], m["d"]), (h * m["v"]) ** -0.5,
                          dtype)}


def _mlp(d: int, ff: int, ks, dtype, lead=()) -> dict:
    return {"wi": _normal(ks[0], lead + (d, ff), d ** -0.5, dtype),
            "wo": _normal(ks[1], lead + (ff, d), ff ** -0.5, dtype),
            "wg": _normal(ks[2], lead + (d, ff), d ** -0.5, dtype)}


def layer_params(cfg: dict, key, dense: bool, dtype=jnp.bfloat16) -> dict:
    m = dims(cfg)
    ks = jax.random.split(key, 16)
    p = {"ln1": {"scale": _scale(ks[0], m["d"])},
         "ln2": {"scale": _scale(ks[1], m["d"])},
         "attn": _mla(m, ks[2:8], dtype)}
    if dense:
        p["mlp"] = _mlp(m["d"], m["ff"], ks[8:11], dtype)
        return p
    moe = _mlp(m["d"], m["ff_e"], ks[8:11], dtype, lead=(m["E_held"],))
    moe["router"] = _normal(ks[11], (m["d"], m["E"]), m["d"] ** -0.5, dtype)
    moe["shared"] = _mlp(m["d"], m["shared"] * m["ff_e"], ks[12:15], dtype)
    p["moe"] = moe
    return p


def make_params(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """The cut model from ``key`` = ``seed_key(seed, WEIGHTS)``; call
    under ``jax.jit``."""
    m = dims(cfg)
    k_emb, k_layers, k_norm, k_out = jax.random.split(key, 4)

    def stack(lo, hi, dense):
        keys = jax.vmap(lambda i: jax.random.fold_in(k_layers, i))(
            jnp.arange(lo, hi))
        return jax.vmap(lambda k: layer_params(cfg, k, dense, dtype))(keys)

    # unit-variance embeddings: at 0.02 a token's own row is 2% of the
    # residual stream after the dense layer, beside a direction that the
    # random layers' causal attention gives every late token alike; the
    # routers then follow that shared direction, which experts it favours
    # changes with the seed, and so did the rows the held experts compute
    # (by 6% from seed to seed, against 0.6% here)
    return {"embed": _normal(k_emb, (m["V"], m["d"]), 1.0, dtype),
            "dense_layers": stack(0, m["L_dense"], True),
            "layers": stack(m["L_dense"], m["L"], False),
            "final_norm": {"scale": _scale(k_norm, m["d"])},
            "unembed": _normal(k_out, (m["d"], m["V"]), m["d"] ** -0.5,
                               dtype)}

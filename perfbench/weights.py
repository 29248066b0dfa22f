"""Weights and inputs made from ``--seed``, on the device, in one jitted
call each.

The layout is the dense GQA decoder's: an embedding, a stack of layers
(leading axis = layer), a final norm and, when untied, an output head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.counts import dims


def seed_key(seed: int, tag: int = 0):
    """A key from any non-negative seed (64 bits are kept), per ``tag``."""
    k = jax.random.key(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, tag)


WEIGHTS, BATCHES = 1, 2        # seed_key tags


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _scale(key, n):
    """Norm scales near one, so a norm applied wrongly shows."""
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def layer_params(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    m = dims(cfg)
    d, h, kv, hd, ff = m["d"], m["h"], m["kv"], m["hd"], m["ff"]
    ks = jax.random.split(key, 10)
    attn = {"wq": _normal(ks[0], (d, h * hd), d ** -0.5, dtype),
            "wk": _normal(ks[1], (d, kv * hd), d ** -0.5, dtype),
            "wv": _normal(ks[2], (d, kv * hd), d ** -0.5, dtype),
            "wo": _normal(ks[3], (h * hd, d), (h * hd) ** -0.5, dtype)}
    if cfg.get("qk_norm"):
        attn["q_norm"] = _scale(ks[4], hd)
        attn["k_norm"] = _scale(ks[5], hd)
    mlp = {"wi": _normal(ks[6], (d, ff), d ** -0.5, dtype),
           "wg": _normal(ks[7], (d, ff), d ** -0.5, dtype),
           "wo": _normal(ks[8], (ff, d), ff ** -0.5, dtype)}
    k1, k2 = jax.random.split(ks[9])
    return {"ln1": {"scale": _scale(k1, d)}, "ln2": {"scale": _scale(k2, d)},
            "attn": attn, "mlp": mlp}


def make_params(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """The whole model (layers stacked) from ``key`` =
    ``seed_key(seed, WEIGHTS)``; call under ``jax.jit``."""
    m = dims(cfg)
    k_emb, k_layers, k_norm, k_out = jax.random.split(key, 4)
    layer_keys = jax.vmap(lambda i: jax.random.fold_in(k_layers, i))(
        jnp.arange(m["L"]))
    p = {"embed": _normal(k_emb, (m["V"], m["d"]), 0.02, dtype),
         "layers": jax.vmap(lambda k: layer_params(cfg, k, dtype))(
             layer_keys),
         "final_norm": {"scale": _scale(k_norm, m["d"])}}
    if not cfg.get("tie_word_embeddings"):
        p["unembed"] = _normal(k_out, (m["d"], m["V"]), m["d"] ** -0.5, dtype)
    return p


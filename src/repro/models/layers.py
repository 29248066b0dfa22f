"""Shared primitive layers: init helpers, norms, rotary embeddings,
activations. Pure functions over plain dict params.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------- initizers

def dense_init(key, d_in: int, d_out: int, dtype, *, scale: float = 1.0
               ) -> jnp.ndarray:
    """Fan-in (LeCun/He-style) normal init."""
    std = scale / (d_in ** 0.5)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ----------------------------------------------------------------- norms

def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6
            ) -> jnp.ndarray:
    """Stats in f32; the (B,S,d)-shaped APPLY stays in x.dtype.

    A full f32 copy of x here is poison at scale: XLA hoists the
    bf16->f32 convert into the layer-scan's saved-carry stack, storing
    all L residual carries in f32 (2x peak memory; §Perf qwen2
    iteration 3). Only the per-row variance is computed in f32; the
    elementwise scaling multiplies bf16 by a broadcast (.., 1) factor.
    """
    # square in x.dtype, ACCUMULATE in f32 (dtype=): no full-tensor
    # convert(x) ever exists, so XLA cannot hoist one out of the
    # backward layer loop as a whole-stack f32 copy.
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                   dtype=jnp.float32)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
              eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (x - mu.astype(x.dtype)) * jax.lax.rsqrt(var + eps).astype(x.dtype)
    out = out * scale.astype(x.dtype) + bias.astype(x.dtype)
    return out


def apply_norm(cfg, x, p):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rmsnorm(x, p["scale"], cfg.norm_eps)


def init_norm(cfg, d: int):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


# ----------------------------------------------------------------- rotary

def rope_freqs(head_dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Inverse frequencies of the rotary pairs, (head_dim/2,). With a
    :class:`repro.configs.base.YaRN` ``scaling``: the original frequency
    below the correction band's low end (fast rotations), the one
    stretched by ``factor`` above its high end, a linear blend between
    (DeepSeek-V2's ``yarn_find_correction_range`` and ramp)."""
    base = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))
    if scaling is None:
        return base

    def corr_dim(rotations):
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(corr_dim(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp            # 1 where the original frequency is kept
    return base / scaling.factor * (1.0 - keep) + base * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor m = 0.1 mscale ln(factor) + 1 (1 when the
    context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_cos_scale(scaling) -> float:
    """What cos and sin are multiplied by: m(mscale) / m(mscale_all_dim)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling=None) -> jnp.ndarray:
    """x: (..., S, H, D) rotated pairwise-half style; positions: (..., S).
    ``scaling``: YaRN frequencies and cos/sin factor (see
    :func:`rope_freqs`)."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta, scaling)               # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., :, None, :]              # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., :, None, :]
    m = rope_cos_scale(scaling)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- act fns

def gelu(x):
    return jax.nn.gelu(x, approximate=True)


ACTS = {
    "silu": jax.nn.silu,
    "gelu": gelu,
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),  # nemotron/minitron
}


def gated(cfg) -> bool:
    return cfg.act in ("silu", "swiglu", "geglu")


def act_fn(cfg):
    name = {"swiglu": "silu", "geglu": "gelu"}.get(cfg.act, cfg.act)
    return ACTS[name]

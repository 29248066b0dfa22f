"""ShapeDtypeStruct input stand-ins + config adaptation per input shape.

``input_specs`` builds every model input for a (arch, shape) pair as
ShapeDtypeStructs — weak-type-correct, shardable, zero allocation — which
is what the dry-run lowers against. ``adapt_config`` applies the
shape-dependent config carve-outs from DESIGN.md §5:

  * long_500k on attention-cache archs -> sliding window 8192 (the
    sub-quadratic variant; MLA is exempt — its compressed latent cache
    fits at 524k natively, which is the point of MLA).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import InputShape, LONG_CONTEXT_WINDOW

SDS = jax.ShapeDtypeStruct


def adapt_config(cfg, shape: InputShape):
    changes: dict[str, Any] = {}
    if (shape.mode == "decode" and shape.seq_len > LONG_CONTEXT_WINDOW
            and not cfg.use_mla and not cfg.is_attention_free
            and cfg.family != "hybrid"
            and cfg.sliding_window == 0):
        changes["sliding_window"] = LONG_CONTEXT_WINDOW
    if (cfg.family == "hybrid" and shape.mode == "decode"
            and shape.seq_len > LONG_CONTEXT_WINDOW
            and cfg.sliding_window == 0):
        # hybrid shared-attention KV window for long-context decode
        changes["sliding_window"] = LONG_CONTEXT_WINDOW
    if changes:
        return dataclasses.replace(cfg, **changes)
    return cfg


def train_batch_specs(cfg, shape: InputShape) -> dict[str, SDS]:
    """Also used for prefill (same inputs, different step)."""
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    if cfg.family == "cnn":
        return {"x": SDS((B, 28, 28, 1), jnp.float32),
                "y": SDS((B,), jnp.int32)}
    specs = {"tokens": SDS((B, S), jnp.int32)}
    if cfg.family == "encdec":
        specs["frames"] = SDS((B, cfg.encoder_seq, cfg.d_model), dt)
    if cfg.family == "vlm":
        specs["image_embeddings"] = SDS((B, cfg.num_image_tokens,
                                         cfg.d_model), dt)
    return specs


def decode_token_specs(shape: InputShape) -> SDS:
    return SDS((shape.global_batch, 1), jnp.int32)


def cache_shapes(model, shape: InputShape):
    """Decode-cache ShapeDtypeStructs via eval_shape (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    return jax.eval_shape(lambda: model.init_cache(B, S))


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.key(0))

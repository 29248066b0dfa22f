"""chunked_lm_loss == lm_loss (values and gradients), incl. VLM slicing
and padded-tail chunks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.train.losses import chunked_lm_loss, lm_loss
from repro.train.step import _forward_and_loss


@pytest.mark.parametrize("chunk", [4, 5, 16])
def test_chunked_equals_full(chunk):
    rng = np.random.default_rng(0)
    B, S, d, V = 2, 15, 8, 32
    hidden = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, V)), jnp.float32) * 0.3
    tokens = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)

    def full(h, w):
        return lm_loss((h @ w).astype(jnp.float32), tokens)

    def chunked(h, w):
        return chunked_lm_loss(h, w, tokens, chunk=chunk)

    lf, gf = jax.value_and_grad(full, argnums=(0, 1))(hidden, w)
    lc, gc = jax.value_and_grad(chunked, argnums=(0, 1))(hidden, w)
    np.testing.assert_allclose(float(lc), float(lf), rtol=1e-6)
    for a, b in zip(gc, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("arch", ["smollm-135m", "paligemma-3b"])
def test_step_level_chunked_loss_matches(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)),
                                   jnp.int32)}
    if cfg.family == "vlm":
        batch["image_embeddings"] = jnp.asarray(
            rng.normal(size=(2, cfg.num_image_tokens, cfg.d_model)) * 0.1,
            jnp.float32)

    loss_full, _ = _forward_and_loss(model, cfg, params, batch)
    cfg_c = dataclasses.replace(cfg, loss_chunk=4)
    model_c = build_model(cfg_c)
    loss_chunked, _ = _forward_and_loss(model_c, cfg_c, params, batch)
    np.testing.assert_allclose(float(loss_chunked), float(loss_full),
                               rtol=1e-5, atol=1e-6)

"""Jitted train/eval step factories for every model family.

One factory handles all families by dispatching on the batch contents the
model forward needs:

  cnn      {"x": images (B,28,28,1), "y": labels (B,)}
  lm       {"tokens": (B, S)}              loss: predict [1:] from [:-1]
  vlm      {"tokens", "image_embeddings"}  prefix-LM loss mask
  encdec   {"tokens", "frames"}            teacher-forced decoder loss

The returned step is a pure function (TrainState, batch) -> (TrainState,
metrics) suitable for `jax.jit` with shardings. The LARS/LAMB `stacked`
marker is baked into the closure (static per arch); when the TrainState
was created on the flat-packed substrate (create_train_state default),
the opt state carries the matching PackedLayout and the update runs the
whole-pytree packed engine — the marker passed here is then only a
consistency check against the init-time layout.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import phases
from repro.train.losses import (chunked_lm_loss, classification_loss,
                                lm_loss)
from repro.train.metrics import accuracy
from repro.train.state import TrainState

Pytree = Any


def _forward_and_loss(model, cfg, params, batch):
    """(loss, (logits, aux)) for any family."""
    if cfg.family == "cnn":
        logits, aux = model.forward(params, batch["x"])
        loss = classification_loss(logits, batch["y"])
        return loss, (logits, aux)
    if cfg.family == "encdec":
        logits, aux = model.forward(params, batch["tokens"],
                                    frames=batch["frames"])
        loss = lm_loss(logits, batch["tokens"])
        return loss + aux.get("aux_loss", 0.0), (logits, aux)
    if cfg.family == "vlm":
        img = batch["image_embeddings"]
        n_img = img.shape[1]
        if getattr(cfg, "loss_chunk", 0):
            hidden, aux = model.forward(params, batch["tokens"],
                                        image_embeddings=img,
                                        return_hidden=True)
            loss = chunked_lm_loss(hidden[:, n_img:],
                                   model.unembed_matrix(params),
                                   batch["tokens"], chunk=cfg.loss_chunk)
            return loss + aux.get("aux_loss", 0.0), (None, aux)
        logits, aux = model.forward(params, batch["tokens"],
                                    image_embeddings=img)
        # logits cover [img prefix | text]; loss only on text targets
        text_logits = logits[:, n_img:]
        loss = lm_loss(text_logits, batch["tokens"])
        return loss + aux.get("aux_loss", 0.0), (text_logits, aux)
    if getattr(cfg, "loss_chunk", 0):
        hidden, aux = model.forward(params, batch["tokens"],
                                    return_hidden=True)
        with jax.named_scope(phases.HEAD_LOSS):
            loss = chunked_lm_loss(hidden, model.unembed_matrix(params),
                                   batch["tokens"], chunk=cfg.loss_chunk)
        return loss + aux.get("aux_loss", 0.0), (None, aux)
    logits, aux = model.forward(params, batch["tokens"])
    with jax.named_scope(phases.HEAD_LOSS):
        loss = lm_loss(logits, batch["tokens"])
    return loss + aux.get("aux_loss", 0.0), (logits, aux)


def make_train_step(model, optimizer, cfg=None) -> Callable:
    """(TrainState, batch) -> (TrainState, metrics dict)."""
    cfg = cfg if cfg is not None else model.cfg
    # stacked marker depends only on the param STRUCTURE -> build it from
    # an eval_shape trace so the factory never allocates real params.
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    stacked = model.stacked_marker(shapes)

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        def loss_fn(params):
            return _forward_and_loss(model, cfg, params, batch)

        (loss, (_, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        new_params, new_opt = optimizer.update(
            grads, state.opt_state, state.params, stacked=stacked)
        metrics = {"loss": loss,
                   "aux_loss": aux.get("aux_loss", jnp.zeros((), jnp.float32)),
                   "step": new_opt.step}
        return TrainState(new_params, new_opt), metrics

    return step


def _param_float_dtype(params):
    for leaf in jax.tree_util.tree_leaves(params):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            return jnp.asarray(leaf).dtype
    return jnp.float32


def make_eval_step(model, cfg=None) -> Callable:
    """(params, batch) -> metrics {loss, accuracy}.

    Accuracy alignment (pinned by tests/test_models.py): the model's
    logit at sequence position t predicts the token at position t+1, so
    ``logits[:, :-1]`` is scored against ``tokens[:, 1:]``. For the VLM
    family ``_forward_and_loss`` already slices the bidirectional image
    prefix off the logits, which re-aligns them with the text tokens —
    the SAME next-token shift then applies (prefix length must not be
    shifted into the targets). The CNN family scores the class head
    directly against labels.
    """
    cfg = cfg if cfg is not None else model.cfg
    # Eval must materialize logits even for configs whose TRAIN loss runs
    # the memory-saving chunked path (which returns hidden states only —
    # accuracy over `None` logits was a crash, not a metric).
    if getattr(cfg, "loss_chunk", 0):
        cfg = dataclasses.replace(cfg, loss_chunk=0)

    def step(params, batch) -> dict:
        # match batch floats to the param compute dtype so evaluating a
        # bf16-policy state with f32 host data works (lax.conv and
        # friends require matching element types)
        dt = _param_float_dtype(params)
        batch = {k: v.astype(dt)
                 if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
                 else v for k, v in batch.items()}
        loss, (logits, _) = _forward_and_loss(model, cfg, params, batch)
        if cfg.family == "cnn":
            acc = accuracy(logits, batch["y"])
        else:
            acc = accuracy(logits[:, :-1], batch["tokens"][:, 1:])
        return {"loss": loss, "accuracy": acc}

    return step


# Convenience aliases used by examples (same factories, LM batch layout).
make_lm_train_step = make_train_step
make_lm_eval_step = make_eval_step

"""Per-architecture smoke + consistency tests (reduced configs, CPU).

For every assigned arch: forward shapes/no-NaN, one LARS train step, and
prefill+decode vs teacher-forced forward agreement (validates KV/SSM
cache semantics, ring buffers, MLA absorbed decode, hybrid shared
attention — everything the serving path relies on).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.core import lars
from repro.models import build_model
from repro.train import TrainState, create_train_state, make_train_step

LM_ARCHS = [n for n in ARCHS if n != "lenet-mnist"]

T = 12  # prompt length for consistency tests


def _batch(cfg, B=2, S=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(B, S)), jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.encoder_seq, cfg.d_model)), jnp.float32)
    if cfg.family == "vlm":
        batch["image_embeddings"] = jnp.asarray(
            rng.normal(size=(B, cfg.num_image_tokens, cfg.d_model)) * 0.1,
            jnp.float32)
    return batch


def _fwd_kwargs(cfg, batch):
    kw = {}
    if cfg.family == "encdec":
        kw["frames"] = batch["frames"]
    if cfg.family == "vlm":
        kw["image_embeddings"] = batch["image_embeddings"]
    return kw


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_forward_smoke(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _batch(cfg)
    logits, aux = model.forward(params, batch["tokens"],
                                **_fwd_kwargs(cfg, batch))
    S_out = batch["tokens"].shape[1] + (cfg.num_image_tokens or 0)
    assert logits.shape == (2, S_out, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_train_step_lars(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    opt = lars(learning_rate=0.1)
    state = create_train_state(model, opt, jax.random.key(1))
    step = jax.jit(make_train_step(model, opt))
    batch = _batch(cfg)
    new_state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(new_state.opt_state.step) == 1
    # params actually moved
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        state.params, new_state.params)
    assert max(jax.tree_util.tree_leaves(diffs)) > 0
    # loss is finite on a second step too (momentum path)
    _, m2 = step(new_state, batch)
    assert bool(jnp.isfinite(m2["loss"]))


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_prefill_decode_matches_forward(arch):
    """decode_step after prefill(T) must reproduce teacher-forced logits."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(2))
    batch = _batch(cfg, S=T + 2, seed=3)
    toks = batch["tokens"]
    kw = _fwd_kwargs(cfg, batch)

    full_logits, _ = model.forward(params, toks, **kw)
    n_img = cfg.num_image_tokens if cfg.family == "vlm" else 0

    pre_kw = dict(kw)
    cap = n_img + T + 2   # cache must cover the image prefix positions too
    logits_T, cache = model.prefill(params, toks[:, :T], cache_len=cap,
                                    **pre_kw)
    # prefill's last-token logits == forward logits at position T-1
    ref_T = full_logits[:, n_img + T - 1]
    np.testing.assert_allclose(np.asarray(logits_T), np.asarray(ref_T),
                               rtol=2e-3, atol=2e-3)

    # one decode step with token T reproduces forward logits at position T
    step_logits, cache = model.decode_step(params, cache, toks[:, T:T + 1])
    ref_next = full_logits[:, n_img + T]
    np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                               np.asarray(ref_next), rtol=2e-3, atol=2e-3)

    # and a second step (exercises cache-advance paths)
    step_logits2, _ = model.decode_step(params, cache, toks[:, T + 1:T + 2])
    ref_next2 = full_logits[:, n_img + T + 1]
    np.testing.assert_allclose(np.asarray(step_logits2[:, 0]),
                               np.asarray(ref_next2), rtol=2e-3, atol=2e-3)


def test_sliding_window_ring_decode():
    """Windowed decode through a ring buffer == windowed forward."""
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              sliding_window=8)
    model = build_model(cfg)
    params = model.init(jax.random.key(4))
    S = 20  # > window so the ring wraps
    toks = jnp.asarray(
        np.random.default_rng(5).integers(0, cfg.vocab_size, (2, S)),
        jnp.int32)
    full_logits, _ = model.forward(params, toks)
    logits_T, cache = model.prefill(params, toks[:, :S - 1],
                                    cache_len=S + 4)
    np.testing.assert_allclose(np.asarray(logits_T),
                               np.asarray(full_logits[:, S - 2]),
                               rtol=2e-3, atol=2e-3)
    step_logits, _ = model.decode_step(params, cache, toks[:, S - 1:S])
    np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                               np.asarray(full_logits[:, S - 1]),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("variant", ["mamba1", "mamba2"])
def test_ssm_chunk_invariance(variant):
    """Streaming chunked scan must be chunk-size invariant."""
    from repro.models import ssm as SSM
    arch = "falcon-mamba-7b" if variant == "mamba1" else "zamba2-7b"
    cfg = get_config(arch).reduced()
    key = jax.random.key(6)
    init = SSM.init_mamba1 if variant == "mamba1" else SSM.init_mamba2
    fwd = SSM.mamba1_forward if variant == "mamba1" else SSM.mamba2_forward
    p = init(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(7), (2, 24, cfg.d_model),
                          jnp.float32) * 0.3
    y_ref, _ = fwd(cfg, p, x, chunk=24)
    for c in (4, 6, 7):   # 7 exercises the padded-tail path
        y, _ = fwd(cfg, p, x, chunk=c)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)


def test_mamba2_large_dt_no_nan():
    """Regression: the SSD intra-chunk gate must mask BEFORE exp — with a
    large dt the s>t exponent overflows to inf and inf*0 = NaN."""
    from repro.models import ssm as SSM
    cfg = get_config("zamba2-7b").reduced()
    p = SSM.init_mamba2(jax.random.key(0), cfg, jnp.float32)
    p = dict(p, dt_bias=jnp.full_like(p["dt_bias"], 60.0))  # huge dt
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model)) * 0.3

    def loss(p):
        y, _ = SSM.mamba2_forward(cfg, p, x, chunk=16)
        return jnp.sum(jnp.square(y))

    val, grads = jax.value_and_grad(loss)(p)
    assert bool(jnp.isfinite(val))
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))


def test_zamba2_streamed_training_stays_finite():
    """Regression: 4 LARS steps on fresh batches (the exact NaN repro)."""
    from repro.core import lars
    from repro.train import create_train_state, make_train_step
    cfg = get_config("zamba2-7b").reduced()
    model = build_model(cfg)
    opt = lars(0.01)
    state = create_train_state(model, opt, jax.random.key(0))
    step = jax.jit(make_train_step(model, opt, cfg))
    rng = np.random.default_rng(7)
    for i in range(4):
        b = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)}
        state, m = step(state, b)
        assert bool(jnp.isfinite(m["loss"])), f"NaN at step {i}"


def test_attention_kv_chunk_invariance():
    from repro.models.attention import attention_core
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(2, 16, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    pos = jnp.arange(16)
    ref = attention_core(q, k, v, q_positions=pos, kv_chunk=4)
    # kv single-block == chunked
    out = attention_core(q, k, v, q_positions=pos, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------- eval-step alignment

# one representative reduced arch per family
_EVAL_FAMILY_ARCHS = {"dense": "smollm-135m", "vlm": "paligemma-3b",
                      "encdec": "whisper-base"}


@pytest.mark.parametrize("family,arch", sorted(_EVAL_FAMILY_ARCHS.items()))
def test_eval_step_accuracy_alignment(family, arch):
    """Pin make_eval_step's accuracy alignment per family: the logit at
    position t scores the token at t+1; for the VLM family the image
    prefix is sliced off the logits FIRST (so the prefix length never
    shifts into the targets), then the same next-token shift applies."""
    from repro.train import make_eval_step
    from repro.train.metrics import accuracy

    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(20))
    batch = _batch(cfg, seed=21)
    m = make_eval_step(model, cfg)(params, batch)

    full_logits, _ = model.forward(params, batch["tokens"],
                                   **_fwd_kwargs(cfg, batch))
    n_img = cfg.num_image_tokens if cfg.family == "vlm" else 0
    # reference alignment built from the FULL (prefix-inclusive) logits:
    # predictions for tokens[:, 1:] live at full positions
    # [n_img, n_img + S - 1)
    expected = accuracy(full_logits[:, n_img:-1], batch["tokens"][:, 1:])
    np.testing.assert_allclose(float(m["accuracy"]), float(expected),
                               rtol=1e-6)
    assert bool(jnp.isfinite(m["loss"]))


def test_eval_step_cnn_scores_class_head():
    from repro.train import make_eval_step
    cfg = get_config("lenet-mnist")
    model = build_model(cfg)
    params = model.init(jax.random.key(22))
    rng = np.random.default_rng(23)
    x = jnp.asarray(rng.random((16, 28, 28, 1)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, 16), jnp.int32)
    m = make_eval_step(model, cfg)(params, {"x": x, "y": y})
    logits, _ = model.forward(params, x)
    expected = float(np.mean(np.argmax(np.asarray(logits), -1)
                             == np.asarray(y)))
    np.testing.assert_allclose(float(m["accuracy"]), expected, rtol=1e-6)


def test_eval_step_materializes_logits_for_chunked_loss_configs():
    """A config whose TRAIN loss runs the chunked (hidden-only) path must
    still produce real logits — and the identical accuracy — in eval."""
    from repro.train import make_eval_step
    cfg = get_config("smollm-135m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(24))
    batch = _batch(cfg, seed=25)
    ref = make_eval_step(model, cfg)(params, batch)
    chunked_cfg = dataclasses.replace(cfg, loss_chunk=4)
    m = make_eval_step(build_model(chunked_cfg), chunked_cfg)(params, batch)
    np.testing.assert_allclose(float(m["accuracy"]), float(ref["accuracy"]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(m["loss"]), float(ref["loss"]),
                               rtol=1e-5)


def test_eval_step_casts_batch_to_bf16_params():
    """Evaluating a bf16-precision state with f32 host batches must cast
    rather than crash (lax.conv requires matching element types)."""
    from repro.train import make_eval_step
    cfg = get_config("lenet-mnist")
    model = build_model(cfg)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16), model.init(jax.random.key(26)))
    rng = np.random.default_rng(27)
    m = make_eval_step(model, cfg)(
        params, {"x": jnp.asarray(rng.random((8, 28, 28, 1)), jnp.float32),
                 "y": jnp.asarray(rng.integers(0, 10, 8), jnp.int32)})
    assert bool(jnp.isfinite(m["loss"]))
    assert 0.0 <= float(m["accuracy"]) <= 1.0


def test_lenet_train_step():
    cfg = get_config("lenet-mnist")
    model = build_model(cfg)
    opt = lars(learning_rate=0.05)
    state = create_train_state(model, opt, jax.random.key(11))
    step = jax.jit(make_train_step(model, opt))
    rng = np.random.default_rng(12)
    batch = {"x": jnp.asarray(rng.random((8, 28, 28, 1)), jnp.float32),
             "y": jnp.asarray(rng.integers(0, 10, 8), jnp.int32)}
    losses = []
    for _ in range(5):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]   # memorizes a fixed batch

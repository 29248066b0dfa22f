"""The plain float32 reference against the program at a tiny size, on
both decoder paths (smollm-135m's tied head without q/k norm; an untied
head with q/k norm, as Qwen3 has), and the seeded weights."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference as ref
from perfbench import weights as W
from perfbench.program import model_config

CONFIGS = Path(__file__).parents[1] / "configs"
SHRINK = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
              vocab_size=128, torch_dtype="float32")


QK_UNTIED = dict(qk_norm=True, tie_word_embeddings=False, rope_theta=1e6,
                 rms_norm_eps=1e-6)


def tiny(**extra):
    cfg = json.loads((CONFIGS / "smollm-135m.json").read_text())
    return dict(cfg, **SHRINK, **extra)


@pytest.fixture(params=[{}, QK_UNTIED], ids=["tied", "qk-untied"])
def case(request):
    from repro.models import build_model
    cfg = tiny(**request.param)
    model = build_model(model_config(cfg))
    params = jax.jit(lambda k: W.make_params(cfg, k, jnp.float32))(
        W.seed_key(2 ** 31 + 5, W.WEIGHTS))
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 0,
                                cfg["vocab_size"])
    return cfg, model, params, tokens


def test_forward_logits_match(case):
    cfg, model, params, tokens = case
    got = model.forward(params, tokens)[0]
    with jax.default_matmul_precision("highest"):
        want = ref.mm(ref.hidden(cfg, params, tokens),
                      ref.head_matrix(params))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_loss_and_gradients_match(case):
    from repro.train.step import _forward_and_loss
    cfg, model, params, tokens = case
    pcfg = model_config(cfg)

    def prog(p):
        return _forward_and_loss(model, pcfg, p, {"tokens": tokens})[0]

    loss, grads = jax.value_and_grad(prog)(params)
    r = ref.TrainReference(cfg, {"lr": 1.0, "momentum": 0.9,
                                 "weight_decay": 1e-4,
                                 "trust_coefficient": 1e-3}, 1)
    rloss, rgrads = r.loss_and_grads(params, tokens)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(rgrads)):
        np.testing.assert_allclose(a, b, rtol=2e-3,
                                   atol=2e-3 * float(jnp.max(jnp.abs(b))))


def test_lars_matches_the_program_over_two_steps(case):
    from repro.core import get_optimizer
    cfg, model, params, _ = case
    hp = {"momentum": 0.9, "weight_decay": 1e-4, "trust_coefficient": 1e-3}
    opt = get_optimizer("lars", learning_rate=2.0, use_pallas=False, **hp)
    state = opt.init(params, stacked=model.stacked_marker(params))
    rp, rm = params, jax.tree_util.tree_map(jnp.zeros_like, params)
    p = params
    update = jax.jit(opt.update)
    rstep = jax.jit(lambda w, g, m: ref.lars_step(w, g, m, lr=2.0, **hp))

    @jax.jit
    def grads_like(key, p):
        leaves, tree = jax.tree_util.tree_flatten(p)
        ks = jax.random.split(key, len(leaves))
        return tree.unflatten([jax.random.normal(k, x.shape) * 1e-2
                               for k, x in zip(ks, leaves)])

    for i in range(2):
        g = grads_like(jax.random.key(10 + i), p)
        p, state = update(g, state, p)
        rp, rm = rstep(rp, g, rm)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(rp)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_seeds_past_32_bits_give_other_weights():
    cfg = tiny()
    make = jax.jit(lambda k: W.make_params(cfg, k)["embed"])
    a = make(W.seed_key(5, W.WEIGHTS))
    b = make(W.seed_key(5 + 2 ** 32, W.WEIGHTS))
    assert not np.array_equal(a, b)


"""DeepSeek-V2-Lite's chip share at a tiny size on the CPU, against the
plain float32 reference of ``perfbench/reference_deepseek_v2.py``: MLA
with YaRN rope, a leading dense layer, shared and routed experts held
in shares of the expert-parallel group, the balance loss, and LARS with
a trust ratio per (layer, expert)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench import reference_deepseek_v2 as ref
from perfbench import weights_deepseek_v2 as W
from repro.configs import get_config
from repro.core import lars, packing
from repro.models import build_model, layers as L, mla, moe

# d 64, 4 heads, nope 16 / rope 8 / v 16, kv_lora 32, 8 experts of which
# 4 held, top-2, 1 dense + 2 MoE layers, vocab 128
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=32, moe_intermediate_size=32,
            router_experts=8, n_routed_experts=4, expert_offset=0,
            num_experts_per_tok=2, n_shared_experts=1,
            first_k_dense_replace=1, num_hidden_layers=3, vocab_size=128,
            torch_dtype="float32")
KIND = harness.kind("train_moe")


def _cfg(**kw):
    bench = harness.load_benchmark()
    cfg = harness.config(bench, "deepseek-v2-lite-ep8")
    return {**cfg, **TINY, **kw}


def _setup(seed=0, **kw):
    cfg = _cfg(**kw)
    pcfg = KIND.model_config(cfg)
    params = jax.jit(lambda k: W.make_params(cfg, k, jnp.float32))(
        jax.random.key(seed))
    tokens = jax.random.randint(jax.random.key(seed + 1), (2, 16), 0,
                                cfg["vocab_size"], jnp.int32)
    return cfg, pcfg, build_model(pcfg), params, tokens


def _program_loss(model, pcfg, params, tokens):
    from repro.train.step import _forward_and_loss
    return _forward_and_loss(model, pcfg, params, {"tokens": tokens})[0]


def _reference_loss(cfg, params, tokens):
    nll, aux = ref.losses(cfg, params, tokens)
    B, S = tokens.shape
    return nll / (B * (S - 1)) + aux / B


def test_program_matches_reference_logits_loss_and_grads():
    cfg, pcfg, model, params, tokens = _setup()
    with jax.default_matmul_precision("highest"):
        logits, aux = jax.jit(model.forward)(params, tokens)
        want, want_aux = jax.jit(lambda p, t: ref.forward(cfg, p, t))(
            params, tokens)
        np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-4)
        assert float(aux["aux_loss"]) == pytest.approx(
            float(jnp.mean(want_aux)), rel=1e-4)
        loss, g = jax.jit(jax.value_and_grad(
            lambda p: _program_loss(model, pcfg, p, tokens)))(params)
        rloss, rg = jax.jit(jax.value_and_grad(
            lambda p: _reference_loss(cfg, p, tokens)))(params)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree_util.tree_leaves(rg)):
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-3 * float(jnp.max(jnp.abs(b))),
            err_msg=jax.tree_util.keystr(path))


def test_two_lars_steps_with_a_trust_ratio_per_expert():
    cfg, pcfg, model, params, tokens = _setup(seed=3)
    opt = {"lr": 1.0, "momentum": 0.9, "weight_decay": 1e-4,
           "trust_coefficient": 0.01}
    marker = model.stacked_marker(params)
    assert marker["layers"]["moe"]["wi"] == 2
    assert marker["layers"]["moe"]["shared"]["wi"] == 1
    assert marker["layers"]["moe"]["router"] == 1
    assert marker["dense_layers"]["mlp"]["wi"] == 1 and marker["embed"] == 0
    layout = packing.build_layout(params, marker)
    wi = next(s for s in layout.segments if s.name.endswith("moe/wi"))
    assert wi.layers == 2 * 4                       # (layer, expert) slices
    o = lars(opt["lr"], momentum=opt["momentum"],
             weight_decay=opt["weight_decay"],
             trust_coefficient=opt["trust_coefficient"], use_pallas=False)
    state = o.init(params, stacked=marker)
    grad = jax.jit(jax.grad(lambda p, t: _program_loss(model, pcfg, p, t)))
    p = params
    batches = [tokens, jax.random.randint(jax.random.key(9), tokens.shape,
                                          0, cfg["vocab_size"], jnp.int32)]
    with jax.default_matmul_precision("highest"):
        for t in batches:
            p, state = jax.jit(o.update)(grad(p, t), state, p)
        want = ref.TrainReference(cfg, opt, 2).run(params, batches)["params"]
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The routed parts of both shares of 4 of 8 experts, with the shared
    expert counted once, equal the reference layer holding all 8."""
    full, pcfg, _, params, _ = _setup(seed=5, n_routed_experts=8)
    p = jax.tree_util.tree_map(lambda t: t[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.key(6), (2, 16, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(full, p, x)
        shared = ref.mlp(p["shared"], x)
        parts = []
        for off in (0, 4):
            share = dataclasses.replace(pcfg, experts_held=4,
                                        expert_offset=off)
            sp = dict(p, **{n: p[n][off:off + 4] for n in ref.EXPERTS})
            parts.append(moe.moe_block(share, sp, x)[0] - shared)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want,
                               rtol=1e-4, atol=1e-5)


def test_skewed_router_drops_no_token():
    """Every token routed to the same two held experts: all N x k rows
    are computed, and the layer equals the reference's."""
    cfg, pcfg, _, params, _ = _setup(seed=7)
    p = jax.tree_util.tree_map(lambda t: t[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.key(8), (2, 16, 64), jnp.float32)
    # a router that sends every token to experts 1 and 2
    p["router"] = jnp.zeros_like(p["router"])
    x = x.at[..., 0].set(10.0)
    p["router"] = p["router"].at[0, 1].set(2.0).at[0, 2].set(1.0)
    with jax.default_matmul_precision("highest"):
        out, aux = moe.moe_block(pcfg, p, x)
        want, _ = ref.moe(cfg, p, x)
    assert float(aux["moe_rows"]) == 2 * 16 * 2
    assert float(aux["moe_load_max_over_mean"]) == pytest.approx(2.0)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m", "share"])
def test_no_token_depends_on_the_others(arch):
    """Dropless: a token's output depends on its own routing alone, so
    the layer over the whole batch equals it over four slices of the
    tokens (granite holding every expert, and a DeepSeek share)."""
    if arch == "share":
        _, pcfg, _, params, _ = _setup(seed=9)
    else:
        pcfg = get_config(arch).reduced()
        params = build_model(pcfg).init(jax.random.key(8))
    p = jax.tree_util.tree_map(lambda t: t[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.key(9), (2, 8, pcfg.d_model),
                          jnp.float32) * 0.2
    whole, _ = moe.moe_block(pcfg, p, x)
    parts = [moe.moe_block(pcfg, p, x[:, i:i + 2])[0] for i in (0, 2, 4, 6)]
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(jnp.concatenate(parts, axis=1)),
                               rtol=1e-4, atol=1e-5)


def test_grouped_matmul_equals_per_expert_einsums():
    cfg, pcfg, _, params, _ = _setup(seed=11)
    p = {n: params["layers"]["moe"][n][0] for n in ref.EXPERTS}
    sizes = jnp.array([5, 0, 9, 3], jnp.int32)
    xs = jax.random.normal(jax.random.key(12), (20, 64), jnp.float32)

    def per_expert(p, xs):
        out, lo = [], 0
        for e, n in enumerate(sizes.tolist()):
            xe = xs[lo:lo + n]
            h = jax.nn.silu(jnp.einsum("md,df->mf", xe, p["wg"][e])) \
                * jnp.einsum("md,df->mf", xe, p["wi"][e])
            out.append(jnp.einsum("mf,fd->md", h, p["wo"][e]))
            lo += n
        return jnp.concatenate(out + [jnp.zeros((20 - lo, 64))])

    def loss(f):
        return lambda p, xs: jnp.sum(jnp.sin(f(p, xs)))

    grouped = lambda p, xs: moe.grouped_mlp(pcfg, p, xs, sizes)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(grouped(p, xs), per_expert(p, xs),
                                   rtol=1e-5, atol=1e-5)
        ga = jax.grad(loss(grouped), argnums=(0, 1))(p, xs)
        gb = jax.grad(loss(per_expert), argnums=(0, 1))(p, xs)
    for a, b in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_causal_attention_kernel_at_mla_head_dims():
    """The splash kernels (interpret mode) at Dqk 192 / Dv 128 against the
    jnp path, with the scale folded into q as MLA folds it."""
    from repro.kernels import causal_attention as CA
    from repro.models.attention import attention_core
    ks = jax.random.split(jax.random.key(13), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 192), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 192), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 128), jnp.float32)
    q = (q * 0.1147).astype(jnp.bfloat16)
    k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    got = CA.causal_attention(q, k, v, scale=1.0, interpret=True)
    want = attention_core(q, k, v, q_positions=jnp.arange(256), scale=1.0)
    assert got.shape == (1, 256, 2, 128)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-2)


def test_yarn_softmax_scale_as_published():
    cfg = get_config("deepseek-v2-lite")
    assert mla.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
    assert mla.softmax_scale(cfg) == pytest.approx(0.1147, abs=1e-4)
    assert L.rope_cos_scale(cfg.rope_scaling) == 1.0
    inv, _, _ = ref.yarn(_cfg(), 64)
    np.testing.assert_allclose(L.rope_freqs(64, 10000.0, cfg.rope_scaling),
                               inv, rtol=1e-6)


def test_dense_smollm_layout_and_tree_unchanged():
    """A dense model keeps its tree and its packed layout: one slice per
    layer, the same segments as a marker of bools gives."""
    model = build_model(get_config("smollm-135m"))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert set(shapes) == {"embed", "layers", "final_norm"}
    marker = model.stacked_marker(shapes)
    assert set(jax.tree_util.tree_leaves(marker)) == {0, 1}
    old = {k: jax.tree_util.tree_map(lambda _: k == "layers", v)
           for k, v in shapes.items()}
    new, want = (packing.build_layout(shapes, m) for m in (marker, old))
    assert new.buffer_shape == want.buffer_shape == (263144, 512)
    assert new.segments == want.segments


@pytest.mark.parametrize("fan", [1, 3])
def test_dispatch_gather_gradient_equals_the_indexed_reads(fan):
    """The dispatch's row gather (a permutation of the slots, ``fan``
    slots a row) has the gradient of the plain indexed read."""
    x = jax.random.normal(jax.random.key(12), (8, 5), jnp.float32)
    perm = jax.random.permutation(jax.random.key(13), 8 * fan)
    inv = jnp.argsort(perm)
    ct = jax.random.normal(jax.random.key(14), (8 * fan, 5), jnp.float32)

    def loss(f):
        return lambda x: jnp.sum(f(x) * ct)

    got = jax.grad(loss(lambda x: moe._gather(x, perm, inv, fan)))(x)
    want = jax.grad(loss(lambda x: x[perm // fan]))(x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

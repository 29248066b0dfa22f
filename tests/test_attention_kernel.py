"""The causal flash-attention kernels (``kernels/causal_attention``)
against ``attention_core``'s jnp online softmax, and the dispatch
between them.

The kernels run in interpret mode here. Outputs and gradients with
respect to q, k and v are compared by the norm of their difference over
the norm of the jnp path's, within twice bf16's epsilon: both paths
take bf16 operands and return bf16, so each rounds its outputs once.
"""

import jax
import jax.numpy as jnp
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.sharding import AbstractMesh

from repro.kernels import causal_attention as CA
from repro.models import lm
from repro.models.attention import attention_core

TOL = 2 * float(jnp.finfo(jnp.bfloat16).eps)


def qkv(B, S, H, Hkv, D, Dv=None, Sk=None, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    Sk = Sk or S
    return (jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16),
            jax.random.normal(ks[1], (B, Sk, Hkv, D), jnp.bfloat16),
            jax.random.normal(ks[2], (B, Sk, Hkv, Dv or D), jnp.bfloat16))


def rel(a, b):
    a, b = (jnp.asarray(x, jnp.float32) for x in (a, b))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("S,D", [(256, 64), (512, 64), (1024, 64),
                                 (256, 128)])
def test_kernel_matches_jnp_path(S, D):
    B, H, Hkv = 2, 6, 2
    q, k, v = qkv(B, S, H, Hkv, D)
    w = jax.random.normal(jax.random.key(1), (B, S, H, D), jnp.float32)
    scale = D ** -0.5

    def kernel(q, k, v):
        return CA.causal_attention(q, k, v, scale=scale, interpret=True)

    def jnp_path(q, k, v):
        return attention_core(q, k, v, q_positions=jnp.arange(S))

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w)

    out, ref = jax.jit(kernel)(q, k, v), jax.jit(jnp_path)(q, k, v)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    assert rel(out, ref) < TOL
    grads = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    refs = jax.jit(jax.grad(loss(jnp_path), argnums=(0, 1, 2)))(q, k, v)
    for g, r in zip(grads, refs):
        assert g.shape == r.shape and rel(g, r) < TOL


@pytest.fixture
def spy(monkeypatch):
    """Count the calls that take the kernel, on a backend said to be
    the TPU (the kernels themselves still interpret on this CPU)."""
    calls = []
    kernel = CA.causal_attention

    def counted(*args, **kwargs):
        calls.append(1)
        return kernel(*args, **kwargs)

    monkeypatch.setattr(CA, "on_tpu", lambda: True)
    monkeypatch.setattr(CA, "causal_attention", counted)
    return calls


def took_kernel(calls, q, k, v, **kwargs):
    kwargs.setdefault("q_positions", None)
    jax.eval_shape(lambda q, k, v: attention_core(q, k, v, **kwargs),
                   q, k, v)
    return bool(calls)


def test_eligible_input_takes_the_kernel_on_tpu(spy):
    assert took_kernel(spy, *qkv(2, 512, 6, 2, 64))


def test_mla_head_dims_take_the_kernel_on_tpu(spy):
    """MLA: q and k of 192, v of 128, one query head per KV head, the
    YaRN scale folded into q by the caller (scale 1)."""
    assert took_kernel(spy, *qkv(2, 512, 4, 4, 192, Dv=128), scale=1.0)


def test_eligible_input_keeps_the_jnp_path_off_tpu(monkeypatch):
    calls = []
    monkeypatch.setattr(CA, "causal_attention",
                        lambda *a, **kw: calls.append(1))
    assert not CA.on_tpu()
    assert not took_kernel(calls, *qkv(2, 512, 6, 2, 64))


B, S, H, HKV, D = 2, 512, 6, 2, 64
# (shape changes, attention_core arguments) of each ineligible call
INELIGIBLE = {
    "window": ({}, dict(window=128)),
    "prefix_len": ({}, dict(prefix_len=64)),
    "kv_len": ({}, dict(kv_len=jnp.full((B,), S))),
    "kv_positions": ({}, dict(kv_positions=jnp.arange(S))),
    "softcap": ({}, dict(softcap=30.0)),
    "positions_given": ({}, dict(q_positions=jnp.arange(S))),
    "sq_ne_sk": (dict(Sk=2 * S), {}),
    "decode": (dict(S=1, Sk=S), dict(kv_len=jnp.full((B,), S))),
    "not_a_block": (dict(S=S + 64), {}),
    "inexact_scale": ({}, dict(scale=D ** -0.5 * 0.9)),
}


@pytest.mark.parametrize("case", sorted(INELIGIBLE))
def test_ineligible_input_keeps_the_jnp_path(spy, case):
    shape, kwargs = INELIGIBLE[case]
    q, k, v = qkv(**{**dict(B=B, S=S, H=H, Hkv=HKV, D=D), **shape})
    assert not took_kernel(spy, q, k, v, **kwargs)


def test_f32_operands_keep_the_jnp_path(spy):
    q, k, v = (x.astype(jnp.float32) for x in qkv(B, S, H, HKV, D))
    assert not took_kernel(spy, q, k, v)


def test_multi_device_mesh_keeps_the_jnp_path(spy):
    with jax.sharding.use_abstract_mesh(
            AbstractMesh((2, 1), ("data", "model"))):
        assert not took_kernel(spy, *qkv(B, S, H, HKV, D))


# ----------------------------------------------------------- layer remat

def pallas_calls(jaxpr) -> list:
    """Names of every ``pallas_call`` in a jaxpr and its sub-jaxprs."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += pallas_calls(sub)
    return names


def remat_layer(policy, attend):
    """A layer's attention under the given remat policy: q projected from
    the input, then attention against k and v."""
    def body(x, wq, k, v):
        q = (x @ wq).reshape(x.shape[0], x.shape[1], -1, k.shape[-1])
        o = attend(q, k, v)
        return jnp.sum(o.astype(jnp.float32) ** 2)
    return jax.checkpoint(body, policy=policy)


def layer_inputs(B=1, S=256, H=4, Hkv=2, D=64):
    x, k, v = qkv(B, S, H, Hkv, D)
    wq = 0.05 * jax.random.normal(jax.random.key(3), (H * D, H * D),
                                  jnp.bfloat16)
    return x.reshape(B, S, H * D), wq, k, v


def computed_residuals(f, *args) -> list:
    """(dtype, shape) of what ``f``'s backward keeps beyond its arguments
    and constants, sorted."""
    return sorted((str(a.dtype), a.shape) for a, why in
                  saved_residuals(f, *args)
                  if not why.startswith(("from the argument",
                                         "from a constant")))


def test_layer_remat_keeps_the_kernels_residuals():
    """The model's layer remat saves the kernel's output and logsumexp,
    so the backward runs the forward kernel once and not twice, and the
    gradients are those of saving nothing, bit for bit."""
    B, S, H, Hkv, D = 1, 256, 4, 2, 64
    args = layer_inputs(B, S, H, Hkv, D)

    def kernel(q, k, v):
        return CA.causal_attention(q, k, v, scale=D ** -0.5, interpret=True)

    kept = remat_layer(lm.LAYER_REMAT, kernel)
    recomputed = remat_layer(jax.checkpoint_policies.nothing_saveable,
                             kernel)
    G = H // Hkv
    assert computed_residuals(kept, *args) == sorted(
        [("bfloat16", (B * Hkv, G, S, D)), ("float32", (B * Hkv, G, S))])
    assert computed_residuals(recomputed, *args) == []

    def forwards(f):
        jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2, 3)))(*args)
        return pallas_calls(jaxpr.jaxpr).count("splash_mqa_fwd_residuals")

    assert forwards(kept) == 1
    assert forwards(recomputed) == 2
    grads = jax.jit(jax.grad(kept, argnums=(0, 1, 2, 3)))(*args)
    refs = jax.jit(jax.grad(recomputed, argnums=(0, 1, 2, 3)))(*args)
    for g, r in zip(grads, refs):
        assert g.dtype == r.dtype and bool(jnp.array_equal(g, r))


@pytest.mark.parametrize("arch", ["smollm-135m", "deepseek-v2-lite"])
def test_lm_remat_off_the_kernel_saves_what_nothing_saveable_saves(
        monkeypatch, arch):
    """A whole model's training forward off the kernel (reduced, on this
    CPU): its layer remat keeps what saving nothing keeps."""
    from repro.configs import get_config
    from repro.models import build_model

    model = build_model(get_config(arch).reduced())
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 128), 0,
                                model.cfg.vocab_size)

    def loss(params):
        return jnp.mean(model.forward(params, tokens)[0])

    def saved():
        return [(str(a.dtype), a.shape)
                for a, _ in saved_residuals(loss, params)]

    kept = saved()
    monkeypatch.setattr(lm, "LAYER_REMAT",
                        jax.checkpoint_policies.nothing_saveable)
    assert kept == saved()

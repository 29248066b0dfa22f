"""Per-kernel correctness sweeps: Pallas (interpret=True) vs pure-jnp ref."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ---------------------------------------------------------------- lars_norms

@pytest.mark.parametrize("shape,stacked", [
    ((128,), False),            # 1-d leaf (bias-sized)
    ((64, 64), False),
    ((5, 7), False),            # odd, forces padding
    ((3, 33, 17), True),        # stacked, odd
    ((4, 256, 512), True),      # stacked, aligned
    ((1, 100), True),           # stacked with L=1
    ((4096, 512), False),       # big unstacked
    ((33, 4096), False),        # 264 rows: a last grid tile of 8 rows
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lars_norms_matches_ref(shape, stacked, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    g = jax.random.normal(k2, shape, jnp.float32).astype(dtype)
    got_w, got_g = ops.lars_norms(w, g, stacked=stacked)
    exp_w, exp_g = ref.lars_norms(w, g, stacked=stacked)
    np.testing.assert_allclose(got_w, exp_w, rtol=1e-5)
    np.testing.assert_allclose(got_g, exp_g, rtol=1e-5)
    if stacked:
        assert got_w.shape == (shape[0],)
    else:
        assert got_w.shape == ()


# ---------------------------------------------------------------- lars_apply

@pytest.mark.parametrize("shape,stacked", [
    ((64, 64), False),
    ((5, 7), False),
    ((3, 33, 17), True),
    ((2, 128, 512), True),
    ((33, 4096), False),        # 264 rows: a last grid tile of 8 rows
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lars_apply_matches_ref(shape, stacked, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    w = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    g = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    m = jax.random.normal(ks[2], shape, jnp.float32)
    if stacked:
        lr = jnp.linspace(0.1, 0.3, shape[0])
    else:
        lr = jnp.asarray(0.17)
    got_w, got_m = ops.lars_apply(w, g, m, local_lr=lr, momentum=0.9,
                                  weight_decay=1e-4)
    exp_w, exp_m = ref.lars_apply(w, g, m, local_lr=lr, momentum=0.9,
                                  weight_decay=1e-4)
    rtol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got_w, np.float32),
                               np.asarray(exp_w, np.float32), rtol=rtol,
                               atol=1e-5)
    np.testing.assert_allclose(got_m, exp_m, rtol=1e-5, atol=1e-6)
    assert got_w.dtype == w.dtype
    assert got_m.dtype == jnp.float32


_PARAMS = {"w": jax.random.normal(jax.random.PRNGKey(0), (37, 19)),
           "stack": jax.random.normal(jax.random.PRNGKey(1), (3, 11, 13)),
           "b": jnp.ones((7,))}
_STACKED = {"w": False, "stack": True, "b": False}


def _grads(params, seed=2):
    return jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(seed), p.shape),
        params)


def test_lars_optimizer_pallas_path_equals_jnp_path():
    """End-to-end: the fused packed Pallas path == the per-leaf jnp
    reference path, leaf-by-leaf, params AND momentum."""
    from repro.core import lars, packing
    grads = _grads(_PARAMS)

    o1, o2 = lars(0.2), lars(0.2, use_pallas=True)
    p1, s1 = o1.update(grads, o1.init(_PARAMS), _PARAMS, stacked=_STACKED)
    p2, s2 = o2.update(grads, o2.init(_PARAMS, stacked=_STACKED), _PARAMS,
                       stacked=_STACKED)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5),
        p1, p2)
    m2 = packing.unpack(s2.layout, s2.slots["momentum"], dtype=jnp.float32)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5),
        s1.slots["momentum"], m2)


@pytest.mark.parametrize("params,stacked", [
    (_PARAMS, _STACKED),
    # many more leaves: launch count must NOT scale with the pytree
    ({f"w{i}": jax.random.normal(jax.random.PRNGKey(i), (16 + i, 24))
      for i in range(9)} | {"stk": jnp.ones((5, 6, 7)), "b": jnp.ones((3,))},
     {f"w{i}": False for i in range(9)} | {"stk": True, "b": False}),
])
def test_whole_pytree_lars_is_two_pallas_launches(params, stacked):
    """Acceptance: the packed LARS update issues exactly 2 pallas_call
    launches per step regardless of leaf count, and its results match the
    jnp reference path leaf-by-leaf for stacked and unstacked leaves."""
    from repro.core import lars
    from repro.kernels.introspect import count_pallas_launches
    grads = _grads(params)
    opt = lars(0.2, use_pallas=True)
    state = opt.init(params, stacked=stacked)
    n = count_pallas_launches(
        lambda g, s, p: opt.update(g, s, p), grads, state, params)
    assert n == 2, f"expected 2 pallas launches/step, traced {n}"

    ref = lars(0.2)
    p_ref, _ = ref.update(grads, ref.init(params), params, stacked=stacked)
    p_got, _ = opt.update(grads, state, params, stacked=stacked)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5),
        p_ref, p_got)


# -------------------------------------------------------------- flash_decode

@pytest.mark.parametrize("B,H,Hkv,S,D,bs", [
    (2, 8, 8, 256, 64, 128),    # MHA
    (2, 8, 2, 256, 64, 128),    # GQA
    (1, 8, 1, 512, 128, 256),   # MQA (paligemma-style)
    (3, 10, 2, 384, 64, 128),   # G=5 (qwen3-style), S not multiple of bs? 384/128=3 ok
    (1, 4, 4, 100, 64, 512),    # S < bs and not multiple -> pad path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_ref(B, H, Hkv, S, D, bs, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32).astype(dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
    got = ops.flash_decode(q, k, v, lengths, block_size=bs)
    exp = ref.flash_decode(q, k, v, lengths)
    rtol, atol = (1e-4, 1e-5) if dtype == jnp.float32 else (2e-2, 2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=rtol, atol=atol)


def test_flash_decode_zero_length_rows_are_finite():
    B, H, Hkv, S, D = 2, 4, 2, 128, 64
    q = jnp.ones((B, H, D))
    k = jnp.ones((B, S, Hkv, D))
    v = jnp.ones((B, S, Hkv, D))
    lengths = jnp.array([0, 5], jnp.int32)
    out = ops.flash_decode(q, k, v, lengths, block_size=64)
    assert np.all(np.isfinite(np.asarray(out)))
    # row with length 5 attends to identical values -> output == value
    np.testing.assert_allclose(out[1], jnp.ones((H, D)), rtol=1e-5)


def test_flash_decode_is_jittable():
    B, H, Hkv, S, D = 1, 4, 2, 256, 64
    q = jnp.ones((B, H, D))
    k = jnp.ones((B, S, Hkv, D))
    v = jnp.ones((B, S, Hkv, D))
    lengths = jnp.array([17], jnp.int32)
    f = jax.jit(lambda *a: ops.flash_decode(*a, block_size=128))
    out = f(q, k, v, lengths)
    assert out.shape == (B, H, D)

"""attention_core's jnp path (the online softmax scanned over KV chunks)
against a dense masked softmax written here: values and gradients must
agree across the mask models, GQA/MQA grouping, softcap, Dv != D (MLA)
and KV chunks that do and do not divide the sequence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import attention_core


def make_qkv(B=2, Sq=16, Sk=16, H=4, Hkv=2, D=8, Dv=None, seed=0):
    rng = np.random.default_rng(seed)
    Dv = Dv or D
    q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sk, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sk, Hkv, Dv)), jnp.float32)
    return q, k, v


def allowed(Sq, Sk, *, causal, window, prefix_len, kv_len):
    """(Sq, Sk) bool: the mask model, one query/key pair at a time."""
    def ok(qp, kp):
        if not causal:
            seen = True
        else:
            seen = kp <= qp or (prefix_len is not None
                                and qp < prefix_len and kp < prefix_len)
        in_window = not window or kp > qp - window
        valid = kv_len is None or kp < kv_len
        return seen and in_window and valid
    return np.array([[ok(qp, kp) for kp in range(Sk)] for qp in range(Sq)])


def dense_attention(q, k, v, *, causal, window, prefix_len, kv_len,
                    softcap):
    """Softmax over the full (Sq, Sk) score matrix; query head h reads
    KV head h // (H / Hkv)."""
    H, Hkv = q.shape[2], k.shape[2]
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    mask = allowed(q.shape[1], k.shape[1], causal=causal, window=window,
                   prefix_len=prefix_len, kv_len=kv_len)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


CASES = [
    pytest.param(dict(), {}, id="causal"),
    pytest.param(dict(causal=False), {}, id="encoder"),
    pytest.param(dict(window=5), {}, id="window"),
    pytest.param(dict(prefix_len=6), {}, id="prefix_lm"),
    pytest.param(dict(softcap=4.0), {}, id="softcap"),
    pytest.param(dict(kv_len=11), {}, id="kv_len"),
    pytest.param(dict(), dict(D=12, Dv=8), id="mla_dv_ne_d"),
    pytest.param(dict(), dict(Hkv=1), id="mqa"),
]


@pytest.mark.parametrize("case,shape", CASES)
@pytest.mark.parametrize("kv_chunk", [4, 5, 16])
def test_attention_core_matches_dense_reference(case, shape, kv_chunk):
    q, k, v = make_qkv(**shape)
    pos = jnp.arange(16)
    kw = dict(causal=True, window=0, prefix_len=None, kv_len=None,
              softcap=0.0)
    kw.update(case)

    def f_core(q, k, v):
        out = attention_core(q, k, v, q_positions=pos, kv_chunk=kv_chunk,
                             **kw)
        return jnp.sum(out * jnp.cos(out)), out

    def f_dense(q, k, v):
        out = dense_attention(q, k, v, **kw)
        return jnp.sum(out * jnp.cos(out)), out

    (_, o_c), g_c = jax.value_and_grad(f_core, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
    (_, o_d), g_d = jax.value_and_grad(f_dense, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_d),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(g_c, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

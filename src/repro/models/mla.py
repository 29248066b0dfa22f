"""MLA — Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV state is compressed to a rank-``kv_lora_rank`` latent c_kv plus one
shared RoPE key per token, so the decode cache stores
``kv_lora_rank + qk_rope_dim`` floats/token (576 for deepseek-v2-236b)
instead of ``2 * H * head_dim`` (32768) — a 57x cache reduction.

Decode uses the *absorbed* formulation: W_uk is folded into the query
(q_nope @ W_uk^T lands in latent space) and W_uv is applied after the
probability-weighted sum of latents, so the per-step cost is
O(S * (r + rope)) per head instead of O(S * H * head_dim) — the cache is
read once, never re-expanded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import phases
from repro.models import layers as L
from repro.models.attention import attention_core, _insert_at, _insert_span

NEG_INF = -1.0e30


def init_mla(key, cfg, d: int, dtype) -> dict:
    H = cfg.num_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    vd, r = cfg.v_head_dim, cfg.kv_lora_rank
    q_dim = H * (nope + rope)
    ks = jax.random.split(key, 7)
    p = {}
    if cfg.q_lora_rank:
        p["q_down"] = L.dense_init(ks[0], d, cfg.q_lora_rank, dtype)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), jnp.float32)
        p["q_up"] = L.dense_init(ks[1], cfg.q_lora_rank, q_dim, dtype)
    else:
        p["wq"] = L.dense_init(ks[0], d, q_dim, dtype)
    p["kv_down"] = L.dense_init(ks[2], d, r + rope, dtype)
    p["kv_norm"] = jnp.ones((r,), jnp.float32)
    p["k_up"] = L.dense_init(ks[3], r, H * nope, dtype)
    p["v_up"] = L.dense_init(ks[4], r, H * vd, dtype)
    p["wo"] = L.dense_init(ks[5], H * vd, d, dtype)
    return p


def softmax_scale(cfg) -> float:
    """(nope + rope)^-0.5, times YaRN's m(mscale_all_dim)^2 where the
    rope is scaled (DeepSeek-V2's ``softmax_scale``)."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= L.yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def _queries(cfg, p, x, positions, scale=None):
    """(q_nope, q_rope) per head. ``scale``: folded into both while they
    are f32, before their one rounding to x.dtype."""
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    out = None if scale is None else jnp.float32
    if cfg.q_lora_rank:
        cq = L.rmsnorm(x @ p["q_down"], p["q_norm"], cfg.norm_eps)
        q = jnp.matmul(cq, p["q_up"], preferred_element_type=out)
    else:
        q = jnp.matmul(x, p["wq"], preferred_element_type=out)
    q = q.reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta,
                          cfg.rope_scaling)
    if scale is not None:
        q_nope = (q_nope * scale).astype(x.dtype)
        q_rope = (q_rope * scale).astype(x.dtype)
    return q_nope, q_rope


def _latents(cfg, p, x, positions):
    """c_kv (B,S,r) normalized latent; k_rope (B,S,1,rope) roped shared key."""
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_dim
    kv = x @ p["kv_down"]
    c_kv, k_rope = kv[..., :r], kv[..., r:]
    c_kv = L.rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                          cfg.rope_scaling)
    return c_kv, k_rope


def mla_block(cfg, p, x, positions) -> jnp.ndarray:
    """Train/prefill: expanded (naive) form — full K/V materialized per
    layer, which is fine when activations are remat'd anyway. The
    softmax scale is folded into q (f32, one rounding), so the core
    runs at scale 1: the causal flash kernels take it on the TPU.
    ``positions`` None means 0..S-1, a fact the core sees while tracing."""
    B, S, d = x.shape
    H = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim

    pos = jnp.arange(S) if positions is None else positions
    q_nope, q_rope = _queries(cfg, p, x, pos, softmax_scale(cfg))
    c_kv, k_rope = _latents(cfg, p, x, pos)

    k_nope = (c_kv @ p["k_up"]).reshape(B, S, H, nope)
    v = (c_kv @ p["v_up"]).reshape(B, S, H, vd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))], axis=-1)

    with jax.named_scope(phases.ATTENTION_CORE):
        out = attention_core(q, k, v, q_positions=positions, causal=True,
                             scale=1.0)
    return out.reshape(B, S, H * vd) @ p["wo"]


def mla_decode(cfg, p, x, cache_ckv, cache_krope, pos):
    """Absorbed decode. x (B,1,d); cache_ckv (B,S,r);
    cache_krope (B,S,rope). Returns (out (B,1,d), new caches)."""
    B, _, d = x.shape
    H = cfg.num_heads
    nope, rope, vd, r = (cfg.qk_nope_dim, cfg.qk_rope_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
    S = cache_ckv.shape[1]

    q_nope, q_rope = _queries(cfg, p, x, pos[:, None])      # (B,1,H,*)
    c_kv, k_rope = _latents(cfg, p, x, pos[:, None])        # (B,1,r),(B,1,1,rope)

    cache_ckv = _insert_at(cache_ckv, c_kv, pos)            # (B,S,r)
    cache_krope = _insert_at(cache_krope, k_rope[:, :, 0, :], pos)  # (B,S,rope)

    # absorb W_uk into q: (B,1,H,nope) @ (r,H,nope)^T -> (B,H,r)
    k_up = p["k_up"].reshape(r, H, nope)
    q_lat = jnp.einsum("bohn,rhn->bhr", q_nope.astype(jnp.float32),
                       k_up.astype(jnp.float32))
    scores = jnp.einsum("bhr,bsr->bhs", q_lat,
                        cache_ckv.astype(jnp.float32))
    scores += jnp.einsum("bohe,bse->bhs", q_rope.astype(jnp.float32),
                         cache_krope.astype(jnp.float32))
    scores *= softmax_scale(cfg)
    valid = jnp.arange(S)[None, None, :] < (pos[:, None, None] + 1)
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)                 # (B,H,S)

    out_lat = jnp.einsum("bhs,bsr->bhr", probs,
                         cache_ckv.astype(jnp.float32))     # (B,H,r)
    v_up = p["v_up"].reshape(r, H, vd)
    out = jnp.einsum("bhr,rhv->bhv", out_lat, v_up.astype(jnp.float32))
    out = out.reshape(B, 1, H * vd).astype(x.dtype) @ p["wo"]
    return out, cache_ckv, cache_krope


def mla_chunk(cfg, p, x, cache_ckv, cache_krope, qpos, start, lengths):
    """Absorbed resume-prefill for a C-token chunk. x (B,C,d);
    qpos (B,C) absolute positions start[b]+i; lengths (B,) valid tokens
    per row. Latents are scattered at [start, start+C) and the chunk
    attends the whole latent cache under a causal + kv_len mask, so
    positions past start+lengths (pad, or a prior occupant's leftovers)
    never contribute. Returns (out (B,C,d), new caches)."""
    B, C, d = x.shape
    H = cfg.num_heads
    nope, rope, vd, r = (cfg.qk_nope_dim, cfg.qk_rope_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
    S = cache_ckv.shape[1]

    q_nope, q_rope = _queries(cfg, p, x, qpos)              # (B,C,H,*)
    c_kv, k_rope = _latents(cfg, p, x, qpos)                # (B,C,r),(B,C,1,rope)

    cache_ckv = _insert_span(cache_ckv, c_kv, start)
    cache_krope = _insert_span(cache_krope, k_rope[:, :, 0, :], start)

    k_up = p["k_up"].reshape(r, H, nope)
    q_lat = jnp.einsum("bchn,rhn->bchr", q_nope.astype(jnp.float32),
                       k_up.astype(jnp.float32))
    scores = jnp.einsum("bchr,bsr->bhcs", q_lat,
                        cache_ckv.astype(jnp.float32))
    scores += jnp.einsum("bche,bse->bhcs", q_rope.astype(jnp.float32),
                         cache_krope.astype(jnp.float32))
    scores *= softmax_scale(cfg)
    kp = jnp.arange(S)[None, None, None, :]                 # linear cache
    qp = qpos[:, None, :, None]
    kv_len = (start + lengths)[:, None, None, None]
    scores = jnp.where((kp <= qp) & (kp < kv_len), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)                 # (B,H,C,S)

    out_lat = jnp.einsum("bhcs,bsr->bchr", probs,
                         cache_ckv.astype(jnp.float32))
    v_up = p["v_up"].reshape(r, H, vd)
    out = jnp.einsum("bchr,rhv->bchv", out_lat, v_up.astype(jnp.float32))
    out = out.reshape(B, C, H * vd).astype(x.dtype) @ p["wo"]
    return out, cache_ckv, cache_krope

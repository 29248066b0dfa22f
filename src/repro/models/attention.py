"""GQA/MHA/MQA attention: blockwise (online-softmax) jnp core + projections.

The blockwise core scans over KV chunks so no (S, S) score matrix ever
materializes — this is the memory-efficient formulation that makes the
32k-prefill and 4k-train shapes fit per-device HBM under remat, and it is
exactly the algorithm the Pallas ``flash_decode`` kernel implements for
the 1-token decode case (kernel used on real TPU; this jnp path is the
oracle and the `pjit`-friendly default).

Mask model (one code path for all families):
  allowed(qp, kp) = [kp <= qp  (causal)
                     OR (qp < prefix_len AND kp < prefix_len)  (prefix-LM)
                     OR not causal (encoder)]
                    AND (window == 0 OR kp > qp - window)
                    AND kp < kv_valid_len
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import phases
from repro.kernels import causal_attention as CA
from repro.models import layers as L

NEG_INF = -1.0e30


def init_attention(key, cfg, d: int, dtype) -> dict:
    H, Hkv, hd = cfg.attn_dims
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, H * hd, dtype),
        "wk": L.dense_init(ks[1], d, Hkv * hd, dtype),
        "wv": L.dense_init(ks[2], d, Hkv * hd, dtype),
        "wo": L.dense_init(ks[3], H * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((Hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((Hkv * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def qkv_project(cfg, p, x, positions, *, rope: bool = True):
    """x (B,S,d) -> q (B,S,H,hd), k/v (B,S,Hkv,hd) with rope + qk_norm."""
    H, Hkv, hd = cfg.attn_dims
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = L.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if rope and cfg.use_rope:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _block_mask(qp, kp, *, causal, window, prefix_len, kv_len):
    """(..., Sq, Kc) bool. qp (..., Sq), kp (Kc,) or (..., Kc) absolute
    positions (2-D kp: per-row KV positions — the chunk-resume path,
    where ring occupants depend on each row's resume offset)."""
    qp = qp[..., :, None]
    kp_b = kp[..., None, :] if kp.ndim > 1 else kp[None, :]
    if causal:
        ok = kp_b <= qp
        if prefix_len is not None:
            pl_ = prefix_len if jnp.ndim(prefix_len) == 0 else prefix_len[..., None, None]
            ok = ok | ((qp < pl_) & (kp_b < pl_))
    else:
        ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp_b.shape), bool)
    if window:
        ok = ok & (kp_b > qp - window)
    if kv_len is not None:
        kvl = kv_len if jnp.ndim(kv_len) == 0 else kv_len[..., None, None]
        ok = ok & (kp_b < kvl)
    return ok


def attention_core(q, k, v, *, q_positions, kv_positions=None,
                   causal: bool = True, window: int = 0,
                   prefix_len=None, kv_len=None,
                   kv_chunk: int = 1024, scale: Optional[float] = None,
                   softcap: float = 0.0) -> jnp.ndarray:
    """Online-softmax attention, scanned over KV chunks.

    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D). q_positions: (Sq,) or
    (B, Sq); None means 0..Sq-1, a fact known while tracing.
    Returns (B, Sq, H, D) in q.dtype; accumulation in f32.

    Plain causal self-attention over positions 0..S-1 (no window,
    prefix, length, KV positions or softcap) runs in the Pallas flash
    kernels on a TPU wherever :func:`repro.kernels.causal_attention.
    supports` the shapes; that path ignores ``kv_chunk``, which shapes
    only this jnp path, its oracle.

    ``kv_chunk >= Sk`` collapses the KV scan into a single unrolled block
    (the decode path: scanning over a TP-sharded cache axis would force
    an all-gather per iteration; one block lets GSPMD keep KV stripes
    local and all-reduce the per-stripe partial softmax — the
    flash-decoding split-KV combine, compiler-inserted).
    """
    B, Sq, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    if q_positions is None:
        if (causal and not window and prefix_len is None and kv_len is None
                and kv_positions is None and not softcap
                and CA.supports(q, k, v, scale)):
            return CA.causal_attention(q, k, v, scale=scale)
        q_positions = jnp.arange(Sq)
    Sk, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[3]                       # may differ from D (MLA)
    G = H // Hkv
    kc = min(kv_chunk, Sk)
    if Sk % kc:
        pad = kc - Sk % kc
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_len = jnp.asarray(Sk if kv_len is None else kv_len)
        Sk = Sk + pad
    nk = Sk // kc
    if kv_positions is None:
        kv_positions = jnp.arange(Sk)

    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    k_r = k.reshape(B, nk, kc, Hkv, D)
    v_r = v.reshape(B, nk, kc, Hkv, Dv)
    if kv_positions.ndim == 1:
        kp_r = kv_positions.reshape(nk, kc)
    elif nk == 1:
        kp_r = None                            # (B, Sk) per-row positions
    else:
        raise ValueError("2-D kv_positions need kv_chunk >= Sk "
                         "(single-block attention)")

    if q_positions.ndim == 1:
        q_positions = jnp.broadcast_to(q_positions[None], (B, Sq))

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kb, vb, kp = inp                       # (B,kc,Hkv,D), ..., (kc,)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kb.astype(jnp.float32))
        s = s * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = _block_mask(q_positions, kp, causal=causal, window=window,
                           prefix_len=prefix_len, kv_len=kv_len)
        # mask (B, Sq, kc) -> (B, 1, 1, Sq, kc)
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = p * (s > NEG_INF / 2)              # zero fully-masked entries
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
        acc = acc * alpha[..., None] + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, Sq, Dv), jnp.float32)
    if nk == 1:   # single block: no scan (keeps sharded-KV decode local)
        kp0 = kv_positions if kp_r is None else kp_r[0]
        (m, l, acc), _ = step((m0, l0, acc0),
                              (k_r[:, 0], v_r[:, 0], kp0))
    else:
        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, acc0),
            (jnp.moveaxis(k_r, 1, 0), jnp.moveaxis(v_r, 1, 0), kp_r))
    out = acc / jnp.maximum(l, 1e-30)[..., None]     # (B,Hkv,G,Sq,Dv)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


def attention_block(cfg, p, x, positions, *, causal=True, prefix_len=None,
                    window=None) -> jnp.ndarray:
    """Full attention sub-block for train/prefill (projections included).
    ``positions`` None means 0..S-1 (see :func:`attention_core`)."""
    B, S, d = x.shape
    H, Hkv, hd = cfg.attn_dims
    q, k, v = qkv_project(cfg, p, x,
                          jnp.arange(S) if positions is None else positions)
    w = cfg.sliding_window if window is None else window
    with jax.named_scope(phases.ATTENTION_CORE):
        out = attention_core(q, k, v, q_positions=positions, causal=causal,
                             window=w, prefix_len=prefix_len,
                             softcap=cfg.attn_logit_softcap)
    return out.reshape(B, S, H * hd) @ p["wo"]


def decode_attention(cfg, p, x, cache_k, cache_v, pos, *,
                     prefix_len=None, use_flash: bool = False):
    """One-token decode: x (B,1,d), cache (B,S_buf,Hkv,hd), pos (B,) int32
    absolute position. Returns (out (B,1,d), new_k, new_v).

    Sliding-window archs keep a RING buffer of capacity window: the new
    key (roped at its absolute position — RoPE is relative, so scores
    stay correct) overwrites slot ``pos % window`` and attention simply
    covers every valid slot. Full-attention archs use a linear buffer of
    capacity seq_len.
    """
    del prefix_len  # decode tokens sit after any prefix => plain causal
    B, _, d = x.shape
    H, Hkv, hd = cfg.attn_dims
    S_buf = cache_k.shape[1]
    windowed = bool(cfg.sliding_window) and cfg.sliding_window <= S_buf
    q, k_new, v_new = qkv_project(cfg, p, x, pos[:, None], rope=True)
    slot = pos % S_buf if windowed else pos
    cache_k = _insert_at(cache_k, k_new, slot)
    cache_v = _insert_at(cache_v, v_new, slot)
    kv_len = jnp.minimum(pos + 1, S_buf) if windowed else pos + 1
    if use_flash:
        from repro.kernels import ops as kops
        out = kops.flash_decode(q[:, 0], cache_k, cache_v, kv_len,
                                scale=hd ** -0.5)[:, None]
    else:
        # windowed ring: every written slot is in-range => no causal mask,
        # only the validity mask. linear buffer: plain causal + validity.
        # kv_chunk = full buffer: one unrolled block so the TP-sharded
        # cache stays local (split-KV partial softmax + all-reduce).
        out = attention_core(q, cache_k, cache_v,
                             q_positions=pos[:, None], causal=not windowed,
                             window=0, kv_len=kv_len,
                             kv_chunk=cache_k.shape[1],
                             softcap=cfg.attn_logit_softcap)
    out = out.reshape(B, 1, H * hd) @ p["wo"]
    return out, cache_k, cache_v


def _insert_at(cache, new, pos):
    """cache (B,S,h,d), new (B,1,h,d), pos (B,) -> cache with row written.

    vmapped dynamic_update_slice => a true scatter (O(1) rows touched),
    not an O(S) one-hot rewrite — matters at 524288-entry caches.
    """
    def one(c, n, p):
        return jax.lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype),
                                                   p, axis=0)
    return jax.vmap(one)(cache, new.astype(cache.dtype), pos)


# ----------------------------------------------------------- chunk resume

def _insert_span(cache, new, start):
    """Write ``new`` (B, C, ...) at rows [start[b], start[b]+C) of a
    linear cache (B, S, ...). Out-of-bounds positions (a pad tail
    hanging past capacity) are DROPPED, not clamped — clamping would
    shift the write window backward over real entries."""
    C = new.shape[1]

    def one(c, n, s):
        return c.at[s + jnp.arange(C)].set(n.astype(c.dtype), mode="drop",
                                           unique_indices=True)

    return jax.vmap(one)(cache, new.astype(cache.dtype),
                         start.astype(jnp.int32))


def _ring_update(ring, chunk, start, lengths):
    """Scatter a prefill chunk into a windowed ring buffer, per row.

    ring (B, cap, ...); chunk (B, C, ...) holds absolute positions
    start[b] + i, of which only i < lengths[b] are valid. Ring slot j
    takes the NEWEST valid chunk position p with p % cap == j and keeps
    its old value otherwise — pad positions never clobber resident
    entries (they may be needed by later chunks or the next occupant
    of a shared-prefix snapshot)."""
    B, cap = ring.shape[:2]
    C = chunk.shape[1]
    j = jnp.arange(cap)[None, :]                     # (1, cap)
    r0 = (j - start[:, None]) % cap                  # smallest i >= 0 -> j
    li = lengths[:, None].astype(jnp.int32)
    i_star = r0 + cap * jnp.maximum((li - 1 - r0) // cap, 0)
    has = (r0 < li) & (i_star < C)                   # (B, cap)
    idx = jnp.clip(i_star, 0, C - 1)
    tail = (1,) * (chunk.ndim - 2)
    picked = jnp.take_along_axis(chunk.astype(ring.dtype),
                                 idx.reshape((B, cap) + tail), axis=1)
    return jnp.where(has.reshape((B, cap) + tail), picked, ring)


def chunk_attention(cfg, p, x, cache_k, cache_v, qpos, start, lengths):
    """Resume-prefill attention for a C-token chunk against a live slot
    cache. x (B, C, d); qpos (B, C) absolute positions start[b]+i;
    lengths (B,) valid tokens per row (0 = row untouched upstream).
    Returns (out (B, C, d), new_k, new_v).

    Linear buffers: the chunk KV is scattered first, then queries attend
    the whole buffer under the causal mask — positions beyond
    start+lengths are masked by kv_len, so stale entries from a retired
    occupant are never read. Windowed rings: queries attend
    [resident ring || chunk KV] BEFORE the ring is rewritten (scattering
    first would lose ring positions that early chunk queries still
    need), with each ring slot's absolute occupant position derived from
    the resume offset; invalid slots are pushed past the newest query so
    the causal mask removes them.
    """
    B, C, d = x.shape
    H, Hkv, hd = cfg.attn_dims
    S_buf = cache_k.shape[1]
    windowed = bool(cfg.sliding_window) and cfg.sliding_window <= S_buf
    q, k_new, v_new = qkv_project(cfg, p, x, qpos)
    kv_len = (start + lengths).astype(jnp.int32)     # (B,)
    if windowed:
        j = jnp.arange(S_buf)[None, :]
        occ = j + S_buf * ((start[:, None] - 1 - j) // S_buf)
        occ = jnp.where(occ < 0, qpos[:, -1:] + 1, occ)   # causal-masked
        kv_k = jnp.concatenate([cache_k, k_new.astype(cache_k.dtype)], 1)
        kv_v = jnp.concatenate([cache_v, v_new.astype(cache_v.dtype)], 1)
        kvp = jnp.concatenate([occ, qpos], axis=1)   # (B, S_buf + C)
        out = attention_core(q, kv_k, kv_v, q_positions=qpos,
                             kv_positions=kvp, causal=True,
                             window=cfg.sliding_window, kv_len=kv_len,
                             kv_chunk=S_buf + C,
                             softcap=cfg.attn_logit_softcap)
        cache_k = _ring_update(cache_k, k_new, start, lengths)
        cache_v = _ring_update(cache_v, v_new, start, lengths)
    else:
        cache_k = _insert_span(cache_k, k_new, start)
        cache_v = _insert_span(cache_v, v_new, start)
        out = attention_core(q, cache_k, cache_v, q_positions=qpos,
                             causal=True, window=0, kv_len=kv_len,
                             kv_chunk=S_buf,
                             softcap=cfg.attn_logit_softcap)
    out = out.reshape(B, C, H * hd) @ p["wo"]
    return out, cache_k, cache_v

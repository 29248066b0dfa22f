"""Flash-decode: single-token GQA attention over a blocked KV cache.

The decode hot path (serve_step) computes attention of ONE query token per
sequence against a cache of up to 524288 keys. On TPU the bottleneck is
streaming the cache through VMEM exactly once; this kernel does the
classic online-softmax accumulation over KV blocks so no (S,)-sized
intermediate ever materializes.

Grid: (B, S/bs) — the S axis is innermost so the running (max, denom,
acc) state lives in VMEM scratch across the blocks of one sequence and
is finalized on its last block.

Every KV head is handled in one grid step, on the cache's own layout
viewed as ``(B, S, Hkv*D)`` (a free reshape): a K/V block is the
lane-dense ``(bs, Hkv*D)`` slab, whose minor dim is the whole array's —
a block Mosaic accepts for any head count. The query arrives
block-diagonal, ``(H, Hkv*D)`` with head ``h``'s group of G query rows
holding its vector only in the lanes of kv head ``h``, so ONE matmul
gives every head its own scores, and ``p @ V`` puts each head's output in
its own kv head's lanes; the wrapper keeps that diagonal. The matmuls do
Hkv times the useful MXU work, which decode — bound by streaming the
cache — has to spare.

Blocks:
  lengths  (B,) int32   — scalar prefetch (SMEM)
  q        (1, H, Hkv*D)
  k/v      (1, bs, Hkv*D)
  out      (1, H, Hkv*D)

VMEM holds the double-buffered K/V blocks, their f32 copies, the
(H, bs) scores and the (H, Hkv*D) accumulator; at qwen3-14b's heads
(Hkv*D = 1024, H = 40) and bs=512 that compiles for a v5e
(``tests/test_tpu_compile.py``).
Per-sequence valid lengths mask the tail (cache is a ring of capacity S).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


@functools.cache
def default_interpret() -> bool:
    """Interpreter mode wherever the Mosaic kernel cannot compile.

    This is a TPU-dialect kernel (``pltpu.VMEM`` scratch): only the TPU
    backend compiles it; CPU/GPU fall back to the Pallas interpreter.
    Callers thread an explicit ``interpret=`` override for tests that
    pin one mode.
    """
    return jax.default_backend() != "tpu"


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, bs: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # (H, Hkv*D)
    k = k_ref[0].astype(jnp.float32)              # (bs, Hkv*D)
    v = v_ref[0].astype(jnp.float32)              # (bs, Hkv*D)
    length = len_ref[b]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < length
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                            # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new) * valid.astype(jnp.float32)   # (H, bs)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_decode_grouped(q4: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         lengths: jnp.ndarray, *, scale: float,
                         bs: int = 512, interpret: Optional[bool] = None
                         ) -> jnp.ndarray:
    """q4: (B, Hkv, G, D); k/v: (B, S, Hkv, D); lengths: (B,) int32.

    Returns (B, Hkv, G, D) attention output in q4.dtype.
    ``interpret=None`` auto-selects from the backend (TPU compiles the
    Mosaic kernel; CPU/GPU interpret).
    """
    if interpret is None:
        interpret = default_interpret()
    B, Hkv, G, D = q4.shape
    S = k.shape[1]
    assert S % bs == 0, (S, bs)
    H, C = Hkv * G, Hkv * D
    # block-diagonal query: row (h, g) carries q4[h, g] in kv head h's lanes
    eye = jnp.eye(Hkv, dtype=q4.dtype)
    q_bd = jnp.einsum("bhgd,hk->bhgkd", q4, eye).reshape(B, H, C)
    kern = functools.partial(_decode_kernel, scale=scale, bs=bs)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, S // bs),
            in_specs=[
                pl.BlockSpec((1, H, C), lambda b, j, n: (b, 0, 0)),   # q
                pl.BlockSpec((1, bs, C), lambda b, j, n: (b, j, 0)),  # k
                pl.BlockSpec((1, bs, C), lambda b, j, n: (b, j, 0)),  # v
            ],
            out_specs=pl.BlockSpec((1, H, C), lambda b, j, n: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),   # running max
                pltpu.VMEM((H, 1), jnp.float32),   # running denom
                pltpu.VMEM((H, C), jnp.float32),   # running acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, C), q4.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(lengths, q_bd, k.reshape(B, S, C), v.reshape(B, S, C))
    # keep each query head's own kv head's lanes: the block diagonal
    out = out.reshape(B, Hkv, G, Hkv, D)
    return jnp.einsum("bhgkd,hk->bhgd", out, eye)

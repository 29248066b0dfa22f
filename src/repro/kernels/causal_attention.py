"""Causal flash attention for training and full prefill on the TPU.

The splash-attention Pallas kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``): a forward kernel
and, under ``jax.grad``, one backward kernel that computes dq, dk and
dv together. Each keeps one (block_q, block_kv) score tile in VMEM,
accumulates the softmax online in f32, and skips the KV blocks the causal mask removes whole — exactly
the blocks whose probabilities are all zero. MXU operands stay in the
inputs' bf16; scores, statistics (max, sum, logsumexp) and
accumulators are f32.

Layout: the model's q (B, S, H, D) and k/v (B, S, Hkv, D) are regrouped
so each (batch row, KV head) is one MQA problem: the head's G = H / Hkv
query heads against its one K and V. One ``vmap`` over the B * Hkv
problems becomes an extra grid axis of each kernel, so a layer runs one
launch of each. The compiled program and the profiler trace name the
kernels' ops ``splash_mqa_fwd_residuals`` (forward under autodiff),
``splash_mqa_fwd_no_residuals`` (inference) and
``splash_mqa_dkv_no_residuals`` (backward).

Under autodiff the forward tags its two residuals, the output and the
logsumexp, with :data:`RESIDUALS` (``jax.ad_checkpoint.checkpoint_name``):
a remat policy that saves that name keeps them, and the backward kernel
then needs no second forward.

Splash takes no softmax scale; it is folded into q before the kernel.
That is exact only where the scale is a power of two (head_dim 64:
1/8), so :func:`supports` accepts no other scale. A caller whose scale
is not one folds it into q itself while q is still f32, before q's one
rounding to bf16, and passes scale 1: q is then rounded once, as it
always was (MLA, whose YaRN scale is 192^-0.5 m^2, does so).

K and V may differ in head dim (MLA: 192 for q and k, 128 for v).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.distributed.constrain import current_mesh
from repro.kernels.flash_decode import default_interpret

# q/kv rows per tile: the largest of these that divides the sequence
# (multiples of Mosaic's 128 lanes). The backward runs as one fused
# dq-dk-dv kernel on larger tiles: the fastest on a v5e at smollm-135m's
# training shapes (block sweep in PERF.md).
FWD_BLOCKS = (512, 256, 128)
BWD_BLOCKS = (1024, 512, 256, 128)

# checkpoint name of the forward's output and logsumexp (the residuals
# the backward kernel reads); the layer remat in ``models/lm`` saves it
RESIDUALS = "attention_residuals"


def _largest(blocks, seq: int) -> int:
    return next((b for b in blocks if seq % b == 0), 0)


def on_tpu() -> bool:
    """The TPU backend compiles the kernels (the package's one policy:
    :func:`repro.kernels.flash_decode.default_interpret`)."""
    return not default_interpret()


def supports(q, k, v, scale: float) -> bool:
    """Whether the kernel computes this call exactly as the jnp path
    would, on this backend: causal self-attention over the same sequence
    (Sq == Sk, a multiple of a block), GQA groups, bf16 operands, a
    scale that folds into bf16 q without rounding, and one device (no
    partitioning rule for Mosaic kernels under a mesh)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    mesh = current_mesh()
    return (on_tpu()
            and (mesh is None or math.prod(mesh.shape.values()) == 1)
            and Sq == Sk and _largest(FWD_BLOCKS, Sq) > 0
            and H % Hkv == 0
            and q.dtype == k.dtype == v.dtype == jnp.bfloat16
            and math.frexp(scale)[0] == 0.5)


def _kernel(seq: int, groups: int, interpret: bool):
    # splash caches the block maps of a mask; built anew in each trace,
    # the kernel's arrays are that trace's constants
    mask = splash.MultiHeadMask([splash.CausalMask((seq, seq))] * groups)
    fwd, bwd = _largest(FWD_BLOCKS, seq), _largest(BWD_BLOCKS, seq)
    sizes = splash.BlockSizes(
        block_q=fwd, block_kv=fwd, block_kv_compute=fwd,
        block_q_dkv=bwd, block_kv_dkv=bwd, block_kv_dkv_compute=bwd,
        use_fused_bwd_kernel=True)
    return splash.make_splash_mqa_single_device(
        mask, block_sizes=sizes, residual_checkpoint_name=RESIDUALS,
        interpret=interpret)


def causal_attention(q, k, v, *, scale: float,
                     interpret: bool | None = None) -> jnp.ndarray:
    """softmax(scale * q k^T, causal) v for q (B, S, H, D), k
    (B, S, Hkv, D), v (B, S, Hkv, Dv), S a multiple of a block; returns
    (B, S, H, Dv) in q.dtype. ``interpret`` None takes the backend's
    mode."""
    B, S, H, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    G = H // Hkv
    if interpret is None:
        interpret = default_interpret()
    qs = (q * jnp.asarray(scale, q.dtype)).reshape(B, S, Hkv, G, D)
    qg = qs.transpose(0, 2, 3, 1, 4).reshape(B * Hkv, G, S, D)
    kg = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vg = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dv)
    out = jax.vmap(_kernel(S, G, interpret))(qg, kg, vg)
    return out.reshape(B, Hkv, G, S, Dv).transpose(0, 3, 1, 2, 4).reshape(
        B, S, H, Dv)

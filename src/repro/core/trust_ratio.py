"""Layer-wise trust-ratio math shared by LARS and LAMB.

Paper Eq. (2)/(3):

    lambda_l = eta * ||w_l|| / (||grad_l|| + beta * ||w_l||)

with eta the trust coefficient and beta the weight decay. "Layer" in the
paper means each weight tensor of the DML script; here it means each
parameter leaf — and each *leading-axes slice* of a leaf marked
``stacked`` (layer-scanned models store params as ``(L, ...)``, marker 1;
routed experts as ``(L, E, ...)``, marker 2: one slice per expert).

Conventions (following You et al. ICPP'18 and common practice):
* parameters whose effective rank is <= 1 (biases, norm scales, scalar
  gains) are NOT adapted: trust ratio = 1. Controlled by
  ``skip_adaptation_1d``.
* degenerate norms (zero weights or zero grads) fall back to trust ratio 1
  so the step degenerates to plain (decayed) SGD instead of 0/0.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def reduction_axes(x: jnp.ndarray, stacked: int) -> Optional[tuple[int, ...]]:
    """Axes over which a 'per-layer' norm reduces.

    Non-stacked: all axes (one scalar norm per tensor).
    Stacked on n axes (``True`` is 1): all but the first n (one norm per
    slice).
    """
    return tuple(range(int(stacked), x.ndim))


def effective_rank(x: jnp.ndarray, stacked: int) -> int:
    return x.ndim - int(stacked)


def layer_norms(w: jnp.ndarray, g: jnp.ndarray, stacked: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(||w||, ||g||) per slice, computed in f32; shape () or the stacked
    axes' (L,) or (L, E)."""
    axes = reduction_axes(w, stacked)
    wf = w.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    w_norm = jnp.sqrt(jnp.sum(jnp.square(wf), axis=axes))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(gf), axis=axes))
    return w_norm, g_norm


def lars_trust_ratio(w_norm: jnp.ndarray, g_norm: jnp.ndarray, *,
                     eta: float, weight_decay: float,
                     eps: float = 1e-9) -> jnp.ndarray:
    """Paper Eq. (3): eta * ||w|| / (||g|| + beta*||w||), guarded."""
    denom = g_norm + weight_decay * w_norm
    ratio = eta * w_norm / (denom + eps)
    ok = (w_norm > 0.0) & (g_norm > 0.0)
    return jnp.where(ok, ratio, 1.0)


def lamb_trust_ratio(w_norm: jnp.ndarray, u_norm: jnp.ndarray, *,
                     clip_max: float = 10.0, eps: float = 1e-9) -> jnp.ndarray:
    """LAMB phi(||w||)/||update|| with phi = clip to [0, clip_max]."""
    phi = jnp.minimum(w_norm, clip_max)
    ratio = phi / (u_norm + eps)
    ok = (w_norm > 0.0) & (u_norm > 0.0)
    return jnp.where(ok, ratio, 1.0)


def broadcast_ratio(ratio: jnp.ndarray, like: jnp.ndarray,
                    stacked: int) -> jnp.ndarray:
    """Reshape a per-slice ratio so it broadcasts against ``like``."""
    n = int(stacked)
    if not n:
        return ratio
    return ratio.reshape(like.shape[:n] + (1,) * (like.ndim - n))

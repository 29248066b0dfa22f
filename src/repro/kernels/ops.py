"""Jit-ready public wrappers around the Pallas kernels.

Responsibilities:
  * expose the whole-pytree packed LARS phases (`lars_norms_packed`,
    `lars_apply_packed`) over the superbuffer layout built by
    :mod:`repro.core.packing` — 2 kernel launches per optimizer step
    total, independent of leaf count;
  * keep the historical per-leaf entry points (`lars_norms`,
    `lars_apply`) as thin adapters over the same flat kernels for the
    kernel sweeps/benchmarks — a single leaf is just a one-segment
    layout;
  * pick interpret mode (CPU container -> interpret=True; real TPU ->
    compiled kernel);
  * expose the same signatures as :mod:`repro.kernels.ref` so callers
    can swap implementations freely.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import packing
from repro.distributed.constrain import current_mesh
from repro.kernels import lars_kernels, flash_decode as fd

LANE = packing.LANE      # packed lane dim (multiple of 128)
BM = packing.BLOCK_ROWS  # sublane rows per block


# one interpret policy for every kernel in the package (TPU compiles;
# CPU/GPU run the interpreter)
_interpret = fd.default_interpret


def resolve_use_pallas(mode: bool | str) -> bool:
    """Backend-aware dispatch for the optimizer megakernels.

    ``"auto"`` (the :func:`repro.core.lars` default) selects the
    compiled Pallas path only where it actually compiles — the TPU
    backend. On CPU/GPU the kernels run through the Pallas interpreter,
    about 100x slower than the fused jnp engine, so "auto" resolves to
    the jnp path there —
    the same policy :func:`flash_decode` applies via its ``interpret``
    default. ``True``/``False`` force one path (kernel tests and
    benchmarks pin the interpreter explicitly).
    """
    if mode == "auto":
        return jax.default_backend() == "tpu"
    return bool(mode)


def _per_device(kernel, *args):
    """Run ``kernel`` on replicated operands under the ambient mesh.

    Mosaic kernels have no partitioning rule, so a multi-device mesh
    cannot hand them to GSPMD. The packed substrate pins the replicated
    layout's superbuffers replicated (``packing.constrain_rows``), so each
    device runs the kernel on its own full copy inside ``shard_map`` —
    the replicated data-parallel update, kept on the megakernels.
    """
    mesh = current_mesh()
    if mesh is None or math.prod(mesh.shape.values()) == 1:
        return kernel(*args)
    return jax.shard_map(kernel, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


# ------------------------------------------------------------ packed kernels

def lars_norms_packed(layout: packing.PackedLayout, wbuf: jnp.ndarray,
                      gbuf: jnp.ndarray
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Joint per-layer-slice (||w||, ||g||) over the whole superbuffer.

    ONE Pallas launch (per-block partial sums) + a static segment fold.
    Returns two (num_slices,) f32 vectors.
    """
    wsq_blk, gsq_blk = _per_device(
        functools.partial(lars_kernels.norms_flat,
                          block_rows=layout.block_rows,
                          interpret=_interpret()), wbuf, gbuf)
    ids = packing.block_slice_ids(layout)
    wsq = jax.ops.segment_sum(wsq_blk, ids, num_segments=layout.num_slices,
                              indices_are_sorted=True)
    gsq = jax.ops.segment_sum(gsq_blk, ids, num_segments=layout.num_slices,
                              indices_are_sorted=True)
    return jnp.sqrt(wsq), jnp.sqrt(gsq)


def lars_apply_packed(layout: packing.PackedLayout, wbuf: jnp.ndarray,
                      gbuf: jnp.ndarray, mbuf: jnp.ndarray,
                      lr_slices: jnp.ndarray, *, momentum: float,
                      weight_decay: float
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused m' = mu*m + lr_l*(g + beta*w); w' = w - m' over the whole
    superbuffer. lr_slices: (num_slices,) per-layer local LR. ONE launch.
    """
    lr_blocks = packing.blocks_expand(layout,
                                      lr_slices.astype(jnp.float32))
    return _per_device(
        functools.partial(lars_kernels.apply_flat, momentum=momentum,
                          weight_decay=weight_decay,
                          block_rows=layout.block_rows,
                          interpret=_interpret()),
        wbuf, gbuf, mbuf, lr_blocks)


def lars_apply_packed_q8(layout: packing.PackedLayout, wbuf: jnp.ndarray,
                         gbuf: jnp.ndarray, q_m: jnp.ndarray,
                         m_scale: jnp.ndarray, lr_slices: jnp.ndarray, *,
                         momentum: float, weight_decay: float
                         ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``lars_apply_packed`` with int8 momentum codes + per-block scales:
    the dequant-update-requant chain fused into the ONE apply launch.
    Returns (w_new, q_new, scale_new)."""
    lr_blocks = packing.blocks_expand(layout,
                                      lr_slices.astype(jnp.float32))
    return _per_device(
        functools.partial(lars_kernels.apply_flat_q8, momentum=momentum,
                          weight_decay=weight_decay,
                          block_rows=layout.block_rows,
                          interpret=_interpret()),
        wbuf, gbuf, q_m, m_scale, lr_blocks)


# ----------------------------------------------------- per-leaf adapters

def _leaf_layout(x: jnp.ndarray, stacked: bool) -> packing.PackedLayout:
    return packing.build_layout({"x": x}, {"x": stacked})


def lars_norms(w: jnp.ndarray, g: jnp.ndarray, *, stacked: bool = False
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Joint (||w||, ||g||); () or (L,) f32. Pallas-fused single pass."""
    layout = _leaf_layout(w, stacked)
    w_norm, g_norm = lars_norms_packed(layout, packing.pack(layout, {"x": w}),
                                       packing.pack(layout, {"x": g}))
    if not stacked:
        return w_norm[0], g_norm[0]
    return w_norm, g_norm


def lars_apply(w: jnp.ndarray, g: jnp.ndarray, m: jnp.ndarray, *,
               local_lr, momentum: float, weight_decay: float
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused m' = mu*m + lr*(g + wd*w); w' = w - m'.

    ``local_lr``: scalar (unstacked leaf) or (L,) vector (stacked leaf —
    detected from its shape).
    """
    lr = jnp.asarray(local_lr, jnp.float32)
    # A (L>1,) lr vector implies a stacked leaf. (L==1 packs identically
    # either way, so size-based inference is exact.)
    stacked = bool(lr.size > 1)
    layout = _leaf_layout(w, stacked)
    lr_slices = jnp.broadcast_to(lr.reshape(-1), (layout.num_slices,))
    w_new, m_new = lars_apply_packed(
        layout, packing.pack(layout, {"x": w}),
        packing.pack(layout, {"x": g}), packing.pack(layout, {"x": m}),
        lr_slices, momentum=momentum, weight_decay=weight_decay)
    return (packing.unpack(layout, w_new)["x"],
            packing.unpack(layout, m_new, dtype=jnp.float32)["x"])


def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 lengths: jnp.ndarray, *, scale: float | None = None,
                 block_size: int = 512,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Single-token decode attention. q (B,H,D); k/v (B,S,Hkv,D);
    lengths (B,) int32. Returns (B,H,D).

    ``interpret`` defaults to backend auto-selection (TPU compiles the
    Mosaic kernel; CPU/GPU interpret); tests pass an explicit override
    to pin one mode.
    """
    B, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    G = H // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    bs = min(block_size, S)
    if S % bs != 0:  # pad cache tail; masked out by lengths
        pad = bs - S % bs
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q4 = q.reshape(B, Hkv, G, D)
    out4 = fd.flash_decode_grouped(q4, k, v, lengths.astype(jnp.int32),
                                   scale=scale, bs=bs, interpret=interpret)
    return out4.reshape(B, H, D)

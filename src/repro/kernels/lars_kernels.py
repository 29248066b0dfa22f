"""Pallas TPU megakernels for the LARS update's two memory-bound phases.

The SystemML implementation of LARS pays ~5 full HBM passes per parameter
per step (read w,g for ||w||; read g for ||g||; read w,g,m + write m for
the momentum update; read w,m + write w for the apply) — and it pays the
kernel-dispatch overhead once per LAYER per step (the paper's §6
bottleneck). An earlier port of these kernels still launched per *leaf*.

Both axes are now collapsed: the optimizer packs the ENTIRE parameter
pytree into one ``(total_rows, lane)`` superbuffer
(:mod:`repro.core.packing`) and each phase below runs as a single
``pallas_call`` with a 1-D grid over row tiles — 2 launches per step
total, independent of the number of parameter leaves or layers:

  * ``norms_flat``  — ONE joint pass producing per-row-group partial
                      ``(sum w^2, sum g^2)`` f32 sums; the caller folds
                      groups into per-layer-slice sums with a static
                      ``segment_sum`` (layer slices are group-aligned).
  * ``apply_flat``  — ONE read-modify-write pass computing
                      ``m' = mu*m + lr_grp*(g + beta*w); w' = w - m'``
                      with the per-layer local LR delivered as one scalar
                      per row group.

Two row granularities:

  * the GROUP (``block_rows`` = :data:`repro.core.packing.BLOCK_ROWS`,
    8 rows) is the unit of the layout: layer slices are group-aligned,
    each group has one owner, and it is the int8 scale group;
  * the TILE (:data:`TILE_ROWS`) is what one grid step moves. It holds
    ``TILE_ROWS / block_rows`` groups, so the per-group outputs travel
    as ``(groups, 1)`` columns — blocks Mosaic accepts (second-minor a
    multiple of 8, minor equal to the array's) — and the per-group
    inputs as the same columns in SMEM, read as scalars.
    A tile is a multiple of Mosaic's (32, 128) int8 tile. The grid is
    ``cdiv(rows, TILE_ROWS)``: the last tile may overhang the buffer;
    every result is per group, so the overhang's groups are never
    written back.

At 256 x 512 f32 a tile is 512 KB per operand; ``apply_flat``'s five
streamed operands, double-buffered, take ~5 MB of VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import Q8_LEVELS


TILE_ROWS = 256   # rows per grid step: 32 groups of 8, 8 int8 (32, 128) tiles


def _tiling(R: int, block_rows: int) -> tuple[int, int]:
    """(rows per grid step, grid size) for an (R, C) superbuffer: one
    whole-array step when it fits, else ``TILE_ROWS`` steps."""
    assert R % block_rows == 0, (R, block_rows)
    assert TILE_ROWS % block_rows == 0, (TILE_ROWS, block_rows)
    tile = min(TILE_ROWS, R)
    return tile, pl.cdiv(R, tile)


_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel",))


# --------------------------------------------------------------------- norms

def _norms_kernel(w_ref, g_ref, wsq_ref, gsq_ref, *, block_rows: int):
    def group_sumsq(x_ref):
        x = x_ref[...].astype(jnp.float32)
        x = x.reshape(-1, block_rows, x.shape[-1])
        return jnp.sum(jnp.sum(x * x, axis=1), axis=1, keepdims=True)

    wsq_ref[...] = group_sumsq(w_ref)
    gsq_ref[...] = group_sumsq(g_ref)


def norms_flat(w2: jnp.ndarray, g2: jnp.ndarray, *, block_rows: int = 8,
               interpret: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row-group (sum w^2, sum g^2) over a packed (R, C) pair.

    Returns two (R // block_rows,) f32 vectors — one partial sum per
    group. One kernel launch regardless of how many leaves/layers are
    packed into the buffer.
    """
    R, C = w2.shape
    tile, steps = _tiling(R, block_rows)
    ngrp, tgrp = R // block_rows, tile // block_rows
    in_spec = pl.BlockSpec((tile, C), lambda i: (i, 0))
    out_spec = pl.BlockSpec((tgrp, 1), lambda i: (i, 0))
    wsq, gsq = pl.pallas_call(
        functools.partial(_norms_kernel, block_rows=block_rows),
        grid=(steps,),
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((ngrp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((ngrp, 1), jnp.float32)],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="lars_norms",
    )(w2, g2)
    return wsq[:, 0], gsq[:, 0]


# --------------------------------------------------------------------- apply

def _apply_kernel(lr_ref, w_ref, g_ref, m_ref, wout_ref, mout_ref, *,
                  momentum: float, weight_decay: float, block_rows: int):
    # one static iteration per group: the group's LR is a (1, 1) slice of
    # the (groups, 1) column, broadcast over its (block_rows, C) rows
    for t in range(lr_ref.shape[0]):
        rows = pl.ds(t * block_rows, block_rows)
        lr = lr_ref[t, 0]
        wf = w_ref[rows, :].astype(jnp.float32)
        gf = g_ref[rows, :].astype(jnp.float32)
        m_new = momentum * m_ref[rows, :] + lr * (gf + weight_decay * wf)
        wout_ref[rows, :] = (wf - m_new).astype(wout_ref.dtype)
        mout_ref[rows, :] = m_new


def apply_flat(w2: jnp.ndarray, g2: jnp.ndarray, m2: jnp.ndarray,
               lr_blocks: jnp.ndarray, *, momentum: float,
               weight_decay: float, block_rows: int = 8,
               interpret: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused momentum+decay+apply over a packed (R, C) superbuffer.

    lr_blocks: (R // block_rows, 1) f32 — the per-layer local learning
    rate gamma_t * lambda_l, pre-broadcast to one scalar per row group
    (layer slices are group-aligned, so each group has a single owner).
    Returns (w_new (R, C) in w2.dtype, m_new (R, C) f32). One launch.
    """
    R, C = w2.shape
    tile, steps = _tiling(R, block_rows)
    ngrp, tgrp = R // block_rows, tile // block_rows
    assert lr_blocks.shape == (ngrp, 1), (lr_blocks.shape, ngrp)
    blk = pl.BlockSpec((tile, C), lambda i: (i, 0))
    col = pl.BlockSpec((tgrp, 1), lambda i: (i, 0),
                       memory_space=pltpu.SMEM)
    kern = functools.partial(_apply_kernel, momentum=momentum,
                             weight_decay=weight_decay,
                             block_rows=block_rows)
    w_new, m_new = pl.pallas_call(
        kern,
        grid=(steps,),
        in_specs=[col, blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((R, C), w2.dtype),
                   jax.ShapeDtypeStruct((R, C), jnp.float32)],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="lars_apply",
    )(lr_blocks, w2, g2, m2)
    return w_new, m_new


# ------------------------------------------------------------ int8 apply

def _apply_q8_kernel(lr_ref, scale_ref, w_ref, g_ref, q_ref, wout_ref,
                     qout_ref, sout_ref, codes_scr, *, momentum: float,
                     weight_decay: float, block_rows: int):
    # the int8 tile is dequantized whole (int8 rows are packed 32 to a
    # tile, so an 8-row group is not addressable in place); groups then
    # slice the f32 value and their codes collect in an f32 scratch that
    # is requantized to int8 in one store
    q = q_ref[...].astype(jnp.float32)
    for t in range(lr_ref.shape[0]):
        lo = t * block_rows
        rows = pl.ds(lo, block_rows)
        lr = lr_ref[t, 0]
        wf = w_ref[rows, :].astype(jnp.float32)
        gf = g_ref[rows, :].astype(jnp.float32)
        # dequantize the group with its scale, update, then requantize
        # against the group's fresh absmax — the f32 momentum exists
        # only in VMEM, never in HBM
        m = q[lo:lo + block_rows] * scale_ref[t, 0]
        m_new = momentum * m + lr * (gf + weight_decay * wf)
        wout_ref[rows, :] = (wf - m_new).astype(wout_ref.dtype)
        amax = jnp.max(jnp.max(jnp.abs(m_new), axis=1, keepdims=True),
                       axis=0, keepdims=True)
        # (1, 1) -> (block_rows, 1) before the select, so each broadcast
        # runs along one axis (Mosaic has no sublane+lane broadcast)
        amax = jnp.broadcast_to(amax, (block_rows, 1))
        s_new = jnp.where(amax > 0.0, amax / Q8_LEVELS, 1.0)
        codes_scr[rows, :] = jnp.clip(jnp.round(m_new / s_new),
                                      -Q8_LEVELS, Q8_LEVELS)
        sout_ref[pl.ds(t, 1), :] = s_new[:1]
    qout_ref[...] = codes_scr[...].astype(jnp.int32).astype(jnp.int8)


def apply_flat_q8(w2: jnp.ndarray, g2: jnp.ndarray, q2: jnp.ndarray,
                  scale: jnp.ndarray, lr_blocks: jnp.ndarray, *,
                  momentum: float, weight_decay: float,
                  block_rows: int = 8, interpret: bool = True
                  ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``apply_flat`` with the momentum stored as int8 codes + per-group
    f32 scales: dequant-update-requant fused into the one launch.

    q2: (R, C) int8 momentum codes; scale: (R // block_rows, 1) f32
    per-group scales (the quantization groups of
    :func:`repro.core.packing.quantize_q8`). Returns (w_new (R, C) in
    w2.dtype, q_new (R, C) int8, scale_new (R // block_rows, 1) f32).
    Numerically identical to dequantizing, running ``apply_flat``, and
    requantizing — the amax reduction and round/clip are the same ops at
    the same f32 precision.
    """
    R, C = w2.shape
    tile, steps = _tiling(R, block_rows)
    ngrp, tgrp = R // block_rows, tile // block_rows
    assert q2.shape == (R, C) and q2.dtype == jnp.int8, (q2.shape, q2.dtype)
    assert scale.shape == (ngrp, 1), (scale.shape, ngrp)
    assert lr_blocks.shape == (ngrp, 1), (lr_blocks.shape, ngrp)
    blk = pl.BlockSpec((tile, C), lambda i: (i, 0))
    col = pl.BlockSpec((tgrp, 1), lambda i: (i, 0))
    smem = pl.BlockSpec((tgrp, 1), lambda i: (i, 0),
                        memory_space=pltpu.SMEM)
    kern = functools.partial(_apply_q8_kernel, momentum=momentum,
                             weight_decay=weight_decay,
                             block_rows=block_rows)
    w_new, q_new, s_new = pl.pallas_call(
        kern,
        grid=(steps,),
        in_specs=[smem, smem, blk, blk, blk],
        out_specs=[blk, blk, col],
        out_shape=[jax.ShapeDtypeStruct((R, C), w2.dtype),
                   jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((ngrp, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tile, C), jnp.float32)],
        compiler_params=_PARALLEL,
        interpret=interpret,
        name="lars_apply_q8",
    )(lr_blocks, scale, w2, g2, q2)
    return w_new, q_new, s_new

"""Shared layer-wise optimizer substrate for SGD / LARS / LAMB / AdamW.

Design notes
------------
* Pure-JAX, optax-free (the container ships no optax, and the point of the
  repo is the optimizer *as the paper's contribution*).
* ``Optimizer.init(params, stacked=None) -> OptState``;
  ``Optimizer.update(grads, state, params, stacked=None) ->
  (new_params, new_state)``. The update is a single jit-able function of
  pytrees; the step counter lives in the state so LR schedules are pure.
* ``stacked``: a pytree mirroring ``params`` of counts of leading slice
  axes. ``True`` (1) marks a parameter whose leading axis stacks layers
  for ``lax.scan`` (shape ``(L, ...)``); 2 marks routed experts stacked
  ``(L, E, ...)``. Layer-wise optimizers (LARS/LAMB) compute their trust
  ratios *per slice* for such tensors, otherwise the "layer-wise"
  semantics of the paper silently degrade to "whole-stack-wise".

The LayerwiseRule abstraction
-----------------------------
You et al.'s LARS (1708.03888) and LAMB (1904.00962) are the *same*
trust-ratio family differing only in the per-layer direction; SGD and
AdamW are the degenerate members with trust ratio 1. A
:class:`LayerwiseRule` captures exactly that factorization:

* ``direction(ctx, g, w, slots)`` — elementwise: the tensor whose norm
  feeds the trust ratio, plus any slot updates that precede it;
* ``trust(ctx, w_norm, u_norm)`` — the per-layer local-LR ratio
  (``None`` for non-layer-wise rules);
* ``apply(ctx, w, g, u, local_lr, slots)`` — elementwise: fold the local
  LR into the weight (and remaining slot) update.

Because every piece is elementwise or a per-layer scalar, ONE rule runs
on two interchangeable engines:

* the **tree engine** (``init(params)`` with no marker): slots mirror the
  param pytree leaf-for-leaf; per-leaf norms. This is the jnp reference
  path and the pjit/sharded fallback — XLA inserts the cross-shard
  reductions for the norms.
* the **flat-packed engine** (``init(params, stacked=marker)``): the whole
  pytree lives in one ``(rows, lane)`` superbuffer
  (:mod:`repro.core.packing`); slots stay packed across steps; norms are
  one segment-reduced pass; the LARS Pallas fast path issues exactly two
  kernel launches per step regardless of leaf count.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import phases
from repro.core import packing
from repro.core import trust_ratio as tr

Pytree = Any
Schedule = Callable[[jnp.ndarray], jnp.ndarray]  # step -> learning rate

tree_map = jax.tree_util.tree_map

# Optimizer-state storage dtypes. "int8" stores every rule slot as int8
# codes + per-group f32 scales (packed engine: per-row-block inside the
# superbuffer; tree engine: per-leading-index per leaf) with
# dequantize-on-read / quantize-on-write around the SAME rule functions,
# so all four optimizers inherit 8-bit states from the substrate. The
# master/weight buffers (MASTER_SLOT / WEIGHT_SLOT) always stay f32 —
# quantizing weights would change trajectories, quantizing moments only
# perturbs them.
SLOT_DTYPES = ("f32", "int8")

# Suffix of the per-group f32 scale slot paired with each int8 code slot
# ("momentum" -> "momentum_scale"). A plain sibling key keeps the scales
# visible to the generic slot machinery: npz checkpoints round-trip them
# by name, sharding specs cover them, and shape mismatches fail loudly.
SCALE_SUFFIX = "_scale"


class PackedGrads(NamedTuple):
    """Mean gradients already living in the (rows, lane) superbuffer.

    :class:`~repro.train.pipeline.TrainPipeline`'s fused accumulation
    epilogue accumulates microbatch gradients directly in packed form and
    hands the result to ``Optimizer.update`` wrapped in this type; the
    packed engine then skips its per-step gradient pack (and the Adam
    family's separate grad^2 pack) and takes the trust-ratio norms from
    the accumulated buffer in place.
    """

    buf: jnp.ndarray


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["step", "slots"], meta_fields=["layout"])
@dataclasses.dataclass
class OptState:
    """Generic optimizer state: step counter + per-rule slot buffers.

    Tree layout (``layout is None``): each slot is a pytree mirroring
    params. Packed layout: each slot is a ``(rows, lane)`` f32 superbuffer
    and ``layout`` carries the static :class:`~repro.core.packing.
    PackedLayout` (pytree *metadata*, not a traced leaf)."""

    step: jnp.ndarray                      # scalar int32
    slots: dict[str, Pytree]               # e.g. {"momentum": ...}
    layout: Optional[packing.PackedLayout] = None


@dataclasses.dataclass(frozen=True)
class LayerwiseRule:
    """One optimizer of the layer-wise trust-ratio family.

    All callables are elementwise over arbitrarily-shaped f32 arrays (a
    single leaf on the tree engine, the whole superbuffer on the packed
    engine); ``trust`` maps per-layer norm scalars/vectors to ratios.
    """

    name: str
    slots: tuple[str, ...]
    # (ctx, g, w, slots) -> (u, slots'): the trust-ratio norm operand.
    direction: Callable[..., tuple[jnp.ndarray, dict]]
    # (ctx, w, g, u, local_lr, slots) -> (w_new, slots')
    apply: Callable[..., tuple[jnp.ndarray, dict]]
    # (ctx, w_norm, u_norm) -> per-layer ratio; None = always 1.
    trust: Optional[Callable[..., jnp.ndarray]] = None
    # step (int32 scalar) -> dict of step-dependent scalars.
    prepare: Optional[Callable[[jnp.ndarray], dict]] = None
    # rank<=1 slices (biases, norm scales) keep trust ratio 1.
    skip_adaptation_1d: bool = True
    # True when ``direction`` returns the raw gradient untouched (LARS):
    # the packed engine may then take the trust-operand norms from the
    # unpacked gradient tree (per-leaf reductions that fuse with the
    # gradient pack) instead of a second full pass over the superbuffer.
    trust_operand_is_grad: bool = False
    # True when ``direction`` consumes g^2 as well as g (Adam family).
    # The packed engine then supplies ``ctx["grad_sq"]`` as a SECOND
    # packed buffer (squares packed from the tree): each concat has one
    # consumer, so XLA:CPU fuses both packs into the moment updates
    # instead of materializing a shared gradient buffer read twice.
    needs_grad_sq: bool = False
    # Optional Pallas megakernel overrides for the packed engine (used
    # when the optimizer is built with use_pallas=True). The engine owns
    # trust/adapt-mask logic either way; these swap only the two
    # memory-bound passes.
    # (layout, wbuf, ubuf) -> (w_norm, u_norm) per slice:
    packed_norms: Optional[Callable[..., tuple]] = None
    # (ctx, layout, wbuf, gbuf, ubuf, lr_slices, slots) -> (wbuf', slots'):
    packed_apply: Optional[Callable[..., tuple[jnp.ndarray, dict]]] = None
    # int8-state Pallas override: same signature as packed_apply but
    # ``slots`` holds RAW int8 codes + per-block scales (keys ``k`` and
    # ``k + SCALE_SUFFIX``) and the returned slots are requantized
    # in-kernel (dequant-update-requant in one launch, so the f32 slot
    # buffer never materializes in HBM). Only valid for rules whose
    # ``direction`` ignores its slots (trust_operand_is_grad family).
    packed_apply_q8: Optional[Callable[..., tuple[jnp.ndarray, dict]]] = None


@dataclasses.dataclass(frozen=True)
class Optimizer:
    """A named pair of pure functions (init, update)."""

    name: str
    init: Callable[..., OptState]
    update: Callable[..., tuple[Pytree, OptState]]
    # Hyperparameters for introspection / experiment logging.
    hyperparams: dict[str, Any] = dataclasses.field(default_factory=dict)

    def __repr__(self) -> str:  # keep experiment logs readable
        hp = ", ".join(f"{k}={v}" for k, v in self.hyperparams.items()
                       if not callable(v))
        return f"Optimizer({self.name}, {hp})"


# ------------------------------------------------------------------ engines

def _tree_update(rule: LayerwiseRule, lr, ctx: dict, grads: Pytree,
                 slots: dict[str, Pytree], params: Pytree,
                 stacked_full: Pytree,
                 master: Optional[Pytree] = None) -> tuple[Pytree, dict]:
    """Per-leaf reference engine (pjit/sharded fallback).

    ``master``: optional f32 weight pytree (the bf16 precision policy's
    master copy). When given, the update reads/writes the master and the
    returned params are the master cast down to each leaf's storage
    dtype; the new master rides along in the slot dict.
    """
    n_rule = len(rule.slots)

    def leaf(g, w, s: int, *extra):
        sl = dict(zip(rule.slots, extra[:n_rule]))
        gf = g.astype(jnp.float32)
        wf = extra[n_rule] if master is not None else w.astype(jnp.float32)
        u, sl = rule.direction(ctx, gf, wf, sl)
        local_lr = lr
        if rule.trust is not None and not (
                rule.skip_adaptation_1d and tr.effective_rank(w, s) <= 1):
            w_norm, u_norm = tr.layer_norms(wf, u, s)
            ratio = rule.trust(ctx, w_norm, u_norm)
            local_lr = lr * tr.broadcast_ratio(ratio, wf, s)
        w_new, sl = rule.apply(ctx, wf, gf, u, local_lr, sl)
        out = (w_new.astype(w.dtype),) + tuple(sl[k] for k in rule.slots)
        if master is not None:
            out += (w_new,)
        return out

    extras = [slots[k] for k in rule.slots]
    if master is not None:
        extras.append(master)
    packs = tree_map(leaf, grads, params, stacked_full, *extras)
    is_tup = lambda t: isinstance(t, tuple)  # noqa: E731
    new_params = tree_map(lambda t: t[0], packs, is_leaf=is_tup)
    new_slots = {k: tree_map(lambda t, i=i + 1: t[i], packs, is_leaf=is_tup)
                 for i, k in enumerate(rule.slots)}
    if master is not None:
        new_slots[packing.MASTER_SLOT] = tree_map(
            lambda t: t[n_rule + 1], packs, is_leaf=is_tup)
    return new_params, new_slots


def _packed_update(rule: LayerwiseRule, layout: packing.PackedLayout, lr,
                   ctx: dict, grads: Pytree, slots: dict, params: Pytree,
                   use_pallas: bool,
                   master: Optional[jnp.ndarray] = None,
                   weights: Optional[jnp.ndarray] = None,
                   slot_dtype: str = "f32") -> tuple[Pytree, dict]:
    """Flat-packed engine: whole-pytree buffers, per-slice scalars.

    ``use_pallas`` swaps the norms/apply passes for the rule's
    megakernels; the trust-ratio and adaptation-mask logic is computed
    here either way, so the two paths cannot drift.

    ``grads`` may arrive as a param-shaped pytree OR as
    :class:`PackedGrads` (the fused accumulation epilogue): the latter
    skips the per-step gradient pack, takes the Adam family's g^2 from
    the buffer directly, and reads the LARS trust norms off the
    accumulated superbuffer in place.

    ``master``: optional f32 master-weight superbuffer. When given, the
    per-step params pack is skipped — the master IS the weight buffer —
    and the updated master is returned in the slot dict; params come back
    as the unpacked (storage-dtype) view of the new master.

    ``weights``: optional persistent packed weight buffer (the no-master
    counterpart, ``WEIGHT_SLOT``). Also skips the per-step params pack,
    but the updated buffer is quantized through each segment's storage
    dtype so trajectories stay bit-identical to repacking every step.
    Only one of ``master`` / ``weights`` may be given.

    ``slot_dtype="int8"`` dequantizes the rule slots (int8 codes +
    per-block scales) to f32 on entry and requantizes the updated slots
    on exit — unless the rule provides ``packed_apply_q8`` under
    ``use_pallas``, in which case the raw codes go straight into the
    fused dequant-update-requant kernel.
    """
    if master is not None:
        wbuf = master
    elif weights is not None:
        wbuf = weights
    else:
        wbuf = packing.pack(layout, params)
    packed_grads = isinstance(grads, PackedGrads)
    with jax.named_scope(phases.GRAD_PACK):
        gbuf = grads.buf if packed_grads else packing.pack(layout, grads)
        if rule.needs_grad_sq:
            # square in f32 (pack would cast AFTER the square, and a bf16
            # square then diverges from the tree engine's f32 one).
            # Squaring the packed buffer is elementwise-identical (0^2 ==
            # 0 in the padding), so the fused path needs no second pack.
            ctx = dict(ctx, grad_sq=jnp.square(gbuf) if packed_grads
                       else packing.pack(layout, tree_map(
                           lambda g: jnp.square(g.astype(jnp.float32)),
                           grads)))
    quant = slot_dtype == "int8"
    q8_kernel = quant and use_pallas and rule.packed_apply_q8 is not None
    if quant:
        # dequantize-on-read; the q8 kernel path instead consumes raw
        # codes (its rules' direction ignores slots by contract)
        f32_slots = {} if q8_kernel else {
            k: packing.dequantize_q8(layout, slots[k],
                                     slots[k + SCALE_SUFFIX])
            for k in rule.slots}
    else:
        f32_slots = dict(slots)
    u, f32_slots = rule.direction(ctx, gbuf, wbuf, f32_slots)
    ratio = None
    if rule.trust is not None:
        with jax.named_scope(phases.LARS_NORMS):
            if use_pallas and rule.packed_norms is not None:
                w_norm, u_norm = rule.packed_norms(layout, wbuf, u)
            elif rule.trust_operand_is_grad:
                w_norm = jnp.sqrt(packing.slice_sumsq(layout, wbuf))
                # fused path: ||sum_i g_i|| must be taken on the
                # ACCUMULATED buffer (cross terms make it impossible to
                # accumulate from per-microbatch norms); the tree path
                # keeps the per-leaf reductions that fuse with the
                # gradient pack
                u_norm = jnp.sqrt(packing.slice_sumsq(layout, gbuf)) \
                    if packed_grads \
                    else jnp.sqrt(packing.tree_slice_sumsq(layout, grads))
            else:
                w_norm, u_norm = packing.slice_norms(layout, wbuf, u)
        with jax.named_scope(phases.TRUST_RATIO):
            ratio = rule.trust(ctx, w_norm, u_norm)
            if rule.skip_adaptation_1d:
                ratio = jnp.where(packing.adapt_mask(layout), ratio, 1.0)
    with jax.named_scope(phases.LARS_APPLY):
        if use_pallas and (q8_kernel or rule.packed_apply is not None):
            ones = jnp.ones((layout.num_slices,), jnp.float32)
            lr_slices = lr * (ratio if ratio is not None else ones)
            if q8_kernel:
                wbuf2, new_slots = rule.packed_apply_q8(
                    ctx, layout, wbuf, gbuf, u, lr_slices, slots)
            else:
                wbuf2, new_slots = rule.packed_apply(
                    ctx, layout, wbuf, gbuf, u, lr_slices, f32_slots)
        else:
            local_lr = lr if ratio is None \
                else lr * packing.rows_expand(layout, ratio)
            wbuf2, new_slots = rule.apply(ctx, wbuf, gbuf, u, local_lr,
                                          f32_slots)
    if quant and not q8_kernel:
        # quantize-on-write: each updated rule slot back to codes+scales
        for k in rule.slots:
            q, s = packing.quantize_q8(layout, new_slots[k])
            new_slots[k] = q
            new_slots[k + SCALE_SUFFIX] = s
    if master is not None:
        new_slots[packing.MASTER_SLOT] = wbuf2
    else:
        wbuf2 = packing.quantize_to_storage(layout, wbuf2)
        if weights is not None:
            new_slots[packing.WEIGHT_SLOT] = wbuf2
    if layout.shards > 1:
        # the ZeRO step's one params all-gather: the locally-updated
        # weight rows leave the shard domain exactly once, here; every
        # slot (including the master / persistent weight buffer) stays
        # row-sharded across steps
        wbuf2 = packing.gather_rows(layout, wbuf2)
    with jax.named_scope(phases.PARAM_UNPACK):
        new_params = packing.unpack(layout, wbuf2)
    return new_params, new_slots


def make_optimizer(rule: LayerwiseRule, learning_rate: float | Schedule, *,
                   use_pallas: bool | str = False,
                   slot_dtype: str = "f32",
                   hyperparams: Optional[dict] = None) -> Optimizer:
    """Build an :class:`Optimizer` from a rule (the ONLY update body —
    individual optimizers supply ~20-line rules, not engines).

    ``use_pallas="auto"`` resolves per backend (compiled megakernels on
    TPU, the jnp engine elsewhere — interpret-mode Pallas on CPU is
    ~100x slower than the fused jnp path);
    ``True``/``False`` force one path (tests, benchmarks).

    ``slot_dtype="int8"`` stores every rule slot as int8 codes + f32
    group scales (see :data:`SLOT_DTYPES`); the engines dequantize on
    read and requantize on write, so the rule functions never see codes.
    """
    lr_fn = as_schedule(learning_rate)
    if slot_dtype not in SLOT_DTYPES:
        raise ValueError(f"unknown slot_dtype {slot_dtype!r}; "
                         f"have {SLOT_DTYPES}")
    if use_pallas == "auto":
        from repro.kernels import ops as kops
        use_pallas = kops.resolve_use_pallas(use_pallas)
    quant = slot_dtype == "int8"

    def init(params: Pytree, stacked: Optional[Pytree] = None,
             master: bool = False, zero_shards: int = 1) -> OptState:
        step = jnp.zeros((), jnp.int32)
        if stacked is None:
            if zero_shards > 1:
                raise ValueError(
                    "zero_shards > 1 requires the flat-packed layout: "
                    "init(params, stacked=marker). The tree layout "
                    "already shards leaf-for-leaf under pjit.")
            slots = {}
            for k in rule.slots:
                if quant:
                    # quantized zeros: 0 codes, unit scales (the amax==0
                    # guard) — exactly what requantizing f32 zeros gives,
                    # so slot shapes/dtypes are stable from step 0
                    packs = tree_map(
                        lambda p: packing.quantize_leaf_q8(
                            jnp.zeros(p.shape, jnp.float32)), params)
                    slots[k], slots[k + SCALE_SUFFIX] = \
                        _split_pair_tree(packs)
                else:
                    slots[k] = zeros_like_tree(params)
            if master:
                slots[packing.MASTER_SLOT] = tree_map(
                    lambda p: p.astype(jnp.float32), params)
            return OptState(step=step, slots=slots)
        # zero_shards > 1: ZeRO row-sharded layout — rows padded to a
        # multiple of shards * block_rows so every slot buffer splits
        # evenly across the mesh data axis (see packing.PackedLayout)
        layout = packing.build_layout(
            params, normalize_stacked(params, stacked),
            shards=int(zero_shards))
        zeros = functools.partial(jnp.zeros, layout.buffer_shape,
                                  jnp.float32)
        slots = {}
        for k in rule.slots:
            if quant:
                slots[k], slots[k + SCALE_SUFFIX] = \
                    packing.quantize_q8(layout, zeros())
            else:
                slots[k] = zeros()
        if master:
            slots[packing.MASTER_SLOT] = packing.init_master(layout, params)
        else:
            # weights live packed across steps (the no-master analogue of
            # the master buffer): update() never repacks params, it reads
            # and writes this slot. See packing.WEIGHT_SLOT.
            slots[packing.WEIGHT_SLOT] = packing.pack(layout, params)
        return OptState(step=step, slots=slots, layout=layout)

    def update(grads: Pytree, state: OptState, params: Pytree,
               stacked: Optional[Pytree] = None
               ) -> tuple[Pytree, OptState]:
        lr = lr_fn(state.step).astype(jnp.float32)
        ctx = rule.prepare(state.step) if rule.prepare is not None else {}
        slots = dict(state.slots)
        master = slots.pop(packing.MASTER_SLOT, None)
        weights = slots.pop(packing.WEIGHT_SLOT, None)
        if state.layout is not None:
            if stacked is not None:
                packing.check_marker(state.layout, params, stacked)
            # ZeRO layouts fall back to the jnp engine: pallas_call has
            # no GSPMD partitioning rules, so a megakernel over the
            # row-sharded buffers would force a full gather per step —
            # the exact memory the sharding exists to avoid
            up = use_pallas and state.layout.shards == 1
            new_params, new_slots = _packed_update(
                rule, state.layout, lr, ctx, grads, slots, params,
                up, master=master, weights=weights,
                slot_dtype=slot_dtype)
        else:
            if use_pallas:
                raise ValueError(
                    f"{rule.name}(use_pallas=True) requires the flat-"
                    "packed layout: build the state with init(params, "
                    "stacked=marker). Tree-layout states (init(params)) "
                    "run the per-leaf jnp reference path only.")
            if isinstance(grads, PackedGrads):
                raise ValueError(
                    "PackedGrads requires the flat-packed layout; tree-"
                    "layout states take param-shaped gradient pytrees")
            stacked_full = normalize_stacked(params, stacked)
            if quant:
                slots = {k: tree_map(packing.dequantize_leaf_q8, slots[k],
                                     slots[k + SCALE_SUFFIX])
                         for k in rule.slots}
            new_params, new_slots = _tree_update(
                rule, lr, ctx, grads, slots, params, stacked_full,
                master=master)
            if quant:
                for k in rule.slots:
                    packs = tree_map(packing.quantize_leaf_q8,
                                     new_slots[k])
                    new_slots[k], new_slots[k + SCALE_SUFFIX] = \
                        _split_pair_tree(packs)
        return new_params, OptState(step=state.step + 1, slots=new_slots,
                                    layout=state.layout)

    return Optimizer(name=rule.name, init=init, update=update,
                     hyperparams=dict(hyperparams or {}))


def _split_pair_tree(packs: Pytree) -> tuple[Pytree, Pytree]:
    """Tree of (a, b) tuples -> (tree of a, tree of b)."""
    is_pair = lambda t: isinstance(t, tuple)  # noqa: E731
    return (tree_map(lambda t: t[0], packs, is_leaf=is_pair),
            tree_map(lambda t: t[1], packs, is_leaf=is_pair))


# ------------------------------------------------------------------ helpers

def adam_moments(b1: float, b2: float, eps: float, weight_decay: float
                 ) -> tuple[Callable, Callable]:
    """Shared (prepare, direction) for the Adam family.

    AdamW and LAMB are the same bias-corrected moment update; they differ
    only in the trust ratio applied afterwards (None vs phi(||w||)/||u||).
    """

    def prepare(step):
        t = (step + 1).astype(jnp.float32)
        return {"c1": 1.0 - jnp.power(b1, t), "c2": 1.0 - jnp.power(b2, t)}

    def direction(ctx, g, w, slots):
        mu = b1 * slots["mu"] + (1 - b1) * g
        # grad_sq: packed-engine fusion hint (g^2 packed from the tree,
        # one consumer per concat); elementwise-identical to squaring g.
        gsq = ctx.get("grad_sq")
        nu = b2 * slots["nu"] + (1 - b2) * (
            jnp.square(g) if gsq is None else gsq)
        u = (mu / ctx["c1"]) / (jnp.sqrt(nu / ctx["c2"]) + eps) \
            + weight_decay * w
        return u, {"mu": mu, "nu": nu}

    return prepare, direction


def as_schedule(lr: float | Schedule) -> Schedule:
    """Promote a constant learning rate to a schedule."""
    if callable(lr):
        return lr
    lr = float(lr)
    return lambda step: jnp.asarray(lr, dtype=jnp.float32)


def zeros_like_tree(params: Pytree, dtype=jnp.float32) -> Pytree:
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, dtype=dtype), params)


def normalize_stacked(params: Pytree, stacked: Optional[Pytree]) -> Pytree:
    """Return a full pytree of slice-axis counts mirroring params
    (default: all 0; ``True`` counts as 1)."""
    if stacked is None:
        return jax.tree_util.tree_map(lambda _: 0, params)
    # Broadcast a prefix tree of markers over params.
    return jax.tree_util.tree_map(
        lambda s, p: int(s), stacked, params)


def apply_updates(params: Pytree, updates: Pytree) -> Pytree:
    """w <- w + u, preserving each param's dtype (updates are f32)."""
    return jax.tree_util.tree_map(
        lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype), params, updates)


def tree_cast(tree: Pytree, dtype) -> Pytree:
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def global_norm(tree: Pytree) -> jnp.ndarray:
    """sqrt(sum of squared L2 norms) across a whole pytree (telemetry)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))

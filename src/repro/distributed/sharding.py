"""Logical sharding rules: param pytree -> PartitionSpec pytree.

Scheme (DESIGN.md §6): a 2-D ``(data, model)`` mesh per pod, optionally a
leading ``pod`` axis. Megatron-style tensor parallelism over ``model``
(attention heads / FFN hidden / vocab / experts / SSM channels) combined
with FSDP-style parameter sharding over ``data`` on the remaining large
axis — so params + grads + LARS momentum all scale 1/(data*model) per
device. GSPMD inserts the per-layer weight all-gathers (FSDP) and the
row/column-parallel reductions (Megatron) that these specs imply.

Rules are matched on the leaf's path (module key + leaf name), falling
back to replication; every leaf under a scan-stacked collection
("layers" / "enc_layers" / "dec_layers") gets a leading ``None`` for the
layer axis (layers are never sharded — they are scanned).

The ``pod`` axis is reserved for pure data parallelism: batch shards over
("pod", "data"); params are replicated across pods (gradient all-reduce
spans pods). This keeps inter-pod traffic to one gradient reduction per
step — the paper's Spark "parallel batches" aggregation, at pod scale.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any

STACKED_KEYS = ("layers", "dense_layers", "enc_layers", "dec_layers")

# (module-context, leaf-name) -> spec for the trailing (own) dims.
# "@model" / "@data" mark mesh axes; None = replicated dim.
_ATTN = {
    "wq": ("@data", "@model"), "wk": ("@data", "@model"),
    "wv": ("@data", "@model"), "wo": ("@model", "@data"),
    "bq": ("@model",), "bk": ("@model",), "bv": ("@model",),
    "q_norm": (None,), "k_norm": (None,),
    # --- MLA ---
    "q_down": ("@data", None), "q_up": ("@data", "@model"),
    "kv_down": ("@data", None), "kv_norm": (None,),
    "k_up": ("@data", "@model"), "v_up": ("@data", "@model"),
}
_MLP = {
    "wi": ("@data", "@model"), "wg": ("@data", "@model"),
    "wo": ("@model", "@data"),
}
_MOE = {
    "router": (None, None),                       # (d, E) small, replicated
    "wi": ("@model", "@data", None),              # (E, d, ff): experts on TP
    "wg": ("@model", "@data", None),
    "wo": ("@model", None, "@data"),
}
# expert-count not divisible by the model axis (granite: 40 experts on a
# 16-way axis) -> expert-INTERNAL tensor parallelism instead: each
# expert's FFN is column/row-parallel over `model`, experts replicated
# across it (the naive fallback — replicating the expert matmuls' d
# contraction — costs an all-reduce per expert matmul; see §Perf).
_MOE_TP = {
    "router": (None, None),
    "wi": (None, "@data", "@model"),
    "wg": (None, "@data", "@model"),
    "wo": (None, "@model", "@data"),
}
_SSM = {
    # Megatron pattern: in_proj column-parallel on d_inner, out_proj
    # row-parallel; per-channel tensors follow the d_inner shard.
    "in_proj": ("@data", "@model"),
    "out_proj": ("@model", "@data"),
    "x_proj": ("@model", None),                   # (din, R+2N) row-parallel
    "dt_proj": (None, "@model"),                  # (R, din)
    "conv_w": (None, "@model"),                   # (K, channels)
    "conv_b": ("@model",),
    "dt_bias": ("@model",),
    "A_log": None,                                # mamba1 (din,N) / mamba2 (heads,)
    "D": ("@model",),
    "norm_scale": ("@model",),
}
_TOP = {
    # vocab-parallel ONLY: sharding d over `data` as well makes the token
    # gather's output sharding ambiguous (batch wants `data` from tokens,
    # d wants `data` from the table) and GSPMD resolves it by unsharding
    # the batch — replicating every activation. Embeds stay modest
    # (V*d/16 per device) so pure vocab parallel is the right trade.
    "embed": ("@model", None),
    "unembed": (None, "@model"),
}


def _resolve(entry, shape) -> P:
    if entry is None:
        return P(*([None] * len(shape)))
    assert len(entry) == len(shape), (entry, shape)
    return P(*[e[1:] if isinstance(e, str) and e.startswith("@") else e
               for e in entry])


def _leaf_spec(path, shape, family: str, moe_tp: bool = False) -> P:
    keys = [getattr(p, "key", None) for p in path
            if getattr(p, "key", None) is not None]
    name = keys[-1] if keys else ""
    stacked = any(k in STACKED_KEYS for k in keys)
    own = shape[1:] if stacked else shape

    spec: Optional[P] = None
    ctx = set(keys)
    moe_rules = _MOE_TP if moe_tp else _MOE
    if name in ("embed", "unembed") and len(own) == 2:
        spec = _resolve(_TOP[name], own)
    elif "ssm" in ctx and name in _SSM:
        ent = _SSM[name]
        if name == "A_log":
            # mamba1: (din, N) -> shard din; mamba2: (heads,) -> replicate
            ent = ("@model", None) if len(own) == 2 else (None,)
        if name == "conv_b" and len(own) == 1:
            ent = ("@model",)
        spec = _resolve(ent, own)
    elif "moe" in ctx and name in moe_rules and "shared" not in ctx:
        spec = _resolve(moe_rules[name], own)
    elif ("mlp" in ctx or "shared" in ctx) and name in _MLP:
        spec = _resolve(_MLP[name], own)
    elif name in _ATTN and len(own) == len(_ATTN[name]):
        spec = _resolve(_ATTN[name], own)
    elif name in ("scale", "bias"):                     # norms
        spec = P(*([None] * len(own)))
    elif len(own) == 2 and name in ("wi", "wg", "wo"):  # bare mlp
        spec = _resolve(_MLP[name], own)
    if spec is None:
        spec = P(*([None] * len(own)))                  # replicate fallback

    if stacked:
        return P(None, *spec)
    return spec


def _divisible(spec: P, shape, mesh: Optional[Mesh]) -> P:
    """Drop sharded axes that don't divide their dim (jit in_shardings
    require exact divisibility — e.g. whisper's vocab 51865 on a 16-way
    model axis falls back to replicated)."""
    if mesh is None:
        return spec
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        names = (ax,) if isinstance(ax, str) else tuple(ax)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        out.append(ax if dim % size == 0 else None)
    return P(*out)


def param_pspecs(cfg, params_shapes: Pytree, mesh: Optional[Mesh] = None
                 ) -> Pytree:
    """Shape pytree (real arrays or ShapeDtypeStructs) -> PartitionSpecs."""
    if cfg.family == "cnn":                             # LeNet: replicated
        return jax.tree_util.tree_map(lambda x: P(), params_shapes)
    moe_tp = bool(cfg.num_experts) and mesh is not None \
        and cfg.num_experts % mesh.shape["model"] != 0
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _divisible(
            _leaf_spec(path, leaf.shape, cfg.family, moe_tp),
            leaf.shape, mesh),
        params_shapes)


def serve_param_pspecs(cfg, params_shapes: Pytree,
                       mesh: Optional[Mesh] = None,
                       hbm_budget: float = 12e9) -> Pytree:
    """Serving-mode param specs: pure tensor parallelism.

    FSDP's `data`-axis weight shard is right for training (params +
    grads + momentum amortize the per-layer all-gathers over a huge
    batch) but wrong for decode: ONE token pays a full weight all-gather
    per layer per step. When the TP-only per-device footprint fits the
    HBM budget, drop the `data` axis from every param spec (weights
    replicated across `data`, still sharded over `model`). Models too
    big for pure TP (deepseek-v2: 30 GB/device) keep the training
    sharding. §Perf decode iteration.
    """
    specs = param_pspecs(cfg, params_shapes, mesh)
    if mesh is None:
        return specs
    total = sum(x.size * np.dtype(x.dtype).itemsize
                for x in jax.tree_util.tree_leaves(params_shapes))
    if total / mesh.shape["model"] > hbm_budget:
        return specs

    def strip(spec: P) -> P:
        out = []
        for ax in spec:
            if ax == "data":
                out.append(None)
            elif isinstance(ax, tuple):
                kept = tuple(a for a in ax if a != "data")
                out.append(kept if kept else None)
            else:
                out.append(ax)
        return P(*out)

    return jax.tree_util.tree_map(
        strip, specs, is_leaf=lambda s: isinstance(s, P))


def state_pspecs(cfg, state_shapes, mesh: Optional[Mesh] = None) -> Any:
    """TrainState(params, OptState(step, slots)) -> matching spec tree.

    Tree-layout opt states: slot pytrees mirror params leaf-for-leaf, so
    they inherit the param specs (momentum is sharded exactly like its
    weight). Flat-packed opt states: each slot is one (rows, lane)
    superbuffer whose rows interleave every leaf's shards, so it is kept
    replicated — UNLESS the layout is ZeRO-sharded (``layout.shards >
    1``, built via ``opt.init(..., zero_shards=n)``), in which case
    every packed slot row-shards ``P("data", None)`` across the mesh
    data axis and per-device optimizer-state memory drops to ~1/ndev.
    """
    from repro.train.state import TrainState
    from repro.core.optim_base import OptState
    pspecs = param_pspecs(cfg, state_shapes.params, mesh)
    opt = state_shapes.opt_state
    if getattr(opt, "layout", None) is not None:
        layout = opt.layout
        # generic over slot keys: covers the int8 code buffers and their
        # (num_blocks, 1) scale siblings alongside the f32 superbuffers
        if getattr(layout, "shards", 1) > 1 and mesh is not None \
                and "data" in mesh.axis_names \
                and layout.total_rows % (mesh.shape["data"]
                                         * layout.block_rows) == 0:
            # ZeRO layout: every packed slot row-shards across the data
            # axis. Rows are padded to a multiple of shards * block_rows
            # at build time, so the (num_blocks, 1) scale siblings split
            # on the same block-aligned boundaries and the divisibility
            # check covers both shapes at once.
            slot_specs = {k: P("data", None) for k in opt.slots}
        else:
            slot_specs = {k: P(None, None) for k in opt.slots}
        opt_spec = OptState(step=P(), slots=slot_specs, layout=opt.layout)
    else:
        from repro.core.optim_base import SCALE_SUFFIX
        replicated = jax.tree_util.tree_map(
            lambda _s: P(), pspecs, is_leaf=lambda s: isinstance(s, P))
        # int8 scale trees mirror params structurally but not in shape
        # (one scalar per leading index), so they cannot inherit the
        # param specs — keep them replicated; they are tiny
        slot_specs = {k: (replicated if k.endswith(SCALE_SUFFIX)
                          else pspecs) for k in opt.slots}
        opt_spec = OptState(step=P(), slots=slot_specs)
    return TrainState(params=pspecs, opt_state=opt_spec)


# ----------------------------------------------------------------- batches

def _batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_pspecs(cfg, mesh: Mesh, *, batch: int) -> dict[str, P]:
    """Input-batch PartitionSpecs for a train/prefill step."""
    ba = _batch_axes(mesh)
    bsz = 1
    for a in ba:
        bsz *= mesh.shape[a]
    b_ax = ba if batch % bsz == 0 else None
    if cfg.family == "cnn":
        return {"x": P(b_ax, None, None, None), "y": P(b_ax)}
    specs = {"tokens": P(b_ax, None)}
    if cfg.family == "encdec":
        specs["frames"] = P(b_ax, None, None)
    if cfg.family == "vlm":
        specs["image_embeddings"] = P(b_ax, None, None)
    return specs


# ------------------------------------------------------------------ caches

def cache_pspecs(cfg, mesh: Mesh, cache_shapes: Pytree, *, batch: int
                 ) -> Pytree:
    """Decode-cache PartitionSpecs.

    Sequence axes shard over ``model`` (flash-decoding split-KV: each TP
    shard holds a KV stripe, partial-softmax combine = the all-reduces
    GSPMD inserts); batch shards over (pod, data) when divisible; for
    global_batch=1 (long_500k) the sequence additionally takes the data
    axis (context parallelism). SSM states shard d_inner over ``model``
    (they follow the Megatron channel shard of the SSM block).
    """
    ba = _batch_axes(mesh)
    bsz = 1
    for a in ba:
        bsz *= mesh.shape[a]
    b_ax: Any = ba if batch % bsz == 0 else None
    seq_ax: Any = "model" if b_ax is not None else ("data", "model")

    def spec(path, leaf):
        keys = [getattr(p, "key", "") for p in path]
        name = keys[-1]
        nd = len(leaf.shape)
        if name == "pos":
            return P(b_ax)
        if name in ("k", "v", "attn_k", "attn_v"):     # (L,B,S,Hkv,hd)
            return P(None, b_ax, seq_ax, None, None)
        if name in ("xk", "xv"):                       # (L,B,S_enc,Hkv,hd)
            return P(None, b_ax, None, None, None)
        if name in ("ckv", "krope"):                   # (L,B,S,r)
            return P(None, b_ax, seq_ax, None)
        if name == "conv":                             # (L,B,K-1,C)
            return P(None, b_ax, None, "model")
        if name == "h":                                # mamba1 (L,B,din,N)
            if nd == 4:                                # / mamba2
                return P(None, b_ax, "model", None)
            return P(None, b_ax, "model", None, None)  # (L,B,heads,hd,N)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _divisible(spec(path, leaf), leaf.shape, mesh),
        cache_shapes)


# ----------------------------------------------------------------- helpers

def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def tree_named(mesh: Mesh, specs: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))

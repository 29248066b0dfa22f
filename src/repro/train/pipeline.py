"""Large-batch execution pipeline: microbatched gradient accumulation,
bf16/f32 precision policy, and a donated mesh-aware train step.

The paper's point is scaling the *global* batch without losing accuracy
(LARS); You et al. (1708.03888, 1904.00962) only reach 16K-32K batches
through gradient accumulation + LR scaling/warmup + mixed precision.
:class:`TrainPipeline` is that execution layer for this repro:

* **Accumulation** — the global batch ``(B, ...)`` is reshaped to
  ``(accum_steps, B/accum_steps, ...)`` and scanned with ``lax.scan``
  inside ONE jitted step. Per-microbatch gradients accumulate into an
  f32 buffer; the optimizer update — and hence the LARS trust ratio —
  runs exactly once per global batch on the mean gradient, so the
  layer-wise semantics match a single step on the full batch. With
  ``accum_steps=1`` the scan is elided entirely and the traced step is
  op-for-op :func:`repro.train.step.make_train_step` (bit-identical
  trajectories under f32 — pinned by test).
* **Precision policy** — ``"f32"`` leaves every dtype alone; ``"bf16"``
  stores params and runs forward/backward in bfloat16 while the
  optimizer keeps f32 master weights in the flat-packed superbuffer
  (:data:`repro.core.packing.MASTER_SLOT`) and accumulates gradients in
  f32. Batch float leaves are cast to the compute dtype inside the step.
* **Mesh awareness** — given a mesh, the step is jitted with explicit
  in/out shardings from :mod:`repro.distributed.sharding` and
  ``donate_argnums=(0,)`` so the TrainState is updated in place
  (params + slots never double-buffer). Tracing happens under
  ``jax.set_mesh(mesh)`` — required by the packed substrate's sharding
  constraints (see ``packing.constrain_rows``).
* **ZeRO optimizer-state sharding** — ``zero=True`` (requires a mesh
  with a ``data`` axis) row-shards every packed optimizer slot across
  the data axis: the layout pads rows to a multiple of
  ``ndata * block_rows``, the mean-grad superbuffer is reduce-scattered
  into the local shard, the layer-wise update runs on local rows (norms
  finalize in one cross-shard reduction), and params all-gather once
  per global step. Per-device slot memory drops to ~1/ndata.

Typical use::

    pipe = TrainPipeline(model, opt, cfg, accum_steps=8, precision="bf16",
                         mesh=mesh)
    state = pipe.init_state(jax.random.key(0))
    for batch in ShardedLoader(host_batches, mesh, pipe.batch_specs(B)):
        state, metrics = pipe(state, batch)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import phases
from repro.core import packing
from repro.core.optim_base import PackedGrads
from repro.models.moe import MOE_COUNTERS, aux_zero
from repro.train.state import TrainState
from repro.train.step import _forward_and_loss

Pytree = Any
tree_map = jax.tree_util.tree_map


# ------------------------------------------------------------- precision

@dataclasses.dataclass(frozen=True)
class Precision:
    """Dtype policy for one training run.

    ``compute_dtype`` is what params, activations and batch floats run
    in (``None`` = leave the model's own dtypes untouched).
    ``master_weights`` keeps an f32 master copy of the params as an
    optimizer slot (the packed engine stores it as the superbuffer and
    skips the per-step params pack entirely).
    """

    name: str
    compute_dtype: Optional[Any]
    master_weights: bool


PRECISIONS: dict[str, Precision] = {
    "f32": Precision("f32", None, False),
    "bf16": Precision("bf16", jnp.bfloat16, True),
}


def get_precision(precision: str | Precision) -> Precision:
    if isinstance(precision, Precision):
        return precision
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; "
                         f"have {sorted(PRECISIONS)}")
    return PRECISIONS[precision]


def cast_floats(tree: Pytree, dtype) -> Pytree:
    """Cast float leaves to ``dtype``; int/bool leaves pass through."""
    if dtype is None:
        return tree
    return tree_map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def compiled_peak_bytes(compiled) -> Optional[int]:
    """temp + argument + output bytes of a compiled program.

    ``None`` only where the backend has no memory analysis; on a TPU a
    missing analysis is an error, not a blank column.
    """
    mem = compiled.memory_analysis()
    if mem is None:
        if jax.default_backend() == "tpu":
            raise RuntimeError("TPU compile returned no memory analysis")
        return None
    return int(mem.temp_size_in_bytes + mem.argument_size_in_bytes
               + mem.output_size_in_bytes)


# -------------------------------------------------------------- metrics

# per-step values the forward reports beside the loss; each a mean over
# microbatches except these sums (rows the held experts computed)
SUMMED = ("moe_rows",)


def _aux_sums(aux: dict) -> dict:
    """The forward's aux values a step reports: the balance loss (zero
    where the model has none) and an MoE model's routing counters."""
    out = {"aux_loss": aux.get("aux_loss", jnp.zeros((), jnp.float32))}
    out.update({k: aux[k] for k in MOE_COUNTERS if k in aux})
    return out


# -------------------------------------------------------------- pipeline

class TrainPipeline:
    """End-to-end jitted train step: accumulate, update once, donate.

    The pipeline compiles lazily on the first call (the global batch
    size is read off the first batch, which fixes the batch shardings),
    then reuses the compiled step. ``already_jitted`` tells
    :func:`repro.train.loop.train_loop` not to wrap it again.
    """

    already_jitted = True

    def __init__(self, model, optimizer, cfg=None, *, accum_steps: int = 1,
                 precision: str | Precision = "f32", mesh=None,
                 donate: bool = True, packed: bool = True,
                 fuse_update: bool | str = "auto", zero: bool = False,
                 stats_fn: Optional[Callable] = None):
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        if fuse_update not in (True, False, "auto"):
            raise ValueError(f"fuse_update must be True/False/'auto', "
                             f"got {fuse_update!r}")
        if zero:
            if mesh is None:
                raise ValueError(
                    "zero=True (ZeRO-sharded optimizer states) requires "
                    "a mesh — the slots shard across its 'data' axis")
            if not packed:
                raise ValueError(
                    "zero=True requires the flat-packed substrate "
                    "(packed=True): ZeRO shards the superbuffer rows")
            if "data" not in mesh.axis_names:
                raise ValueError(
                    f"zero=True needs a mesh with a 'data' axis, got "
                    f"axes {mesh.axis_names}")
        self.model = model
        self.optimizer = optimizer
        self.cfg = cfg if cfg is not None else model.cfg
        self.accum_steps = accum_steps
        self.precision = get_precision(precision)
        self.mesh = mesh
        self.donate = donate
        self.packed = packed
        # ZeRO: row-shard every packed optimizer slot across the mesh
        # data axis (1/ndev slot memory per device). The step then runs:
        # reduce-scatter the mean-grad superbuffer into the local shard
        # (the pack's sharding constraint), update locally, all-gather
        # the params once per global step (gather_rows before unpack).
        self.zero = zero
        self._zero_shards = int(mesh.shape["data"]) if zero else 1
        # Fused accumulation epilogue: with accum_steps > 1 and a
        # flat-packed opt state, microbatch gradients accumulate directly
        # in the (rows, lane) superbuffer inside the scan and the
        # optimizer consumes the buffer in place (PackedGrads) — the
        # per-layer grad norms finalize once on the accumulated buffer,
        # eliminating the epilogue's full gradient pack (and the Adam
        # family's second g^2 pack). "auto" fuses whenever it applies
        # and elides at accum_steps == 1 (bit-identical to
        # make_train_step). Under a mesh the fuse is valid whenever the
        # mesh is pure data-parallel (model axis size 1); "auto" only
        # takes it in ZeRO mode, where each microbatch pack lands as a
        # reduce-scatter into the local shard (cheaper than the
        # replicated path's per-microbatch all-gather, which is why
        # plain data-parallel "auto" still runs unfused).
        self.fuse_update = fuse_update
        # optional per-step telemetry computed INSIDE the jitted step on
        # (params, mean grads, stacked marker) — e.g. the per-layer
        # trust-ratio table from repro.core.grad_stats.stats_hook. The
        # result rides back under metrics["stats"] as device arrays; no
        # host sync happens unless the caller reads them.
        self.stats_fn = stats_fn
        # stacked marker from an eval_shape trace: never allocates params
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        marker_fn = getattr(model, "stacked_marker", None)
        self._stacked = (marker_fn(shapes)
                         if packed and marker_fn is not None else None)
        self._compiled: Optional[Callable] = None
        self._step_fn = self._build_step()
        self._calls = 0     # steps dispatched: the profiler's step number

    # ------------------------------------------------------------- state

    def init_state(self, key) -> TrainState:
        """Fresh TrainState on this pipeline's precision policy (+ mesh
        placement when mesh-aware): params in the compute dtype, f32
        master weights as an optimizer slot when the policy keeps one."""
        params = self.model.init(key)
        params = cast_floats(params, self.precision.compute_dtype)
        kw = {"zero_shards": self._zero_shards} \
            if self._zero_shards > 1 else {}
        opt_state = self.optimizer.init(
            params, stacked=self._stacked,
            master=self.precision.master_weights, **kw)
        state = TrainState(params=params, opt_state=opt_state)
        return self.place_state(state)

    def place_state(self, state: TrainState) -> TrainState:
        """Device-put a (possibly host/restored) state onto the mesh."""
        if self.mesh is None:
            return state
        from repro.distributed.sharding import state_pspecs, tree_named
        specs = state_pspecs(self.cfg, jax.eval_shape(lambda: state),
                             self.mesh)
        return jax.device_put(state, tree_named(self.mesh, specs))

    def batch_specs(self, global_batch: int):
        """PartitionSpecs a host loader should place batches with."""
        from repro.distributed.sharding import batch_pspecs
        if self.mesh is None:
            raise ValueError("batch_specs requires a mesh-aware pipeline")
        return batch_pspecs(self.cfg, self.mesh, batch=global_batch)

    # -------------------------------------------------------------- step

    def _build_step(self) -> Callable:
        model, cfg = self.model, self.cfg
        optimizer, stacked = self.optimizer, self._stacked
        k = self.accum_steps
        compute_dtype = self.precision.compute_dtype
        stats_fn = self.stats_fn
        fuse_mode, mesh, zero = self.fuse_update, self.mesh, self.zero
        # a pure data-parallel mesh (model axis size 1) keeps every
        # microbatch gradient in one replica group per shard row, so the
        # fused packed accumulation is valid under it
        pure_data = mesh is None or all(
            mesh.shape[a] == 1 for a in mesh.axis_names
            if a not in ("data", "pod"))

        def step(state: TrainState, batch) -> tuple[TrainState, dict]:
            batch = cast_floats(batch, compute_dtype)
            # layout is OptState METADATA — a static Python value at
            # trace time, so the fuse decision shapes the traced graph
            layout = state.opt_state.layout
            can_fuse = k > 1 and layout is not None and pure_data
            if fuse_mode is True and not can_fuse:
                raise ValueError(
                    "fuse_update=True needs accum_steps > 1, a flat-"
                    "packed opt state, and no mesh or a pure data-"
                    "parallel mesh (model axis size 1); use "
                    "fuse_update='auto' to fall back silently")
            # "auto" fuses off-mesh and in ZeRO mode (per-microbatch
            # packs reduce-scatter into the local shard); under a
            # replicated mesh each pack would all-gather instead, so
            # auto stays unfused there — explicit True overrides.
            fuse = can_fuse and (fuse_mode is True or (
                fuse_mode is not False and (mesh is None or zero)))

            def loss_fn(params, mb):
                return _forward_and_loss(model, cfg, params, mb)

            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

            if k == 1:
                # exactly make_train_step's body: bit-identical under f32
                (loss, (_, aux)), grads = grad_fn(state.params, batch)
                aux = _aux_sums(aux)
            else:
                micro = tree_map(
                    lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                    batch)

                def body(carry, mb):
                    gsum, lsum, asum = carry
                    (loss, (_, aux)), g = grad_fn(state.params, mb)
                    aux = _aux_sums(aux)
                    if fuse:
                        # accumulate in packed form: pack casts to f32
                        # BEFORE adding, so every element sees the same
                        # f32 addition chain as the tree carry below —
                        # the accumulated buffer is bit-identical to
                        # pack(tree-accumulated grads)
                        with jax.named_scope(phases.GRAD_PACK):
                            gsum = gsum + packing.pack(layout, g)
                    else:
                        gsum = tree_map(
                            lambda a, gi: a + gi.astype(jnp.float32),
                            gsum, g)
                    asum = tree_map(jnp.add, asum, aux)
                    return (gsum, lsum + loss, asum), None

                zeros = jnp.zeros(layout.buffer_shape, jnp.float32) \
                    if fuse else tree_map(
                        lambda p: jnp.zeros(p.shape, jnp.float32),
                        state.params)
                carry0 = (zeros, jnp.zeros((), jnp.float32),
                          aux_zero(cfg))
                (gsum, lsum, asum), _ = jax.lax.scan(body, carry0, micro)
                # equal-size microbatches + mean losses: the mean of the
                # per-microbatch mean gradients IS the full-batch mean
                # gradient, so the (single) LARS trust ratio matches a
                # one-shot step on the whole global batch.
                inv = 1.0 / k
                if fuse:
                    with jax.named_scope(phases.GRAD_PACK):
                        grads = PackedGrads(gsum * inv)
                else:
                    grads = tree_map(lambda g: g * inv, gsum)
                loss = lsum * inv
                aux = {name: v if name in SUMMED else v * inv
                       for name, v in asum.items()}

            new_params, new_opt = optimizer.update(
                grads, state.opt_state, state.params, stacked=stacked)
            metrics = dict(aux, loss=loss, step=new_opt.step)
            if stats_fn is not None:
                stat_grads = packing.unpack(layout, grads.buf,
                                            dtype=jnp.float32) \
                    if isinstance(grads, PackedGrads) else grads
                metrics["stats"] = stats_fn(state.params, stat_grads,
                                            stacked)
            return TrainState(new_params, new_opt), metrics

        return step

    def _jit(self, state: TrainState, batch):
        """The raw ``jax.jit``-wrapped step (shardings + donation)."""
        donate = (0,) if self.donate else ()
        if self.mesh is None:
            return jax.jit(self._step_fn, donate_argnums=donate)
        from repro.distributed.sharding import (batch_pspecs, state_pspecs,
                                                tree_named)
        leaves = jax.tree_util.tree_leaves(batch)
        global_batch = leaves[0].shape[0]
        sspecs = state_pspecs(self.cfg, jax.eval_shape(lambda: state),
                              self.mesh)
        bspecs = batch_pspecs(self.cfg, self.mesh, batch=global_batch)
        sshard = tree_named(self.mesh, sspecs)
        return jax.jit(self._step_fn,
                       in_shardings=(sshard, tree_named(self.mesh, bspecs)),
                       out_shardings=(sshard, None),
                       donate_argnums=donate)

    def _compile(self, state: TrainState, batch) -> Callable:
        fn = self._jit(state, batch)
        if self.mesh is None:
            return fn
        mesh = self.mesh

        def call(s, b):
            # trace/execute under the ambient mesh: the packed substrate
            # pins its superbuffers replicated only when it can see one
            with jax.set_mesh(mesh):
                return fn(s, b)

        return call

    def compiled_peak_bytes(self, batch) -> Optional[int]:
        """Compiled peak memory (temp + args + outputs) of this step on
        an example batch, cached per pipeline; ``None`` on backends
        without memory analysis (see :func:`compiled_peak_bytes`).
        Family-agnostic — any batch pytree the step accepts works, so
        the experiment harness reports the same column for CNN and
        token-LM cells."""
        if getattr(self, "_peak_bytes", "miss") == "miss":
            state = self.init_state(jax.random.key(0))
            self._peak_bytes = compiled_peak_bytes(
                self.lower(state, batch).compile())
        return self._peak_bytes

    def lower(self, state: TrainState, batch):
        """``jax.stages.Lowered`` for this step — compile-time
        introspection (``.compile().memory_analysis()`` gives its peak
        memory, see :func:`compiled_peak_bytes`)."""
        fn = self._jit(state, batch)
        if self.mesh is not None:
            with jax.set_mesh(self.mesh):
                return fn.lower(state, batch)
        return fn.lower(state, batch)

    def __call__(self, state: TrainState, batch) -> tuple[TrainState, dict]:
        if self.accum_steps > 1:
            b = jax.tree_util.tree_leaves(batch)[0].shape[0]
            if b % self.accum_steps:
                raise ValueError(
                    f"global batch {b} not divisible by "
                    f"accum_steps={self.accum_steps}")
        if self._compiled is None:
            self._compiled = self._compile(state, batch)
        # one host span per optimizer step, the step marker of profiler
        # step-time views; a few microseconds when no trace is running
        with jax.profiler.StepTraceAnnotation("train_step",
                                              step_num=self._calls):
            self._calls += 1
            return self._compiled(state, batch)

"""TrainState: the single pytree carried across steps.

The ``stacked`` marker (which leaves are (L, ...) layer stacks) is STATIC
per architecture. It is threaded into ``optimizer.init`` so the optimizer
state is born on the flat-packed layer-wise substrate: slot buffers
(momentum, second moment) live packed in one superbuffer across steps and
the OptState carries the static PackedLayout as pytree metadata. Pass
``packed=False`` to keep per-leaf slot pytrees instead — the reference
layout used when slots must shard leaf-for-leaf alongside FSDP params
(``opt.init(p)``).

Memory trade-off under pjit: the packed superbuffers (params/grads
repacked per step, slots persistent) are REPLICATED per device — right
for single-replica-group training, wrong for FSDP-scale models where
the point is sharding optimizer memory 1/(data*model). Use
``packed=False`` there; `distributed/sharding.state_pspecs` handles
both layouts.
"""

from __future__ import annotations

from typing import Any, NamedTuple

from repro.core.optim_base import OptState

Pytree = Any


class TrainState(NamedTuple):
    params: Pytree
    opt_state: OptState


def create_train_state(model, optimizer, key, *, packed: bool = True,
                       precision: str = "f32") -> TrainState:
    """Fresh TrainState; ``precision="bf16"`` stores params in bfloat16
    and seeds an f32 master-weight slot in the optimizer state (packed:
    the superbuffer itself) — the same policy `TrainPipeline` applies."""
    from repro.train.pipeline import cast_floats, get_precision
    policy = get_precision(precision)
    params = cast_floats(model.init(key), policy.compute_dtype)
    marker_fn = getattr(model, "stacked_marker", None)
    stacked = (marker_fn(params)
               if packed and marker_fn is not None else None)
    return TrainState(params=params,
                      opt_state=optimizer.init(
                          params, stacked=stacked,
                          master=policy.master_weights))

"""Training traffic for a DeepSeek-V2 configuration (MLA, leading dense
layers, shared and routed experts, the chip's share of the experts):
every optimizer step takes a global batch of token rows drawn from the
seed over the vocabulary slice, through the program's ``TrainPipeline``
built as ``launch/train.build_job`` builds it.

As ``kinds/train.py`` (whose window, readings and memory peak this kind
shares): set-up builds the pipeline and its state from the seed, drives
it through the checked steps and hands it to the window; after the
window the plain float32 reference (``reference_deepseek_v2.py``)
follows the same steps from the same weights and batches. The window
also reads the routing counters the pipeline reports each step.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import types

import jax
import jax.numpy as jnp

from perfbench import harness
from perfbench import reference_deepseek_v2 as ref
from perfbench import runtime as rt
from perfbench import weights_deepseek_v2 as W
from perfbench.counts import lars_update_bytes
from perfbench.counts_deepseek_v2 import dims, train_step_flops
from perfbench.program import check_tree
from perfbench.weights import BATCHES, WEIGHTS, seed_key

T = harness.kind("train")
CHECKED_STEPS, BATCH_POOL = T.CHECKED_STEPS, T.BATCH_POOL
REFERENCE_ROWS = 1     # rows of a batch per block of the reference: its
                       # f32 state (params, momentum, gradient sums) takes
                       # 8.6 GB of the chip at this size
host_leaves, measure, readings, compiled_peak, free = (
    T.host_leaves, T.measure, T.readings, T.compiled_peak, T.free)
_cast = T._cast


def model_config(cfg: dict, **changes):
    """The program's ``ModelConfig`` of a DeepSeek-V2 configuration file
    (``changes``: fields replaced, for the planted faults)."""
    from repro.configs.base import ModelConfig, YaRN
    m = dims(cfg)
    rs = cfg["rope_scaling"]
    if cfg.get("q_lora_rank") or cfg.get("attention_bias"):
        raise ValueError("q compression and attention biases are not "
                         "part of this configuration")
    pcfg = ModelConfig(
        name=cfg["name"], family="moe", num_layers=m["L"],
        first_k_dense=m["L_dense"], d_model=m["d"], num_heads=m["h"],
        num_kv_heads=cfg["num_key_value_heads"], d_ff=m["ff"],
        vocab_size=m["V"], norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=YaRN(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        use_mla=True, kv_lora_rank=m["r"], q_lora_rank=0,
        qk_nope_dim=m["nope"], qk_rope_dim=m["rope"], v_head_dim=m["v"],
        num_experts=m["E"], experts_held=m["E_held"],
        expert_offset=int(cfg["expert_offset"]),
        num_shared_experts=m["shared"], experts_per_token=m["k"],
        moe_d_ff=m["ff_e"], norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        seq_aux=bool(cfg["seq_aux"]),
        router_aux_coef=float(cfg["aux_loss_alpha"]),
        act=cfg["hidden_act"], tie_embeddings=False,
        dtype=cfg["torch_dtype"], source=cfg["source"])
    return dataclasses.replace(pcfg, **changes)


class Counted:
    """The pipeline, keeping each step's routing counters (device
    arrays, read after the window)."""

    def __init__(self, pipe):
        self.pipe, self.counters = pipe, []

    def __call__(self, state, batch):
        state, m = self.pipe(state, batch)
        self.counters.append((m["moe_rows"], m["moe_load_max_over_mean"]))
        return state, m

    def lower(self, state, batch):
        return self.pipe.lower(state, batch)


def build(cfg: dict, mix: dict, seed: int, chips: int, **changes):
    """The program's pipeline and state, and the batches, from the seed."""
    from repro.core import get_optimizer, schedules
    from repro.distributed.sharding import tree_named
    from repro.launch.mesh import mesh_from_spec
    from repro.models import build_model
    from repro.train import TrainPipeline
    from repro.train.state import TrainState

    o = mix["optimizer"]
    pcfg = model_config(cfg, **changes)
    model = build_model(pcfg)
    mesh = mesh_from_spec(f"{chips}x1")
    opt = get_optimizer(o["name"], learning_rate=schedules.constant(o["lr"]),
                        momentum=o["momentum"],
                        weight_decay=o["weight_decay"],
                        trust_coefficient=o["trust_coefficient"],
                        slot_dtype=mix["opt_state_dtype"])
    pipe = TrainPipeline(model, opt, pcfg, accum_steps=mix["accum_steps"],
                         precision=mix["precision"], mesh=mesh)
    dtype = pipe.precision.compute_dtype
    wkey = seed_key(seed, WEIGHTS)

    def init(key):
        params = _cast(W.make_params(cfg, key), dtype)
        return TrainState(params, opt.init(
            params, stacked=model.stacked_marker(params),
            master=pipe.precision.master_weights))

    check_tree(model, jax.eval_shape(lambda: W.make_params(cfg, wkey)))
    state = pipe.place_state(jax.jit(init)(wkey))
    B, S = mix["global_batch"], mix["seq_len"]
    pool = jax.jit(lambda k: jax.random.randint(
        k, (BATCH_POOL, B, S), 0, dims(cfg)["V"], jnp.int32))(
        seed_key(seed, BATCHES))
    shard = tree_named(mesh, pipe.batch_specs(B))
    batches = [jax.device_put({"tokens": pool[i]}, shard)
               for i in range(BATCH_POOL)]
    checked = [pool[i] for i in range(CHECKED_STEPS)]
    del pool
    return types.SimpleNamespace(cfg=cfg, mix=mix, pcfg=pcfg, model=model,
                                 pipe=Counted(pipe), state=state,
                                 batches=batches, checked=checked, wkey=wkey,
                                 dtype=dtype, opt=o)


def first_steps(job) -> dict:
    """The checked steps, as ``kinds/train.first_steps`` drives them."""
    from repro.core import packing
    layout = job.state.opt_state.layout
    momentum = jax.jit(lambda st: packing.unpack(
        layout, st.opt_state.slots["momentum"], dtype=jnp.float32))
    cfg, dtype = job.cfg, job.dtype

    def delta(st, key):
        w0 = ref.to_f32(_cast(W.make_params(cfg, key), dtype))
        w3 = packing.unpack(layout, st.opt_state.slots[packing.MASTER_SLOT],
                            dtype=jnp.float32)
        return ref.leaf_norms(jax.tree_util.tree_map(jnp.subtract, w3, w0))

    losses = []
    for i in range(CHECKED_STEPS):
        with rt.span("pipeline"):
            job.state, m = job.pipe(job.state, job.batches[i])
        losses.append(m["loss"])
        if i == 0:
            first = host_leaves(momentum(job.state))
    change = jax.device_get(jax.jit(delta)(job.state, job.wkey))
    job.checked_rows = [float(r) for r, _ in
                        jax.device_get(job.pipe.counters)]
    job.pipe.counters.clear()
    return {"losses": [float(x) for x in losses], "first_momentum": first,
            "change": change}


def reference(job, rnd=ref.ident) -> dict:
    """The reference's steps from the same weights and batches (``rnd``
    rounds its matrix products' operands: the control)."""
    weights = jax.jit(lambda k: ref.to_f32(
        _cast(W.make_params(job.cfg, k), job.dtype)))
    r = ref.TrainReference(job.cfg, job.opt, REFERENCE_ROWS, rnd)
    out = r.run(weights(job.wkey), job.checked)
    w0 = weights(job.wkey)     # made again: not held through the steps
    out["change"] = jax.device_get(jax.jit(lambda a, b: ref.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(out.pop("params"), w0))
    out["first_grad"] = jax.device_get(out["first_grad"])
    out["first_momentum"] = host_leaves(out["first_momentum"])
    return out


def run(ctx: dict) -> dict:
    cfg, mix, seed = ctx["cfg"], ctx["mix"], ctx["seed"]
    job = build(cfg, mix, seed, ctx["chips"])
    prog = first_steps(job)
    win = measure(job, ctx["seconds"], ctx["trace"], ctx.get("keep_trace"))
    setup_s = win["t0_epoch"] - ctx["t_start"]
    counters = jax.device_get(job.pipe.counters)
    rows = [float(r) for r, _ in counters]
    moe_rows = sum(rows) / len(rows)
    moe_load = sum(float(x) for _, x in counters) / len(counters)
    runtime_peak = rt.memory_peak(ctx["devices"])
    step_peak = compiled_peak(job)
    rt.say(f"train_moe: memory peak {runtime_peak} B as the runtime counts "
           f"it, {step_peak} B as the step is compiled")
    layout = job.state.opt_state.layout
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(job.state.params))
    steps, elapsed = win["steps"], win["elapsed"]
    B, S = mix["global_batch"], mix["seq_len"]
    rt.say(f"train_moe: {steps} steps in {elapsed!r} s window, "
           f"{win['compiles']} compiles and {win['traces']} traces in the "
           f"window, set-up {setup_s!r} s; {n_params} parameters")
    rt.say(f"train_moe: routed rows a step {moe_rows!r}, busiest held "
           f"expert over the mean {moe_load!r}")
    rt.say(f"train_moe: routed rows of each step: checked "
           f"{job.checked_rows}, window {rows}")
    rt.say(f"train_moe: losses of the checked steps {prog['losses']}, "
           f"last loss of the window {win['last_loss']!r}")
    free(job)
    in_use = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                 for d in ctx["devices"])
    rt.say(f"train_moe: {in_use} B in use before the reference")
    refr = reference(job)
    rt.say(f"train_moe: reference losses {refr['losses']}")
    nums = readings(prog, refr)
    rt.say(f"train_moe: readings {nums}")
    checks = rt.Checks()
    for name, limit in mix["limits"].items():
        checks.add(name, nums[name], limit)
    layer_ctx = {
        "kind": "train", "cfg": cfg, "mix": mix, "peak": ctx["peak"],
        "chips": ctx["chips"], "trace": win.get("trace"), "steps": steps,
        "step_flops": train_step_flops(cfg, B, S),
        "lars_bytes": lars_update_bytes(
            layout.buffer_shape[0] * layout.buffer_shape[1], n_params),
        "superbuffer": layout.buffer_shape,
        "moe_rows": moe_rows, "moe_load_max_over_mean": moe_load,
    }
    return {"e2e": {"train_tokens_per_s": steps * B * S / elapsed,
                    "setup_s": setup_s},
            "layer_ctx": layer_ctx, "attempted": CHECKED_STEPS + steps,
            "failed": sum(not math.isfinite(x) for x in prog["losses"]),
            "checks": checks,
            "memory_peak_bytes": max(runtime_peak, step_peak),
            "compiles_in_window": win["compiles"],
            "traces_in_window": win["traces"], "trace": win.get("trace")}


@contextlib.contextmanager
def _expert_dropped(expert: int = 0):
    """Planted fault: the program drops every token routed to one held
    expert (as a capacity of zero would)."""
    from repro.models import moe
    keep = moe.held_slots

    def held_slots(mcfg, ids):
        local = keep(mcfg, ids)
        return jnp.where(local == expert, moe.held_experts(mcfg), local)

    moe.held_slots = held_slots
    try:
        yield
    finally:
        moe.held_slots = keep


def _planted(fault: str, cfg: dict):
    """(context that plants ``fault``, config fields it changes)."""
    if fault == "gates_renormalised":     # as norm_topk_prob would
        return contextlib.nullcontext(), {
            "norm_topk_prob": not cfg["norm_topk_prob"]}
    if fault == "expert_dropped":
        return _expert_dropped(), {}
    raise ValueError(f"no fault {fault!r} for {cfg['name']}")


def calibrate(cfg: dict, mix: dict, seed: int, chips: int, *,
              control: bool = False, faults=()):
    """Yield (kind, readings): the program's sound run, the control in
    its place, and each planted fault, all against the one reference."""
    job = build(cfg, mix, seed, chips)
    prog = first_steps(job)
    free(job)
    refr = reference(job)
    yield "program", readings(prog, refr)
    if control:
        yield "control_fp8", readings(reference(job, ref.fp8_round), refr)
    for fault in faults:
        planted, changes = _planted(fault, cfg)
        with planted:
            fjob = build(cfg, mix, seed, chips, **changes)
            fprog = first_steps(fjob)
        free(fjob)
        yield "fault_" + fault, readings(fprog, refr)

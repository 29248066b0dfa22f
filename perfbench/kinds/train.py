"""Training traffic: every optimizer step takes a global batch of token
rows drawn from the seed over the whole vocabulary, through the
program's ``TrainPipeline`` built as ``launch/train.build_job`` builds it.

Set-up builds the one pipeline and its state from the seed, drives it
through its first three steps (rows that all differ), records what the
comparison needs, and hands the same pipeline and state to the window.
After the window the plain float32 reference follows the same three
steps from the same weights and batches.
"""

from __future__ import annotations

import gc
import math
import statistics
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference as ref
from perfbench import runtime as rt
from perfbench import weights as W
from perfbench.counts import dims, lars_update_bytes, train_step_flops
from perfbench.program import check_tree, model_config

CHECKED_STEPS = 3
IN_FLIGHT = 2          # steps the host may run ahead of the device
BATCH_POOL = 8         # distinct batches the window cycles through
REFERENCE_ROWS = 2     # rows of a batch per block of the reference


def _cast(tree, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def build(cfg: dict, mix: dict, seed: int, chips: int):
    """The program's pipeline and state, and the batches, from the seed."""
    from repro.core import get_optimizer, schedules
    from repro.distributed.sharding import tree_named
    from repro.launch.mesh import mesh_from_spec
    from repro.models import build_model
    from repro.train import TrainPipeline
    from repro.train.state import TrainState

    o = mix["optimizer"]
    pcfg = model_config(cfg)
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
    wkey = W.seed_key(seed, W.WEIGHTS)

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
        W.seed_key(seed, W.BATCHES))
    shard = tree_named(mesh, pipe.batch_specs(B))
    batches = [jax.device_put({"tokens": pool[i]}, shard)
               for i in range(BATCH_POOL)]
    checked = [pool[i] for i in range(CHECKED_STEPS)]
    del pool
    return types.SimpleNamespace(cfg=cfg, mix=mix, pcfg=pcfg, model=model,
                                 pipe=pipe, state=state, batches=batches,
                                 checked=checked, wkey=wkey, dtype=dtype,
                                 opt=o)


def host_leaves(tree) -> dict:
    """{path: float32 numpy array} of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {jax.tree_util.keystr(p): np.asarray(x, np.float32)
            for p, x in flat}


def first_steps(job) -> dict:
    """Drive the pipeline through the checked steps, reading the momentum
    after the first (to the host) and the change of the master weights
    after the last.
    """
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
    return {"losses": [float(x) for x in losses], "first_momentum": first,
            "change": change}


def measure(job, seconds: float, traced: bool, keep=None) -> dict:
    """Optimizer steps for ``seconds``; the window ends when the device
    has finished every step dispatched in it."""
    P = len(job.batches)
    pending = []
    with rt.window(traced, keep) as win:
        t0, e0 = time.perf_counter(), time.time()
        n = 0
        while True:
            with rt.span("pipeline"):
                job.state, m = job.pipe(job.state, job.batches[n % P])
            n += 1
            pending.append(m["loss"])
            if len(pending) > IN_FLIGHT:
                with rt.span("wait"):
                    pending.pop(0).block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with rt.span("drain"):
            jax.block_until_ready(job.state)
        t1 = time.perf_counter()
    win.update(steps=n, elapsed=t1 - t0, t0_epoch=e0,
               last_loss=float(pending[-1]))
    return win


def reference(job, rnd=ref.ident) -> dict:
    """The reference's three steps from the same weights and batches
    (``rnd`` rounds its matrix products' operands: the control)."""
    w0 = jax.jit(lambda k: ref.to_f32(
        _cast(W.make_params(job.cfg, k), job.dtype)))(job.wkey)
    r = ref.TrainReference(job.cfg, job.opt, REFERENCE_ROWS, rnd)
    out = r.run(w0, job.checked)
    out["change"] = jax.device_get(jax.jit(lambda a, b: ref.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(out.pop("params"), w0))
    out["first_grad"] = jax.device_get(out["first_grad"])
    out["first_momentum"] = host_leaves(out["first_momentum"])
    return out


def _per_leaf(gap: dict, want: dict, keep) -> dict:
    """{leaf: gap / max(want, median of want)} over kept leaves."""
    med = statistics.median(float(want[k]) for k in keep)
    return {k: float(gap[k]) / max(float(want[k]), med) for k in keep}


def readings(prog: dict, refr: dict) -> dict:
    """The numbers compared. The momentum after the first step is the
    first gradient as LARS gets it, lr x trust ratio x (g + wd w): its
    norm per leaf (``first_grad_gap``) and, since the trust ratio cancels
    a matrix leaf's gradient norm, its difference from the reference's
    per leaf (``first_grad_diff``), which sees the direction. Leaves whose
    first reference gradient is under a thousandth of the median leaf's
    are nought to rounding and left out of the change."""
    g = {k: float(v) for k, v in refr["first_grad"].items()}
    med = statistics.median(g.values())
    moved = [k for k in g if g[k] >= 1e-3 * med]
    mp, mr = prog["first_momentum"], refr["first_momentum"]
    norm = {k: float(np.linalg.norm(v)) for k, v in mr.items()}
    first = _per_leaf({k: abs(float(np.linalg.norm(mp[k])) - norm[k])
                       for k in mr}, norm, mr)
    diff = _per_leaf({k: float(np.linalg.norm(mp[k] - mr[k])) for k in mr},
                     norm, mr)
    change = _per_leaf({k: abs(float(prog["change"][k]) -
                               float(refr["change"][k])) for k in moved},
                       refr["change"], moved)
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in
                        zip(prog["losses"], refr["losses"])),
        "first_grad_gap": max(first.values()),
        "first_grad_diff": max(diff.values()),
        "change_gap": max(change.values()),
        "worst_leaves": [max(d, key=d.get) for d in (first, diff, change)],
    }


def free(job) -> None:
    job.state = job.batches = job.pipe = None
    gc.collect()


def compiled_peak(job) -> int:
    """Peak bytes of the timed step as compiled: temporaries, arguments
    and outputs, less the outputs that reuse donated arguments."""
    m = job.pipe.lower(job.state, job.batches[0]).compile().memory_analysis()
    if m is None:
        return 0
    return int(m.temp_size_in_bytes + m.argument_size_in_bytes
               + m.output_size_in_bytes - m.alias_size_in_bytes)


def run(ctx: dict) -> dict:
    cfg, mix, seed = ctx["cfg"], ctx["mix"], ctx["seed"]
    job = build(cfg, mix, seed, ctx["chips"])
    prog = first_steps(job)
    win = measure(job, ctx["seconds"], ctx["trace"], ctx.get("keep_trace"))
    setup_s = win["t0_epoch"] - ctx["t_start"]
    runtime_peak = rt.memory_peak(ctx["devices"])
    step_peak = compiled_peak(job)
    rt.say(f"train: memory peak {runtime_peak} B as the runtime counts it, "
           f"{step_peak} B as the step is compiled")
    layout = job.state.opt_state.layout
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(job.state.params))
    steps, elapsed = win["steps"], win["elapsed"]
    B, S = mix["global_batch"], mix["seq_len"]
    rt.say(f"train: {steps} steps in {elapsed!r} s window, "
           f"{win['compiles']} compiles and {win['traces']} traces in the "
           f"window, set-up {setup_s!r} s")
    rt.say(f"train: losses of the checked steps {prog['losses']}, "
           f"last loss of the window {win['last_loss']!r}")
    free(job)
    refr = reference(job)
    rt.say(f"train: reference losses {refr['losses']}")
    nums = readings(prog, refr)
    rt.say(f"train: readings {nums}")
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
    }
    return {"e2e": {"train_tokens_per_s": steps * B * S / elapsed,
                    "setup_s": setup_s},
            "layer_ctx": layer_ctx, "attempted": CHECKED_STEPS + steps,
            "failed": sum(not math.isfinite(x) for x in prog["losses"]),
            "checks": checks,
            "memory_peak_bytes": max(runtime_peak, step_peak),
            "compiles_in_window": win["compiles"],
            "traces_in_window": win["traces"], "trace": win.get("trace")}


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
        if fault != "half_batch":
            raise ValueError(f"no fault {fault!r} for training")
        half = dict(mix, global_batch=mix["global_batch"] // 2)
        fjob = build(cfg, half, seed, chips)
        fjob.batches = [{"tokens": t[:half["global_batch"]]}
                        for t in job.checked]
        fprog = first_steps(fjob)
        free(fjob)
        yield "fault_half_batch", readings(fprog, refr)

"""Smoke run of the system's main paths on a TPU, through their entry points.

    python chip_smoke.py              # one chip: train, then serve
    python chip_smoke.py --chips 4    # four chips: ZeRO vs replicated step

One chip, smollm-135m at its published widths (30 layers, d_model 576,
9 heads / 3 KV heads, vocab 49152), random weights from ``--seed``:

* train: ``launch/train.py``'s own path (``build_job`` -> ``TrainPipeline``
  -> ``ShardedLoader`` -> ``train_loop``) with LARS on its ``"auto"``
  engine, bf16 with f32 master weights, 2 accumulation steps, global
  batch 16 x 2048 tokens generated from the seed. The compiled step must
  hold the Pallas megakernels (``tpu_custom_call``) and the loss must
  stay finite and fall over ``--steps`` steps. The kernel engine is held
  to the jnp engine (``use_pallas=False``) twice, by the first momentum
  (which IS the layer-wise update): one update of each on the same
  params and the same random f32 gradients, max |m_kernel - m_jnp| <=
  ENGINE_RTOL * max |m_jnp|; and one whole step of each on the same first
  batch, within STEP_RTOL (the loss too). The whole steps are two
  programs whose bf16 backward passes XLA may round differently (it
  keeps excess precision where it fuses), so that bound is bf16-sized.
* serve: a ``ServeEngine`` whose ``use_flash`` resolves to True (the
  flash-decode kernel) answers a few requests. Teacher-forced, the
  decode-step logits must match ``forward()`` logits at the same
  positions within DECODE_ATOL + DECODE_RTOL * max |logit|, and every
  greedy token the engine emitted must be ``forward()``'s argmax unless
  ``forward()``'s top two logits there are closer than that bound.

Four chips (``--chips 4``): the smollm-135m step on a (4, 1) mesh with
ZeRO-sharded optimizer state against the replicated step on the same
mesh, same seed and batches: losses within ZERO_LOSS_RTOL, each param
within one bf16 ulp or ZERO_PARAM_ATOL of its leaf's largest |param| (the
replicated path all-reduces bf16 gradients, ZeRO reduce-scatters f32
ones), the sharded state's shards on four distinct devices.

Phase results go to stdout; the last line is one JSON object naming the
device. Any failure, or a platform other than TPU, exits non-zero
without that line. Everything runs in this one process (a chip belongs
to one process at a time).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ENGINE_RTOL = 1e-4          # kernel vs jnp engine, same inputs: f32 order
STEP_RTOL = 1e-2            # whole steps: bf16 gradients may round apart
DECODE_ATOL = 0.05          # decode vs forward logits, bf16 weights/caches
DECODE_RTOL = 2.0 ** -5     # ... plus 8 bf16 ulps of the largest logit
ZERO_LOSS_RTOL = 5e-3       # tests/test_golden.py MESH_RTOL
BF16_ULP = 2.0 ** -7        # params: one bf16 ulp, relative ...
ZERO_PARAM_ATOL = 1e-3      # ... or this share of the leaf's max |param|

TRAIN_ARGV = ["--arch", "smollm-135m", "--optimizer", "lars",
              "--lr", "1.0", "--precision", "bf16", "--accum-steps", "2",
              "--batch", "16", "--seq", "2048"]
SERVE_ARCH = "smollm-135m"
SERVE_PROMPTS = (17, 40, 64, 100)    # prompt lengths, one request each
SERVE_NEW_TOKENS = 16


def _import_repo():
    root = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke: no repository next to this script "
                 f"({src}/repro missing)")
    sys.path.insert(0, src)


def _say(msg: str) -> None:
    print(msg, flush=True)


def _require(ok, what: str) -> None:
    """A smoke check (not ``assert``: it must hold under ``python -O``)."""
    if not ok:
        raise RuntimeError(what)


# ------------------------------------------------------------------ train

def _kernel_launches(compiled) -> int:
    """Pallas kernels (Mosaic custom calls) in a compiled program."""
    return compiled.as_text().count("tpu_custom_call")


def _momentum(state):
    import numpy as np
    return np.asarray(state.opt_state.slots["momentum"], np.float32)


def _engine_gap(pipe, ref, seed: int) -> float:
    """max |m_kernel - m_jnp| / max |m_jnp| after one optimizer update of
    each pipeline's engine on its fresh state and the same random f32
    gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    moms = []
    for p in (pipe, ref):
        state = p.init_state(jax.random.key(seed))
        leaves, tree = jax.tree_util.tree_flatten(state.params)
        keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
        grads = tree.unflatten([jax.random.normal(k, x.shape, jnp.float32)
                                for k, x in zip(keys, leaves)])
        _, opt_state = jax.jit(p.optimizer.update)(
            grads, state.opt_state, state.params)
        moms.append(np.asarray(opt_state.slots["momentum"], np.float32))
        del state, grads, opt_state
    return float(np.max(np.abs(moms[0] - moms[1])) /
                 np.max(np.abs(moms[1])))


def train_phase(argv: list[str], steps: int) -> None:
    import jax
    import numpy as np

    from repro.kernels import ops as kops
    from repro.launch import train
    from repro.train.pipeline import compiled_peak_bytes

    args = train.parse_args(argv)
    job = train.build_job(args)
    pipe = job.pipeline
    first = next(job.host_batches)
    _say(f"train: {job.cfg.name} layers={job.cfg.num_layers} "
         f"d_model={job.cfg.d_model} vocab={job.cfg.vocab_size} "
         f"batch={args.batch}x{args.seq} accum={args.accum_steps} "
         f"precision={args.precision} mesh={dict(job.mesh.shape)}")

    ref = train.build_job(args, use_pallas=False).pipeline
    gap = _engine_gap(pipe, ref, args.seed)
    _say(f"train: kernel vs jnp engine, one update on the same random "
         f"gradients: max |dm| = {gap!r} of max |m| (bound {ENGINE_RTOL})")
    _require(gap <= ENGINE_RTOL, "kernel update strays from jnp update")

    # the jnp engine's first step, kept on the host for the comparison
    state = ref.init_state(jax.random.key(args.seed))
    state, m = ref(state, first)
    ref_loss, ref_mom = float(m["loss"]), _momentum(state)
    del state, ref

    state = pipe.init_state(jax.random.key(args.seed))
    t0 = time.perf_counter()
    compiled = pipe.lower(state, first).compile()
    kernels = _kernel_launches(compiled)
    _say(f"train: step compiled in {time.perf_counter() - t0:.1f}s, "
         f"compiled peak {compiled_peak_bytes(compiled)} bytes")
    _require(kops.resolve_use_pallas("auto"), "auto did not pick the kernels")
    _require(kernels > 0, "no Pallas kernel (tpu_custom_call) in the step")
    _say(f"train: optimizer engine = Pallas megakernels "
         f"({kernels} tpu_custom_call in the compiled step)")

    state, m = pipe(state, first)
    loss0 = float(m["loss"])
    dev = float(np.max(np.abs(_momentum(state) - ref_mom)))
    scale = float(np.max(np.abs(ref_mom)))
    _say(f"train: kernel vs jnp engine, whole step on batch 0: loss "
         f"{loss0!r} vs {ref_loss!r}; max |dm| = {dev / scale!r} of "
         f"max |m| (bound {STEP_RTOL})")
    _require(abs(loss0 - ref_loss) <= STEP_RTOL * abs(ref_loss),
             "same params and batch gave another loss")
    _require(dev <= STEP_RTOL * scale, "kernel step strays from jnp step")
    del ref_mom

    state, hist, dt = train.run_job(job, state, steps - 1, log_every=1)
    losses = [loss0] + [h["loss"] for h in hist]
    _say(f"train: {steps} steps, losses {losses} ({dt:.1f}s wall for "
         f"the last {steps - 1}, compile included)")
    _require(np.all(np.isfinite(losses)), "non-finite loss")
    _require(losses[-1] < losses[0], "loss did not fall")


# ------------------------------------------------------------------ serve

def serve_phase(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.serve import ServeEngine

    model = build_model(cfg)
    params = model.init(jax.random.key(seed))
    capacity = 256
    engine = ServeEngine(model, params, cfg, slots=4, capacity=capacity)
    _say(f"serve: {cfg.name} layers={cfg.num_layers} "
         f"attention={'flash-decode kernel' if engine.use_flash else 'jnp'}"
         f" slots=4 capacity={capacity}")
    _require(engine.use_flash, "ServeEngine did not pick the flash kernel")

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in SERVE_PROMPTS]
    outs = engine.generate(prompts, SERVE_NEW_TOKENS)
    _say(f"serve: {len(outs)} requests answered, "
         f"{sum(len(o) for o in outs)} tokens")

    forward = jax.jit(lambda p, t: model.forward(p, t)[0])
    prefill = jax.jit(lambda p, t: model.prefill(p, t, cache_len=capacity))
    decode = jax.jit(lambda p, c, t: model.decode_step(p, c, t,
                                                       use_flash=True))
    worst = 0.0
    for prompt, out in zip(prompts, outs):
        _require(len(out) == SERVE_NEW_TOKENS,
                 f"{len(out)} tokens for {SERVE_NEW_TOKENS} asked")
        seq = np.concatenate([prompt, out]).astype(np.int32)
        ref = np.asarray(forward(params, seq[None])[0], np.float32)
        n = len(prompt)
        logits, cache = prefill(params, jnp.asarray(prompt[None]))
        got = [np.asarray(logits[0], np.float32)]
        for tok in out[:-1]:
            logits, cache = decode(params, cache,
                                   jnp.asarray([[tok]], jnp.int32))
            got.append(np.asarray(logits[0, -1], np.float32))
        got = np.stack(got)
        want = ref[n - 1:n - 1 + len(out)]
        bound = DECODE_ATOL + DECODE_RTOL * float(np.max(np.abs(want)))
        dev = float(np.max(np.abs(got - want)))
        worst = max(worst, dev / bound)
        _require(dev <= bound, f"decode logits stray from forward: {dev}")
        top2 = np.sort(want, axis=-1)[:, -2:]
        tie = (top2[:, 1] - top2[:, 0]) < bound
        wrong = (out != np.argmax(want, axis=-1)) & ~tie
        _require(not wrong.any(),
                 f"greedy tokens differ at {wrong.nonzero()}")
        _say(f"serve: prompt {n:3d}: decode vs forward max |dlogit| = "
             f"{dev!r} (bound {bound!r}); greedy tokens agree")
    _say(f"serve: worst deviation {worst!r} of its bound")


# ------------------------------------------------------------- four chips

def zero_phase(argv: list[str], steps: int) -> None:
    import jax
    import numpy as np

    from repro.launch import train
    from repro.train import TrainPipeline

    args = train.parse_args(argv)
    job = train.build_job(args)
    batches = [next(job.host_batches) for _ in range(steps)]
    rep = job.pipeline
    zero = TrainPipeline(job.model, rep.optimizer, job.cfg,
                         accum_steps=args.accum_steps,
                         precision=args.precision, mesh=job.mesh, zero=True)
    _say(f"zero: {job.cfg.name} mesh={dict(job.mesh.shape)} "
         f"batch={args.batch}x{args.seq} accum={args.accum_steps} "
         f"{steps} steps")
    runs = {}
    for name, pipe in (("replicated", rep), ("zero", zero)):
        state = pipe.init_state(jax.random.key(args.seed))
        compiled = pipe.lower(state, batches[0]).compile()
        engine = ("Pallas megakernels" if _kernel_launches(compiled)
                  else "jnp engine")
        if name == "zero":
            mom = state.opt_state.slots["momentum"]
            devs = {s.device for s in mom.addressable_shards}
            rows = {s.data.shape[0] for s in mom.addressable_shards}
            _say(f"zero: momentum {mom.shape} in {len(devs)} shards of "
                 f"{rows} rows on devices {sorted(d.id for d in devs)}")
            _require(len(devs) == 4 and rows == {mom.shape[0] // 4},
                     "ZeRO state is not split over four devices")
        losses = []
        for b in batches:
            state, m = pipe(state, b)
            losses.append(float(m["loss"]))
        params = [np.asarray(x, np.float32)
                  for x in jax.tree_util.tree_leaves(state.params)]
        runs[name] = (losses, params)
        _say(f"zero: {name} ({engine}) losses {losses}")
        del state
    (l_rep, p_rep), (l_zero, p_zero) = runs["replicated"], runs["zero"]
    np.testing.assert_allclose(l_zero, l_rep, rtol=ZERO_LOSS_RTOL,
                               atol=1e-6, err_msg="ZeRO losses drifted")
    worst = 0.0
    for a, b in zip(p_rep, p_zero):
        top = float(np.max(np.abs(a)))
        np.testing.assert_allclose(b, a, rtol=BF16_ULP,
                                   atol=ZERO_PARAM_ATOL * top,
                                   err_msg="ZeRO params drifted")
        worst = max(worst, float(np.max(np.abs(a - b))) / top)
    _say(f"zero: ZeRO == replicated: losses within rtol "
         f"{ZERO_LOSS_RTOL}, params max |dp| / max |p| = {worst!r}")


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args(argv)
    if args.steps < 2:
        ap.error("--steps must be at least 2 (the first is compared)")
    _import_repo()
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0

    import jax

    from repro.configs import get_config
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _say(f"chip_smoke: FAILED: no TPU (platform {dev.platform!r})")
        return 1
    if len(devices) < args.chips:
        _say(f"chip_smoke: FAILED: --chips {args.chips} but "
             f"{len(devices)} device(s)")
        return 1
    _say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"compile cache {cache} ({entries} entries at start)")

    seed = ["--seed", str(args.seed)]
    if args.chips == 4:
        phases = [("zero", lambda: zero_phase(
            TRAIN_ARGV + seed + ["--mesh", "4x1"], 3))]
    else:
        phases = [("train", lambda: train_phase(
                      TRAIN_ARGV + seed + ["--mesh", "1x1"], args.steps)),
                  ("serve", lambda: serve_phase(get_config(SERVE_ARCH),
                                                args.seed))]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
            _say(f"phase {name}: ok ({time.perf_counter() - t0:.1f}s)")
        except Exception:
            traceback.print_exc()
            _say(f"phase {name}: FAILED")
            failed.append(name)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

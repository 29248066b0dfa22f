"""Training entry point: ``--arch`` selects any registered config; runs a
real (CPU-scale or TPU) training job through the large-batch
:class:`~repro.train.pipeline.TrainPipeline` — microbatched gradient
accumulation, bf16/f32 precision policy, and a donated mesh-aware step
fed by the double-buffered :class:`~repro.data.ShardedLoader`.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --reduced --steps 50 --batch 32 --seq 64 --optimizer lars
  PYTHONPATH=src python -m repro.launch.train --arch lenet-mnist \
      --steps 200 --batch 4096 --accum-steps 8 --precision bf16 \
      --optimizer lars --lr 0.02 --warmup 20 --lr-policy linear
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Iterator, Optional

import jax
import numpy as np

from repro.checkpoint import restore_train_state, save_train_state
from repro.configs import ARCHS, get_config
from repro.core import get_optimizer, schedules
from repro.core.scaling import scaled_lr
from repro.data import (ShardedLoader, TokenTaskConfig, batch_iterator,
                        synthetic_mnist, token_batches)
from repro.distributed.sharding import batch_pspecs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_from_spec
from repro.launch.overrides import apply_overrides
from repro.models import build_model
from repro.train import TrainPipeline, make_eval_step, train_loop


def lm_batches(cfg, batch: int, seq: int, seed: int = 0):
    """Host-side numpy batches (device placement is the loader's job)."""
    task = TokenTaskConfig(vocab_size=min(cfg.vocab_size, 512), seed=seed)
    for toks in token_batches(task, batch=batch, seq_len=seq, seed=seed):
        b = {"tokens": np.asarray(toks[:, :seq], np.int32)}
        if cfg.family == "encdec":
            b["frames"] = np.zeros((batch, cfg.encoder_seq, cfg.d_model),
                                   np.float32)
        if cfg.family == "vlm":
            b["image_embeddings"] = np.zeros(
                (batch, cfg.num_image_tokens, cfg.d_model), np.float32)
        yield b


def make_lr_schedule(args) -> schedules.Schedule:
    """Paper recipe: batch-size scaling of (--lr, --base-batch), then
    either warmup + polynomial decay (--warmup > 0, You et al. — the
    packaged ``schedules.large_batch_lr`` recipe) or a flat scaled LR."""
    if args.warmup > 0:
        return schedules.large_batch_lr(
            args.lr, args.base_batch, args.batch, total_steps=args.steps,
            warmup_steps=args.warmup, policy=args.lr_policy)
    return schedules.constant(
        scaled_lr(args.lr, args.base_batch, args.batch, args.lr_policy))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (CPU-scale) variant")
    ap.add_argument("--optimizer", default="lars",
                    choices=("lars", "lamb", "sgd", "adamw"))
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--lr-policy", default="none",
                    choices=("none", "linear", "sqrt"),
                    help="batch-size LR scaling from (--lr, --base-batch)")
    ap.add_argument("--base-batch", type=int, default=32)
    ap.add_argument("--warmup", type=int, default=0,
                    help="warmup steps; >0 switches to the You et al. "
                    "warmup + polynomial-decay schedule")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32,
                    help="GLOBAL batch size (split into --accum-steps "
                    "microbatches)")
    ap.add_argument("--accum-steps", type=int, default=1,
                    help="microbatches accumulated per optimizer update")
    ap.add_argument("--opt-state-dtype", default="f32",
                    choices=("f32", "int8"),
                    help="optimizer slot storage: int8 codes + per-"
                         "segment f32 scales (master weights stay f32)")
    ap.add_argument("--precision", default="f32", choices=("f32", "bf16"),
                    help="bf16: bf16 compute + f32 master weights")
    ap.add_argument("--mesh", default="auto",
                    help="'auto' (all devices on data) or DATAxMODEL, "
                    "e.g. 4x2")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default=None,
                    help="save the FULL TrainState here when done")
    ap.add_argument("--resume", default=None,
                    help="restore a TrainState checkpoint before training")
    ap.add_argument("--set", action="append", default=[],
                    metavar="FIELD=VALUE",
                    help="config override, e.g. --set loss_chunk=8")
    args = ap.parse_args(argv)
    if args.batch % args.accum_steps:
        raise SystemExit(f"--batch {args.batch} must be divisible by "
                         f"--accum-steps {args.accum_steps}")
    return args


@dataclasses.dataclass
class TrainJob:
    """Everything a training run is built from, before any step runs."""

    cfg: Any
    model: Any
    mesh: Any
    pipeline: TrainPipeline
    batch: int                 # global batch
    host_batches: Iterator     # global numpy batches, generated from --seed
    eval_batches: Optional[list]


def build_job(args, *, use_pallas: bool | str = "auto") -> TrainJob:
    """Config, model, mesh, optimizer, pipeline and host data for
    ``args``. ``use_pallas`` forces LARS's optimizer engine ("auto": the
    megakernels on TPU, the jnp engine elsewhere)."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = apply_overrides(cfg, args.set)
    model = build_model(cfg)
    mesh = mesh_from_spec(args.mesh)
    engine = {} if use_pallas == "auto" else {"use_pallas": use_pallas}
    opt = get_optimizer(args.optimizer, learning_rate=make_lr_schedule(args),
                        slot_dtype=args.opt_state_dtype, **engine)
    pipeline = TrainPipeline(model, opt, cfg,
                             accum_steps=args.accum_steps,
                             precision=args.precision, mesh=mesh)
    eval_batches = None
    if cfg.family == "cnn":
        # size the procedural dataset to the global batch —
        # batch_iterator's epoch wrap can only cover a shortfall of one
        # dataset, and a silently smaller batch would train with an LR
        # scaled for the REQUESTED batch
        x_tr, y_tr, x_te, y_te = synthetic_mnist(max(8192, args.batch))
        host_batches = batch_iterator(x_tr, y_tr, batch=args.batch,
                                      seed=args.seed)
        eval_batches = [{"x": x_te[i:i + 256], "y": y_te[i:i + 256]}
                        for i in range(0, len(x_te), 256)]
    else:
        host_batches = lm_batches(cfg, args.batch, args.seq, args.seed)
    return TrainJob(cfg, model, mesh, pipeline, args.batch, host_batches,
                    eval_batches)


def run_job(job: TrainJob, state, steps: int, *, log_every: int = 10):
    """Train ``steps`` steps through the double-buffered loader.
    Returns (state, history, wall seconds)."""
    bspecs = batch_pspecs(job.cfg, job.mesh, batch=job.batch)
    batches = ShardedLoader(job.host_batches, job.mesh, bspecs)
    eval_fn = make_eval_step(job.model, job.cfg) if job.eval_batches \
        else None
    t0 = time.perf_counter()
    try:
        state, hist = train_loop(job.pipeline, state, batches, steps,
                                 log_every=log_every, eval_fn=eval_fn,
                                 eval_batches=job.eval_batches)
    finally:
        batches.close()
    return state, hist, time.perf_counter() - t0


def main(argv=None) -> None:
    enable_compile_cache()
    args = parse_args(argv)
    job = build_job(args)
    state = job.pipeline.init_state(jax.random.key(args.seed))
    if args.resume:
        state = job.pipeline.place_state(
            restore_train_state(args.resume, state))
        print(f"resumed from {args.resume} "
              f"at step {int(state.opt_state.step)}")
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    micro = args.batch // args.accum_steps
    print(f"arch={job.cfg.name} family={job.cfg.family} params={n_params:,} "
          f"opt={job.pipeline.optimizer.name} lr={args.lr} "
          f"mesh={dict(job.mesh.shape)} global_batch={args.batch} "
          f"micro_batch={micro} accum={args.accum_steps} "
          f"precision={args.precision}")
    state, hist, dt = run_job(job, state, args.steps,
                              log_every=args.log_every)
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({args.steps / dt:.2f} steps/s, "
          f"{args.steps * args.batch / dt:.0f} examples/s)")
    if hist and "eval_accuracy" in hist[-1]:
        print(f"eval accuracy: {hist[-1]['eval_accuracy']:.4f}")
    if args.checkpoint:
        save_train_state(args.checkpoint, state)
        print(f"full TrainState checkpoint -> {args.checkpoint}")


if __name__ == "__main__":
    main()

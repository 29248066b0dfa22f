"""Paper Figs. 2/3/4: SGD vs LARS across a batch-size sweep on the
paper's CNN (§3.1) — test accuracy, train accuracy, generalization error.

Protocol (paper §4): fixed hyperparameters (Table 1) across the sweep,
fixed epoch budget, batch size scaled up until the optimizers separate.
The dataset is the procedural MNIST stand-in (offline container;
DESIGN.md §9), so absolute numbers differ from the paper's MNIST, and the
claims validated are the paper's *shape*:

  C1 both optimizers are comparable at small batch;
  C2 SGD's test accuracy collapses beyond a batch threshold;
  C3 LARS holds materially higher accuracy at large batch;
  C4 generalization error grows much faster for SGD than LARS.

The sweep itself is a :class:`repro.experiments.GridSpec` executed by
the experiment harness (``repro.experiments``): every cell trains
through the large-batch TrainPipeline with in-jit trust-ratio
telemetry, streams a JSONL trajectory into ``--workdir``, and is
resumable mid-grid with ``--resume``. ``--accum-steps`` and
``--precision bf16`` sweep under gradient accumulation / master
weights. ``--accum-bench`` skips the accuracy sweep and instead
measures the execution pipeline itself — a global batch 8x the largest
single-step microbatch, steps/s and compiled peak-memory for f32 vs
bf16 — appending the results to ``BENCH_optimizer.json``.

``--family lm`` runs the token-LM counterpart of the sweep (the paper's
§6 future work): lamb/adamw/lars/sgd cells on a reduced LM config over
the seeded synthetic Markov corpus, eval perplexity as the metric,
optionally under ``--lr-schedule poly_warmup`` (the You et al.
warmup + poly-decay recipe).

Usage: PYTHONPATH=src python -m benchmarks.paper_sweep [--quick]
       PYTHONPATH=src python -m benchmarks.paper_sweep --family lm \
           --optimizers lamb adamw --lr-policy sqrt \
           --lr-schedule poly_warmup
       PYTHONPATH=src python -m benchmarks.paper_sweep --accum-bench
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import lars, schedules
from repro.experiments import GridRunner, GridSpec, aggregate
from repro.experiments.spec import (INIT_LR, LR_DECAY, MOMENTUM,
                                    TRUST_COEF, WEIGHT_DECAY)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.train import TrainPipeline
from repro.train.pipeline import compiled_peak_bytes


# ------------------------------------------------- execution-pipeline bench

def _bench_opt():
    """The sweep's LARS under Table-1 hyperparameters (bench workload)."""
    return lars(schedules.inverse_time_decay(INIT_LR, LR_DECAY),
                momentum=MOMENTUM, weight_decay=WEIGHT_DECAY,
                trust_coefficient=TRUST_COEF)


def accum_bench(*, micro_batch: int = 256, accum_steps: int = 8,
                steps: int = 10, out: str = "BENCH_optimizer.json") -> dict:
    """Benchmark the execution pipeline itself (not accuracy): a global
    batch ``accum_steps``x the largest single-step microbatch, run via
    scan accumulation, for f32 vs bf16 — steps/s and compiled
    peak-memory deltas, merged into ``out`` under
    ``"large_batch_pipeline"`` (the optimizer bench owns the rest of the
    file, so run this after it)."""
    assert accum_steps >= 8, "bench contract: global >= 8x microbatch"
    global_batch = micro_batch * accum_steps
    cfg = get_config("lenet-mnist")
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.random((global_batch, 28, 28, 1)),
                              jnp.float32),
             "y": jnp.asarray(rng.integers(0, 10, global_batch), jnp.int32)}
    rows = []
    for precision in ("f32", "bf16"):
        pipe = TrainPipeline(model, _bench_opt(), cfg,
                             accum_steps=accum_steps, precision=precision)
        state = pipe.init_state(jax.random.key(0))
        peak = compiled_peak_bytes(pipe.lower(state, batch).compile())
        state, m = pipe(state, batch)          # compile + warmup
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = pipe(state, batch)
        jax.block_until_ready(m["loss"])
        dt = (time.perf_counter() - t0) / steps
        rows.append({"precision": precision, "micro_batch": micro_batch,
                     "accum_steps": accum_steps,
                     "global_batch": global_batch,
                     "steps_per_s": 1.0 / dt,
                     "examples_per_s": global_batch / dt,
                     "peak_bytes": peak,
                     "loss": float(m["loss"])})
        peak_s = f"{peak / 1e6:8.1f} MB" if peak is not None else "   n/a"
        print(f"{precision:5s} global={global_batch} (micro={micro_batch} "
              f"x accum={accum_steps})  {1.0 / dt:6.2f} steps/s  "
              f"{global_batch / dt:9.0f} ex/s  peak {peak_s}", flush=True)

    by = {r["precision"]: r for r in rows}
    deltas = {"bf16_vs_f32_steps_per_s":
              by["bf16"]["steps_per_s"] / by["f32"]["steps_per_s"] - 1.0}
    if by["f32"]["peak_bytes"] and by["bf16"]["peak_bytes"]:
        deltas["bf16_vs_f32_peak_bytes"] = \
            by["bf16"]["peak_bytes"] / by["f32"]["peak_bytes"] - 1.0
    section = {"backend": jax.default_backend(), "rows": rows,
               "deltas": deltas}
    payload = {}
    if out and os.path.exists(out):
        with open(out) as f:
            payload = json.load(f)
    payload["large_batch_pipeline"] = section
    if out:
        with open(out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"merged large_batch_pipeline section into {out}")
    return section


# ----------------------------------------------------------------- sweep

def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny sweep for CI (seconds, not minutes)")
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--family", default="cnn", choices=("cnn", "lm"),
                    help="cnn: the paper's LeNet/MNIST study; lm: the "
                    "token-LM extension (eval perplexity) on a reduced "
                    "LM config")
    ap.add_argument("--arch", default=None,
                    help="model config for --family lm "
                    "(default smollm-135m)")
    ap.add_argument("--seq-len", type=int, default=64,
                    help="LM training sequence length")
    ap.add_argument("--optimizers", nargs="+",
                    default=["sgd", "lars"])
    ap.add_argument("--trust-coef", type=float, default=TRUST_COEF)
    ap.add_argument("--lr-policy", default="none",
                    choices=("none", "linear", "sqrt"))
    ap.add_argument("--lr-schedule", default="inverse_time",
                    choices=("inverse_time", "poly", "poly_warmup"),
                    help="per-cell LR shape; poly_warmup = the You et "
                    "al. large_batch_lr recipe (warmup + poly decay)")
    ap.add_argument("--base-lr", type=float, default=None,
                    help="sgd/lars base LR (default: Table 1's 0.01 for "
                    "cnn, the lm_smoke-tuned 0.3 for lm)")
    ap.add_argument("--n-train", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="write the aggregated report JSON here")
    ap.add_argument("--workdir", default=None,
                    help="harness run directory (default "
                    "runs/<sweep name>)")
    ap.add_argument("--resume", action="store_true",
                    help="continue an interrupted sweep in --workdir")
    ap.add_argument("--accum-steps", type=int, default=1,
                    help="microbatches accumulated per update in each cell")
    ap.add_argument("--precision", default="f32", choices=("f32", "bf16"))
    ap.add_argument("--accum-bench", action="store_true",
                    help="skip the accuracy sweep; benchmark the "
                    "accumulation pipeline (f32 vs bf16) into "
                    "BENCH_optimizer.json")
    args = ap.parse_args()

    if args.accum_bench:
        micro, accum = (64, 8) if args.quick else (256, 8)
        accum_bench(micro_batch=micro, accum_steps=accum,
                    steps=3 if args.quick else 10,
                    out=args.out or "BENCH_optimizer.json")
        return

    lm = args.family == "lm"
    if args.quick:
        n_train, n_test = (512, 64) if lm else (2048, 512)
        batches = (16, 64, 256) if lm else (64, 512, 2048)
        epochs = args.epochs or (1 if lm else 6)
    else:
        n_train, n_test = (8192, 512) if lm else (8192, 2048)
        batches = ((16, 64, 256, 1024) if lm
                   else (32, 128, 512, 1024, 2048, 4096, 8192))
        epochs = args.epochs or (4 if lm else 20)
    if args.n_train:
        n_train = args.n_train

    extra = {}
    if lm:
        # the lm_smoke-tuned per-optimizer bases (see experiments.spec)
        extra = dict(family="lm", arch=args.arch or "smollm-135m",
                     seq_len=args.seq_len, vocab_size=512,
                     model_layers=2, model_d_model=192, base_batch=16,
                     adam_base_lr=0.01,
                     base_lr_overrides=(("lars", 1.0), ("lamb", 0.1)))
    base_lr = args.base_lr if args.base_lr is not None \
        else (0.3 if lm else INIT_LR)
    grid = GridSpec(
        name=("lm_" if lm else "") + (
            "paper_sweep_quick" if args.quick else "paper_sweep"),
        optimizers=tuple(args.optimizers), batches=batches,
        precisions=(args.precision,), accum_steps=(args.accum_steps,),
        lr_policies=(args.lr_policy,),
        lr_schedules=(args.lr_schedule,), epochs=epochs,
        n_train=n_train, n_test=n_test, base_lr=base_lr,
        trust_coef=args.trust_coef, **extra)
    workdir = args.workdir or f"runs/{grid.name}"
    if not args.resume and os.path.exists(
            os.path.join(workdir, "manifest.json")):
        # benchmark semantics: a fresh invocation re-measures (the
        # harness CLI keeps the strict refuse-to-clobber behavior)
        print(f"# discarding previous sweep in {workdir} "
              "(pass --resume to continue it)")
        import shutil
        shutil.rmtree(workdir)
    runner = GridRunner(grid, workdir, log=None)

    print(f"# paper sweep via experiment harness: family={args.family} "
          f"epochs={epochs} n_train={n_train} "
          f"optimizers={args.optimizers} lr_policy={args.lr_policy} "
          f"lr_schedule={args.lr_schedule} trust_coef={args.trust_coef} "
          f"workdir={workdir}")
    if lm:
        print(f"{'opt':6s} {'batch':>6s} {'steps':>6s} {'eval_ppl':>9s} "
              f"{'eval_loss':>10s} {'wall':>6s}")
    else:
        print(f"{'opt':6s} {'batch':>6s} {'steps':>6s} {'train':>7s} "
              f"{'test':>7s} {'gen_err':>8s} {'wall':>6s}")

    def on_row(row: dict) -> None:
        if lm:
            print(f"{row['optimizer']:6s} {row['batch']:6d} "
                  f"{row['steps']:6d} {row['eval_ppl']:9.3f} "
                  f"{row['eval_loss']:10.4f} {row['wall_s']:5.1f}s",
                  flush=True)
        else:
            print(f"{row['optimizer']:6s} {row['batch']:6d} "
                  f"{row['steps']:6d} {row['train_acc']:7.4f} "
                  f"{row['test_acc']:7.4f} {row['gen_error']:8.4f} "
                  f"{row['wall_s']:5.1f}s", flush=True)

    manifest = runner.run(resume=args.resume, on_row=on_row)
    payload = aggregate(grid, manifest)
    if args.out:
        from repro.experiments.record import atomic_write_json
        atomic_write_json(args.out, payload)
        print(f"wrote {args.out}")

    for key, val in payload["claims"].items():
        print(f"claim {key}: {val}")


if __name__ == "__main__":
    main()

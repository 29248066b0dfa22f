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
weights.

``--family lm`` runs the token-LM counterpart of the sweep (the paper's
§6 future work): lamb/adamw/lars/sgd cells on a reduced LM config over
the seeded synthetic Markov corpus, eval perplexity as the metric,
optionally under ``--lr-schedule poly_warmup`` (the You et al.
warmup + poly-decay recipe).

Usage: PYTHONPATH=src python -m benchmarks.paper_sweep [--quick]
       PYTHONPATH=src python -m benchmarks.paper_sweep --family lm \
           --optimizers lamb adamw --lr-policy sqrt \
           --lr-schedule poly_warmup
"""

from __future__ import annotations

import argparse
import os

from repro.experiments import GridRunner, GridSpec, aggregate
from repro.experiments.spec import INIT_LR, TRUST_COEF
from repro.launch.compile_cache import enable_compile_cache


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
    args = ap.parse_args()

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

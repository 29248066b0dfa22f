"""Experiment CLI: run a declarative grid end to end, resumably.

Examples::

  # the CI smoke study (2x2: sgd/lars x small/large batch)
  PYTHONPATH=src python -m repro.launch.experiment --grid lars_vs_sgd_smoke

  # the token-LM smoke study: lamb/adamw/lars/sgd x small/large batch on
  # a reduced smollm, eval perplexity as the metric
  PYTHONPATH=src python -m repro.launch.experiment --grid lm_smoke

  # the full paper sweep, interruptible and resumable mid-grid
  PYTHONPATH=src python -m repro.launch.experiment --grid lars_vs_sgd
  PYTHONPATH=src python -m repro.launch.experiment --grid lars_vs_sgd --resume

  # one cell only (debugging / sharding work across machines)
  PYTHONPATH=src python -m repro.launch.experiment --grid lars_vs_sgd \
      --cell lars-b8192-f32-a1-linear-s0

  # the grid as a PBT population (experiments/controller): the seeds
  # axis becomes member slots, base_lr/trust_coef are tuned mid-run by
  # exploit/explore; the pbt block merges into the study's report file
  PYTHONPATH=src python -m repro.launch.experiment --grid pbt_smoke \
      --pbt --population 4 --exploit-every 4

The run directory (``--out-dir``, default ``runs/<grid>``) holds the
manifest and one JSONL trajectory per cell; the aggregated report
(metric-vs-batch table + claim checks) is written to ``--out`` (default:
the grid's registered report file, e.g. ``EXPERIMENTS_<grid>.json`` or
``EXPERIMENTS_lm_lars_vs_lamb.json`` for the LM study) after every
invocation, from whatever cells have completed so far.
"""

from __future__ import annotations

import argparse
import sys

import jax

from repro.experiments import (GRIDS, GridRunner, PopulationController,
                               format_table, get_grid, write_pbt_report,
                               write_report)
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", choices=sorted(GRIDS),
                    help="named grid from the registry")
    ap.add_argument("--list-grids", action="store_true",
                    help="print the registry (name, cells, axes) and exit")
    ap.add_argument("--list-cells", action="store_true",
                    help="print the grid's cell ids and exit")
    ap.add_argument("--out-dir", default=None,
                    help="run directory (default runs/<grid>)")
    ap.add_argument("--out", default=None,
                    help="aggregated report path (default "
                    "EXPERIMENTS_<grid>.json)")
    ap.add_argument("--resume", action="store_true",
                    help="continue an interrupted run of this grid "
                    "(skips completed cells, restores mid-cell "
                    "checkpoints)")
    ap.add_argument("--cell", action="append", default=None,
                    metavar="CELL_ID", help="run only this cell "
                    "(repeatable)")
    ap.add_argument("--checkpoint-every", type=int, default=25,
                    help="steps between mid-cell TrainState checkpoints "
                    "(0 disables; resume then restarts the cell)")
    ap.add_argument("--no-stats", action="store_true",
                    help="skip the in-jit per-layer trust-ratio "
                    "telemetry")
    ap.add_argument("--epochs", type=int, default=None,
                    help="override the grid's epoch budget")
    ap.add_argument("--n-train", type=int, default=None,
                    help="override the grid's train-set size")
    ap.add_argument("--seeds", type=int, nargs="+", default=None,
                    help="override the grid's replicate seeds")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="override an LM grid's training sequence length")
    ap.add_argument("--pbt", action="store_true",
                    help="run the grid as a PBT population: the seeds "
                    "axis becomes member slots and the controller tunes "
                    "base_lr/trust_coef mid-run via exploit/explore")
    ap.add_argument("--population", type=int, default=None,
                    help="PBT members per (optimizer, batch) group "
                    "(sets the grid's seeds axis to 0..N-1)")
    ap.add_argument("--exploit-every", type=int, default=4,
                    help="PBT round length in optimizer steps")
    ap.add_argument("--pbt-seed", type=int, default=0,
                    help="controller rng seed (init jitter + "
                    "exploit/explore perturbations)")
    args = ap.parse_args(argv)

    if args.list_grids:
        for name in sorted(GRIDS):
            g = GRIDS[name]
            print(f"{name}: {len(g.cells())} cells  family={g.family} "
                  f"optimizers={list(g.optimizers)} "
                  f"batches={list(g.batches)} epochs={g.epochs}")
        return 0
    if not args.grid:
        ap.error("--grid is required (or --list-grids)")

    overrides = {}
    if args.population is not None:
        if not args.pbt:
            ap.error("--population requires --pbt")
        overrides["seeds"] = tuple(range(args.population))
    if args.epochs is not None:
        overrides["epochs"] = args.epochs
    if args.n_train is not None:
        overrides["n_train"] = args.n_train
    if args.seeds is not None:
        overrides["seeds"] = tuple(args.seeds)
    if args.seq_len is not None:
        overrides["seq_len"] = args.seq_len
    grid = get_grid(args.grid, **overrides)

    if args.list_cells:
        for cell in grid.cells():
            print(f"{cell.cell_id}  ({cell.steps} steps)")
        return 0

    out_dir = args.out_dir or f"runs/{grid.name}"
    out = args.out or grid.report_file
    runner = GridRunner(grid, out_dir,
                        checkpoint_every=args.checkpoint_every,
                        collect_stats=not args.no_stats)

    if args.pbt:
        ctl = PopulationController(runner,
                                   exploit_every=args.exploit_every,
                                   seed=args.pbt_seed)
        print(f"# pbt {grid.name}: {len(grid.cells())} members -> "
              f"{out_dir} (backend={jax.default_backend()})")
        interrupted = False
        try:
            pbt = ctl.run(resume=args.resume)
        except KeyboardInterrupt:
            from repro.experiments.record import load_json
            pbt = load_json(ctl.manifest_path)
            interrupted = True
            print("interrupted — rerun with --resume to continue",
                  flush=True)
        payload = write_pbt_report(out, grid, pbt, out_dir=out_dir,
                                   backend=jax.default_backend())
        section = payload["pbt"]
        done = sum(m["status"] == "done"
                   for m in section["members"].values())
        print(f"# pbt report ({done}/{len(section['members'])} members "
              f"finished, {section['events']['exploit']} exploits, "
              f"{section['events']['kill']} kills, "
              f"{section['events']['early_stop']} early-stops) -> {out}")
        for name, g in section["groups"].items():
            best = g.get("best")
            if best:
                metric = next(v for k, v in best.items()
                              if k.endswith(("test_acc", "eval_ppl")))
                print(f"  {name}: best {best['cell_id']} "
                      f"(lr {best['base_lr']:.4g}, trust "
                      f"{best['trust_coef']:.4g}) -> {metric}")
        for key, val in section["claims"].items():
            print(f"claim pbt.{key}: {val}")
        return 130 if interrupted else 0

    print(f"# grid {grid.name}: {len(grid.cells())} cells -> {out_dir} "
          f"(backend={jax.default_backend()})")
    interrupted = False
    try:
        manifest = runner.run(resume=args.resume, cell_ids=args.cell)
    except KeyboardInterrupt:
        from repro.experiments.record import load_json
        manifest = load_json(runner.manifest_path)
        interrupted = True
        print("interrupted — rerun with --resume to continue", flush=True)

    payload = write_report(out, grid, manifest,
                           backend=jax.default_backend())
    print(f"# report ({payload['completed_cells']}/"
          f"{payload['total_cells']} cells) -> {out}")
    print(format_table(payload))
    for key, val in payload["claims"].items():
        print(f"claim {key}: {val}")
    return 130 if interrupted else 0


if __name__ == "__main__":
    sys.exit(main())

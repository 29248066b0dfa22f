"""Readings from which the limits of ``correct`` are set, made on the
chip at a cell's own size and kept out of the benchmark's runs:

* the program's sound runs over many seeds (the lower readings);
* the control: the reference computed one precision step below the
  configuration (operands of every matrix product rounded to float8
  e4m3) put in the program's place (the upper reading);
* planted faults of the timed path.

    python3 perfbench/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--control] [--fault half_batch] [--out FILE]

Everything runs in this one process. One JSON line per reading.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--control-seeds", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perfbench import harness
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = harness.load_benchmark()
    cell = harness.workload(bench, args.workload)
    cfg = harness.config(bench, cell["config"])
    mix = harness.traffic(cell["traffic"])
    drv = harness.kind(mix["kind"])
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl_seeds = ([int(s) for s in args.control_seeds.split(",")]
                 if args.control_seeds else seeds[:3])
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec = dict(rec, workload=args.workload,
                   device=jax.devices()[0].device_kind)
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in seeds:
        for kind, value in drv.calibrate(cfg, mix, seed, cell["chips"],
                                         control=args.control
                                         and seed in ctl_seeds,
                                         faults=args.fault
                                         if seed in ctl_seeds else []):
            emit({"seed": seed, "kind": kind, "readings": value})
    return 0


if __name__ == "__main__":
    sys.exit(main())

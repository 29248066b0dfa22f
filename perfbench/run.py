"""Run one cell of the benchmark once and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix come from
``BENCHMARK.json``; ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiled window. The last line
of standard output is one JSON object; the comparison with the plain
reference is printed beside its limits as the last lines of standard
error and under ``checks`` in that object. Without a TPU, or with fewer
chips than the cell asks for, the run fails and prints no result.
"""

from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def process_start() -> float:
    """Epoch seconds at which this process started (Linux), else the
    time this module was first imported."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def fail(msg: str, code: int) -> int:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)
    return code


def chips(n: int) -> list:
    """The first ``n`` TPU chips; RuntimeError where there are fewer."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(f"no TPU (JAX found {devices[0].platform!r})")
    if len(devices) < n:
        raise RuntimeError(f"the cell needs {n} chips, JAX found "
                           f"{len(devices)}")
    return devices[:n]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the compact trace (gzip JSON) here")
    return ap.parse_args(argv)


def result_line(bench, cell, res, devices, traced) -> dict:
    from perfbench import harness, trace as tr
    if traced:
        metrics = harness.read_layers(bench, cell["name"], res["layer_ctx"],
                                      ROOT / "perfbench")
    else:
        metrics = {m["name"]: {"value": res["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in harness.metrics_for(bench, "end_to_end",
                                                cell["name"])}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": res["checks"].ok and res["failed"] == 0,
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": device}
    if traced:
        t = res["trace"]
        device["busy_s"] = tr.busy_s(t)
        device["window_s"] = tr.window_s(t)
        out["breakdown"] = tr.breakdown(t)
    out["checks"] = res["checks"].as_dict()
    return out


def main(argv=None) -> int:
    t_start = process_start()
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program under test ({ROOT}/src/repro) is missing", 3)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perfbench import harness
    bench = harness.load_benchmark(ROOT)
    cell = harness.workload(bench, args.workload)
    cfg = harness.config(bench, cell["config"], ROOT)
    mix = harness.traffic(cell["traffic"], ROOT / "perfbench")

    from repro.launch.compile_cache import enable_compile_cache
    import jax
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = chips(cell["chips"])
    except RuntimeError as e:
        return fail(str(e), 2)
    from perfbench.peaks import peak
    ctx = {"cell": cell, "cfg": cfg, "mix": mix, "seed": args.seed,
           "seconds": args.seconds, "trace": bool(args.trace),
           "chips": cell["chips"], "devices": devices,
           "peak": peak(devices[0].device_kind), "t_start": t_start,
           "keep_trace": args.keep_trace}
    print(f"perfbench: {cell['name']} seed {args.seed} on "
          f"{devices[0].device_kind} x{len(devices)}, compile cache {cache}",
          flush=True)
    res = harness.kind(mix["kind"], ROOT / "perfbench").run(ctx)
    print(f"perfbench: compiles inside the window: "
          f"{res['compiles_in_window']} (traces: {res['traces_in_window']})",
          flush=True)
    line = result_line(bench, cell, res, devices, bool(args.trace))
    res["checks"].print_stderr()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

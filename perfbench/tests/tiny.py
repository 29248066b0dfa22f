"""A throwaway checkout for CPU tests: this benchmark's files plus a tiny
cell (small widths, CPU limits) added as new files and new entries, and
a way to run a cell there with the look for a chip skipped."""

from __future__ import annotations

import contextlib
import importlib.util
import io
import json
import shutil
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[2]
SHRINK = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
              vocab_size=128)
TRAIN_MIX = dict(global_batch=4, seq_len=32, accum_steps=2,
                 limits={"loss_gap": 2e-3, "first_grad_gap": 0.02,
                         "first_grad_diff": 0.05, "change_gap": 0.02})


def checkout(tmp: Path) -> Path:
    """A copy of BENCHMARK.json and perfbench/ with the tiny cell
    ``tiny-lm.train`` added."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "perfbench/configs/smollm-135m.json").read_text())
    cfg.update(SHRINK, name="tiny-lm")
    (root / "perfbench/configs/tiny-lm.json").write_text(json.dumps(cfg))
    mix = json.loads((REPO / "perfbench/traffic/lars-train-16x2048.json")
                     .read_text())
    mix.update(TRAIN_MIX)
    (root / "perfbench/traffic/tiny-train.json").write_text(json.dumps(mix))
    bench["configs"].append({"name": "tiny-lm", "source": "test",
                             "file": "perfbench/configs/tiny-lm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-lm.train", "config": "tiny-lm",
                               "traffic": "tiny-train", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-lm.train")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def load_run(root: Path):
    spec = importlib.util.spec_from_file_location(
        "perfbench_run_" + root.parent.name, root / "perfbench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_cell(root: Path, monkeypatch, cell: str, seed: int = 2 ** 31 + 9,
             seconds: float = 1.0, trace: int = 0):
    """Run a cell on the CPU, the look for a chip skipped; returns the
    parsed result line (or None) and the exit code."""
    from perfbench import peaks
    run = load_run(root)
    monkeypatch.setattr(run, "chips", lambda n: jax.devices()[:n])
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    out = io.StringIO()
    # run.main turns the persistent compile cache on; keep that to this run
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
    finally:
        from jax.experimental.compilation_cache import compilation_cache
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    lines = out.getvalue().strip().splitlines()
    return (json.loads(lines[-1]) if rc == 0 else None), rc

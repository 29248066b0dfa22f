"""What a run is made of, found by name in ``BENCHMARK.json``.

* a configuration: ``configs[*].file`` (JSON);
* a traffic mix: ``traffic/<traffic>.json``, whose ``kind`` names the
  general code ``kinds/<kind>.py`` that runs every mix of that kind;
* a per-layer metric: ``metrics/<metric name>.py``, a reader with
  ``read(ctx) -> float | None`` (None: nothing to read, metric left out).

Adding a configuration, a mix, a metric or a cell is adding files and
entries; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, here: Path = HERE) -> dict:
    return json.loads((here / "traffic" / f"{name}.json").read_text())


def metrics_for(bench: dict, section: str, cell: str) -> list:
    """Entries of ``end_to_end`` or ``per_layer`` that ``cell`` reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def _load(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, here: Path = HERE):
    return _load(here / "metrics" / f"{metric}.py",
                 "perfbench_metric_" + metric.replace(".", "_"))


def kind(name: str, here: Path = HERE):
    return _load(here / "kinds" / f"{name}.py", "perfbench_kind_" + name)


def read_layers(bench: dict, cell: str, ctx: dict, here: Path = HERE) -> dict:
    """Every per-layer metric of ``cell`` that its reader finds."""
    out = {}
    for m in metrics_for(bench, "per_layer", cell):
        v = reader(m["name"], here).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out

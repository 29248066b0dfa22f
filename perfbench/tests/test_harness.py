"""The harness is driven by data: a configuration, a traffic mix and a
per-layer metric are found by name as new files plus new entries;
BENCHMARK.json keeps to its contract; a run without a TPU, or without
the program, fails and prints no result."""

import hashlib
import json
import re
import subprocess
import sys
from pathlib import Path

import numpy as np

from perfbench import harness
from perfbench.tests import tiny

REPO = tiny.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "c"
    import shutil
    shutil.copytree(REPO / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    before = digest(root / "perfbench")
    here = root / "perfbench"
    (here / "configs" / "throwaway.json").write_text(json.dumps(
        {"name": "throwaway", "hidden_size": 8}))
    (here / "traffic" / "throwaway-mix.json").write_text(json.dumps(
        {"kind": "train", "global_batch": 1}))
    (here / "metrics" / "throwaway.metric.py").write_text(
        "def read(ctx):\n    return ctx['x'] * 2\n")
    (here / "metrics" / "throwaway.silent.py").write_text(
        "def read(ctx):\n    return None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "throwaway", "source": "test",
                             "file": "perfbench/configs/throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway.cell",
                               "config": "throwaway",
                               "traffic": "throwaway-mix", "chips": 1,
                               "why": "test"})
    for m in ("throwaway.metric", "throwaway.silent"):
        bench["per_layer"].append({"name": m, "unit": "%",
                                   "better": "higher",
                                   "source": "program_counter",
                                   "layer": "test", "moves": "setup_s",
                                   "workloads": ["throwaway.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    b = harness.load_benchmark(root)
    cell = harness.workload(b, "throwaway.cell")
    assert harness.config(b, cell["config"], root)["hidden_size"] == 8
    assert harness.traffic(cell["traffic"], here)["kind"] == "train"
    assert hasattr(harness.kind("train", here), "run")
    got = harness.read_layers(b, "throwaway.cell", {"x": 21.0}, here)
    assert got == {"throwaway.metric": {"value": 42.0, "unit": "%"}}
    after = digest(root / "perfbench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_benchmark_json_keeps_its_contract():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    here = REPO / "perfbench"
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    assert {w["config"] for w in cells.values()} == set(configs)
    for c in configs.values():
        assert NAME.match(c["name"]) and (REPO / c["file"]).is_file()
        assert c["file"].startswith("perfbench/")
        assert json.loads((REPO / c["file"]).read_text())["name"] == c["name"]
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    setup = [m for m in b["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]

    def cells_of(m):
        return m.get("workloads", list(cells))
    for w in cells.values():
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (here / "traffic" / f"{w['traffic']}.json").is_file()
        mine = [m for m in metrics if w["name"] in cells_of(m)]
        e2e = {m["name"] for m in mine if "bound" in m}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any("layer" in m for m in mine)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(cells_of(m)) <= set(cells)
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert (here / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
        for w in m["workloads"]:
            assert any(w in cells_of(e) for e in b["end_to_end"]
                       if e["name"] == m["moves"])


def test_training_batches_come_from_the_seed(tmp_path):
    """The same seed gives the same rows; another seed other rows; ids
    are drawn over the whole vocabulary."""
    root = tiny.checkout(tmp_path)
    here = root / "perfbench"
    b = harness.load_benchmark(root)
    cfg = harness.config(b, "tiny-lm", root)
    mix = harness.traffic("tiny-train", here)
    drv = harness.kind("train", here)

    def rows(seed):
        return np.stack([np.asarray(t) for t in
                         drv.build(cfg, mix, seed, 1).checked])

    a, c = rows(2 ** 31 + 3), rows(2 ** 31 + 4)
    np.testing.assert_array_equal(a, rows(2 ** 31 + 3))
    assert not np.array_equal(a, c)
    assert a.shape == (drv.CHECKED_STEPS, mix["global_batch"],
                       mix["seq_len"])
    # rows all differ; 384 uniform draws over 128 ids leave about 6 unseen
    assert len({r.tobytes() for r in a.reshape(-1, a.shape[-1])}) == \
        a.shape[0] * a.shape[1]
    V = cfg["vocab_size"]
    assert a.min() >= 0 and a.max() < V
    assert len(np.unique(a)) >= 0.9 * V


def _run(cwd, *extra):
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "smollm-135m.lars-train-16x2048", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, capture_output=True, text=True,
        timeout=300, env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                          "HOME": str(cwd)})


def test_without_the_program_no_result(tmp_path):
    import shutil
    shutil.copytree(REPO / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = _run(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_without_a_tpu_no_result():
    r = _run(REPO)
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
    assert "no TPU" in r.stderr

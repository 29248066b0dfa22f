"""The DeepSeek-V2 training kind on the CPU at a tiny size, the look for a
chip skipped: a sound run is ``correct``; each of the kind's planted
faults (the top-k gates renormalised, one held expert's tokens dropped)
and the control fail it. And the three expert-layer readers give known
values on a small synthetic trace and counter."""

import json

import pytest

from perfbench import harness
from perfbench.tests import tiny

CELL = "tiny-moe.train"
SHRINK = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
              num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16, kv_lora_rank=32, moe_intermediate_size=32,
              router_experts=8, n_routed_experts=4, expert_offset=0,
              num_experts_per_tok=2, n_shared_experts=1,
              first_k_dense_replace=1, num_hidden_layers=3, vocab_size=128)
# CPU limits of the tiny cell: sound runs read up to 0.005, 0.018, 0.10
# and 0.02 over three seeds (routing flips near top-k ties move single
# expert rows); the faults 0.004, 0.055, 0.39 and 0.08 at least, the
# control 0.011, 0.46, 1.5 and 0.45
MIX = dict(global_batch=4, seq_len=32, accum_steps=2,
           limits={"loss_gap": 0.02, "first_grad_gap": 0.04,
                   "first_grad_diff": 0.25, "change_gap": 0.05})


def checkout(tmp):
    """``tiny.checkout`` with the tiny MoE cell added as new files and
    entries."""
    root = tiny.checkout(tmp)
    cfg = json.loads((root / "perfbench/configs/deepseek-v2-lite-ep8.json")
                     .read_text())
    cfg.update(SHRINK, name="tiny-moe")
    (root / "perfbench/configs/tiny-moe.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "perfbench/traffic/lars-train-8x4096.json")
                     .read_text())
    mix.update(MIX)
    (root / "perfbench/traffic/tiny-moe-train.json").write_text(
        json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "perfbench/configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-moe",
                               "traffic": "tiny-moe-train", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return checkout(tmp_path_factory.mktemp("moe"))


def _cell(root):
    bench = harness.load_benchmark(root)
    cell = harness.workload(bench, CELL)
    return (harness.config(bench, cell["config"], root),
            harness.traffic(cell["traffic"], root / "perfbench"),
            harness.kind("train_moe", root / "perfbench"))


def test_sound_moe_training_run_is_correct(root, monkeypatch):
    line, rc = tiny.run_cell(root, monkeypatch, CELL, trace=1)
    assert rc == 0 and line["correct"], line
    assert set(line["checks"]) == {"loss_gap", "first_grad_gap",
                                   "first_grad_diff", "change_gap"}
    got = line["metrics"]
    assert got["moe_load.max_over_mean"]["value"] >= 1.0
    assert "train_step.mfu" in got and "moe_experts.device_ms" not in got


@pytest.mark.parametrize("fault", ["gates_renormalised", "expert_dropped"])
def test_planted_fault_is_not_correct(root, fault):
    cfg, mix, drv = _cell(root)
    got = dict(drv.calibrate(cfg, mix, 2 ** 31 + 21, 1, faults=[fault]))
    assert all(got["program"][k] <= v for k, v in mix["limits"].items())
    assert any(got["fault_" + fault][k] > v
               for k, v in mix["limits"].items()), got


def test_moe_control_fails_a_training_number(root):
    cfg, mix, drv = _cell(root)
    got = dict(drv.calibrate(cfg, mix, 2 ** 31 + 22, 1, control=True))
    assert any(got["control_fp8"][k] > v for k, v in mix["limits"].items())


def _ctx(cfg, **kw):
    trace = {"window_ns": [0, 10_000_000_000],
             "ops": {"/device:TPU:0": [
                 ["%ragged-dot-metadata.1 = (s32[9]) custom-call()", 0,
                  1_000_000],
                 ["%ragged-dot-none.2 = bf16[64,32] custom-call()",
                  2_000_000, 99_000_000],
                 ["%fusion.3 = bf16[64] fusion()", 200_000_000,
                  50_000_000]]},
             "spans": []}
    return dict({"kind": "train", "cfg": cfg, "trace": trace, "steps": 4,
                 "peak": {"bf16_flops": 1e12}, "moe_rows": 1000.0,
                 "moe_load_max_over_mean": 1.25}, **kw)


def test_expert_readers_read_a_synthetic_trace(root):
    cfg, _, _ = _cell(root)
    ctx = _ctx(cfg)
    ms = harness.reader("moe_experts.device_ms", root / "perfbench")
    roof = harness.reader("moe_experts_roofline", root / "perfbench")
    load = harness.reader("moe_load.max_over_mean", root / "perfbench")
    assert ms.read(ctx) == pytest.approx(100.0 / 4)
    # 2 x 3 x 64 x 32 x 1000 rows x 3 FLOPs a step, 4 steps, in 0.1 s
    flops = 2 * 3 * 64 * 32 * 1000 * 3 * 4
    assert roof.read(ctx) == pytest.approx(100.0 * flops / (0.1 * 1e12))
    assert load.read(ctx) == 1.25


def test_expert_readers_read_nothing_without_experts(root):
    cfg, _, _ = _cell(root)
    ctx = _ctx(cfg, moe_rows=None, moe_load_max_over_mean=None)
    ctx["trace"]["ops"]["/device:TPU:0"] = ctx["trace"]["ops"][
        "/device:TPU:0"][2:]
    for name in ("moe_experts.device_ms", "moe_experts_roofline",
                 "moe_load.max_over_mean"):
        assert harness.reader(name, root / "perfbench").read(ctx) is None

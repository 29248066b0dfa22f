"""Roofline analysis unit tests: HLO collective parsing, term math,
config adaptation and input specs (no compilation)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, get_shape
from repro.launch import roofline as RL
from repro.launch.specs import adapt_config, train_batch_specs

HLO = """
  %ar = f32[16,1024]{1,0} all-reduce(f32[16,1024]{1,0} %x), replica_groups={}
  %ag.1 = bf16[4,256]{1,0} all-gather(bf16[1,256]{1,0} %y), dimensions={0}
  %rs = (f32[8]{0}, f32[8]{0}) reduce-scatter(%a, %b), dimensions={0}
  %a2a = f32[2,64]{1,0} all-to-all(f32[2,64]{1,0} %z), dimensions={0}
  %cp-start = f32[32]{0} collective-permute-start(f32[32]{0} %w)
  %cp-done = f32[32]{0} collective-permute-done(%cp-start)
  %ard = f32[99]{0} all-reduce-done(%nope)
  %fake = f32[7]{0} add(f32[7]{0} %p, f32[7]{0} %q)
"""


def test_parse_collectives_types_and_bytes():
    got = RL.parse_collectives(HLO)
    assert got["all-reduce"] == 16 * 1024 * 4
    assert got["all-gather"] == 4 * 256 * 2
    assert got["reduce-scatter"] == 2 * 8 * 4
    assert got["all-to-all"] == 2 * 64 * 4
    assert got["collective-permute"] == 32 * 4
    # -done ops are not double-counted
    assert sum(got.values()) == (16 * 1024 * 4 + 4 * 256 * 2 + 2 * 8 * 4
                                 + 2 * 64 * 4 + 32 * 4)


def test_wire_bytes_all_reduce_2x():
    assert RL.wire_bytes({"all-reduce": 100, "all-gather": 50}) == 250


def test_roofline_terms_and_dominant():
    r = RL.Roofline(arch="a", shape="s", mesh="pod", chips=256,
                    flops_per_device=197e12,         # exactly 1 s compute
                    bytes_per_device=819e9 * 2,      # 2 s memory
                    collective_bytes=0, per_type={"all-gather": 50e9},
                    model_flops=197e12 * 256 * 0.5)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.t_collective == pytest.approx(1.0)
    assert r.dominant == "memory"
    assert r.useful_flops_ratio == pytest.approx(0.5)


def test_adapt_config_long_context_window():
    shape = get_shape("long_500k")
    dense = adapt_config(get_config("qwen2-72b"), shape)
    assert dense.sliding_window == 8192
    mla = adapt_config(get_config("deepseek-v2-236b"), shape)
    assert mla.sliding_window == 0            # MLA keeps the full cache
    hyb = adapt_config(get_config("zamba2-7b"), shape)
    assert hyb.sliding_window == 8192         # shared-attn window
    ssm = adapt_config(get_config("falcon-mamba-7b"), shape)
    assert ssm.sliding_window == 0            # attention-free


def test_adapt_config_keeps_config_without_carve_out():
    shape = get_shape("train_4k")
    for arch in ("granite-moe-3b-a800m", "deepseek-v2-lite", "qwen2-72b"):
        cfg = get_config(arch)
        assert adapt_config(cfg, shape) is cfg


def test_train_batch_specs_shapes():
    cfg = get_config("whisper-base")
    specs = train_batch_specs(cfg, get_shape("train_4k"))
    assert specs["tokens"].shape == (256, 4096)
    assert specs["frames"].shape == (256, cfg.encoder_seq, cfg.d_model)
    assert specs["tokens"].dtype == jnp.int32

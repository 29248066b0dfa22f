"""Config registry tests: analytic parameter counts against the
published sizes, and the reduced-config invariants."""

import pytest

from repro.configs import ARCHS, get_config, param_count


def test_param_count_orders_of_magnitude():
    """Analytic counts should land near the models' nameplate sizes."""
    expect = {
        "qwen2-72b": 72e9, "qwen3-14b": 14e9, "minitron-8b": 8e9,
        "falcon-mamba-7b": 7e9, "zamba2-7b": 7e9, "smollm-135m": 135e6,
        "deepseek-v2-236b": 236e9, "paligemma-3b": 2.6e9,  # text tower
        "deepseek-v2-lite": 15.7e9,
    }
    for name, nominal in expect.items():
        total, active = param_count(get_config(name))
        assert 0.55 * nominal < total < 1.6 * nominal, \
            (name, total, nominal)
        assert active <= total


def test_param_count_counts_leading_dense_layers():
    """DeepSeek-V2-Lite's first layer is dense (an MLP of 10944): 15.7B in
    all, as published; an all-MoE count would differ by that layer."""
    import dataclasses
    cfg = get_config("deepseek-v2-lite")
    total, _ = param_count(cfg)
    assert total == pytest.approx(15.7e9, rel=0.005)
    all_moe, _ = param_count(dataclasses.replace(cfg, first_k_dense=0))
    moe_ffn = (cfg.d_model * cfg.num_experts
               + (cfg.num_experts + cfg.num_shared_experts)
               * 3 * cfg.d_model * cfg.moe_d_ff)
    assert all_moe - total == moe_ffn - 3 * cfg.d_model * cfg.d_ff


def test_moe_active_less_than_total():
    for name in ("deepseek-v2-236b", "deepseek-v2-lite",
                 "granite-moe-3b-a800m"):
        total, active = param_count(get_config(name))
        assert active < 0.5 * total    # top-k of many experts


def test_reduced_configs_within_caps():
    for name, cfg in ARCHS.items():
        r = cfg.reduced()
        assert r.num_layers <= 2
        assert r.d_model <= 512
        assert r.num_experts <= 4
        assert r.vocab_size <= 512
        if cfg.num_heads:
            assert r.num_heads % max(r.num_kv_heads, 1) == 0


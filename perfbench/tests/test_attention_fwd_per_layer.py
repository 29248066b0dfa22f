"""The attention forward kernel's runs per layer application, read by the
name the trace gives it: 2 where the backward recomputes each layer's
forward, 1 where the forward's residuals are kept."""

import pytest

from perfbench.tests.test_attention_kernels import HAND, op, read


def kept_step(t):
    """One optimizer step of two layers, one microbatch, whose backward
    reuses the forward's residuals: the forward per layer, then the
    backward's dq and dk/dv per layer."""
    evs = []
    for i in range(2):
        evs.append(op("splash_mqa_fwd_residuals.1", t + 10 * i, 5))
    for i in range(2):
        evs.append(op("splash_mqa_dq_no_residuals.1", t + 40 + 30 * i, 6))
        evs.append(op("splash_mqa_dkv_no_residuals.1", t + 50 + 30 * i, 7))
    return evs


# window [0, 1000) ns, two steps; ops outside it and ops of other names
# do not count
KEPT = {**HAND, "ops": {"/device:TPU:0": (
    [op("splash_mqa_fwd_residuals.1", -50, 20)] + kept_step(100)
    + [op("splash_mqa_fwd_no_residuals.1", 300, 30),
       op("my_splash_mqa_fwd_residuals.1", 340, 30)]
    + kept_step(500)
    + [op("splash_mqa_fwd_residuals.1", 1000, 20)])}}


def test_recomputed_forward_reads_two():
    # the hand trace's steps recompute each layer's forward
    assert read("attention_kernels.fwd_per_layer", HAND) == \
        pytest.approx(2.0)
    # two microbatches a step would need twice the runs
    assert read("attention_kernels.fwd_per_layer", HAND, accum=2) == \
        pytest.approx(1.0)


def test_kept_residuals_read_one():
    assert read("attention_kernels.fwd_per_layer", KEPT) == \
        pytest.approx(1.0)
    assert read("attention_kernels.fwd_per_layer", KEPT, steps=1) == \
        pytest.approx(2.0)


def test_averages_device_planes():
    two = {**HAND, "ops": {"/device:TPU:0": HAND["ops"]["/device:TPU:0"],
                           "/device:TPU:1": KEPT["ops"]["/device:TPU:0"]}}
    assert read("attention_kernels.fwd_per_layer", two) == \
        pytest.approx(1.5)


def test_nothing_to_read():
    jnp_only = {**HAND, "ops": {"/device:TPU:0": [
        ["%fusion.442 = f32[8,3,3,2048]{3,2,1,0} fusion(...)", 10, 500]]}}
    no_fwd = {**HAND, "ops": {"/device:TPU:0": [
        op("splash_mqa_dkv_no_residuals.1", 100, 7),
        op("splash_mqa_fwd_residuals.1", 1200, 5)]}}
    assert read("attention_kernels.fwd_per_layer", jnp_only) is None
    assert read("attention_kernels.fwd_per_layer", no_fwd) is None
    assert read("attention_kernels.fwd_per_layer", HAND, steps=0) is None
    assert read("attention_kernels.fwd_per_layer", HAND,
                kind="serve") is None
    assert read("attention_kernels.fwd_per_layer", None) is None

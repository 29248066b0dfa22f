"""The attention kernels read by the names the trace gives them: their
device time per optimizer step, and the share of the step's layer
applications whose backward ran in the dk/dv kernel."""

import pytest

from perfbench import harness

BF = "bf16[24,2048,64]{2,1,0:T(8,128)(2,1)}"
CFG = {"hidden_size": 576, "num_attention_heads": 9,
       "num_key_value_heads": 3, "head_dim": 64, "intermediate_size": 1536,
       "num_hidden_layers": 2, "vocab_size": 49152}


def op(name, start, dur):
    return [f"%{name} = ({BF}) custom-call({BF} %p), "
            'custom_call_target="tpu_custom_call"', start, dur]


def step(t):
    """One optimizer step of two layers, one microbatch: forward and
    recompute, then the backward's dq and dk/dv per layer."""
    evs = []
    for i in range(2):
        evs.append(op("splash_mqa_fwd_residuals.1", t + 10 * i, 5))
    for i in range(2):
        evs.append(op("splash_mqa_fwd_residuals.2", t + 30 + 30 * i, 5))
        evs.append(op("splash_mqa_dq_no_residuals.1", t + 40 + 30 * i, 6))
        evs.append(op("splash_mqa_dkv_no_residuals.1", t + 50 + 30 * i, 7))
    return evs


# window [0, 1000) ns, two steps; ops outside it and ops of other names
# do not count
HAND = {
    "window_ns": [0, 1000],
    "ops": {"/device:TPU:0": (
        [op("splash_mqa_dkv_no_residuals.1", -50, 20)] + step(100)
        + [["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
            300, 100],
           op("splash_mqa_dkv_no_residuals_extra.1", 420, 30),
           op("my_splash_mqa_dq_no_residuals.1", 460, 30)]
        + step(500) + [op("splash_mqa_dkv_no_residuals.1", 1200, 20)])},
    "spans": [["pb.window", 0, 1000]],
}
PER_STEP_NS = 2 * 5 + 2 * (5 + 6 + 7)


def read(metric, trace, steps=2, kind="train", accum=1):
    return harness.reader(metric).read(
        {"kind": kind, "trace": trace, "steps": steps, "cfg": CFG,
         "mix": {"accum_steps": accum}})


def test_kernel_time_by_name_per_step():
    assert read("attention_kernels.device_ms", HAND) == pytest.approx(
        PER_STEP_NS * 1e-6)
    assert read("attention_kernels.device_ms", HAND, steps=1) == \
        pytest.approx(2 * PER_STEP_NS * 1e-6)


def test_coverage_counts_dkv_runs_per_layer_and_microbatch():
    assert read("attention_kernels.coverage", HAND) == pytest.approx(100.0)
    # two microbatches a step would need twice the runs
    assert read("attention_kernels.coverage", HAND, accum=2) == \
        pytest.approx(50.0)


def test_coverage_averages_device_planes():
    two = {**HAND, "ops": {"/device:TPU:0": HAND["ops"]["/device:TPU:0"],
                           "/device:TPU:1": step(100)}}
    assert read("attention_kernels.coverage", two) == pytest.approx(75.0)


@pytest.mark.parametrize("metric", ["attention_kernels.device_ms",
                                    "attention_kernels.coverage"])
def test_nothing_to_read(metric):
    jnp_only = {**HAND, "ops": {"/device:TPU:0": [
        ["%fusion.442 = f32[8,3,3,2048]{3,2,1,0} fusion(...)", 10, 500]]}}
    assert read(metric, jnp_only) is None
    assert read(metric, HAND, steps=0) is None
    assert read(metric, HAND, kind="serve") is None
    assert read(metric, None) is None

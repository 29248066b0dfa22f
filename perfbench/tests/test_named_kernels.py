"""The LARS update's kernels read by the names the program gives them:
``lars_norms`` and ``lars_apply`` (not ``lars_apply_q8``), per optimizer
step, and nothing where the trace names them otherwise."""

from pathlib import Path

import pytest

from perfbench import harness
from perfbench import trace as tr

DATA = Path(__file__).parents[1] / "data"
BUF = "f32[263144,512]{1,0:T(8,128)}"


def op(name, start, dur):
    return [f"%{name} = ({BUF}, {BUF}) custom-call({BUF} %copy.1), "
            'custom_call_target="tpu_custom_call"', start, dur]


# window [0, 1000) ns, two optimizer steps on one plane
HAND = {
    "window_ns": [0, 1000],
    "ops": {"/device:TPU:0": [
        op("lars_norms.1", 100, 20), op("lars_apply.3", 120, 60),
        ["%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 180, 300],
        op("lars_norms.1", 600, 22), op("lars_apply.3", 622, 58),
        op("lars_apply_q8.1", 700, 90), op("lars_norms_extra.2", 800, 50),
    ]},
    "spans": [["pb.window", 0, 1000]],
}


def read(metric, trace, steps=2, kind="train"):
    return harness.reader(metric).read(
        {"kind": kind, "trace": trace, "steps": steps})


@pytest.mark.parametrize("metric,ns", [("lars_norms.device_ms", 20 + 22),
                                       ("lars_apply.device_ms", 60 + 58)])
def test_kernel_time_by_name_per_step(metric, ns):
    assert read(metric, HAND) == pytest.approx(ns * 1e-6 / 2)
    assert read(metric, HAND, steps=1) == pytest.approx(ns * 1e-6)


def test_q8_apply_and_longer_names_do_not_count():
    only = {**HAND, "ops": {"/device:TPU:0": [
        op("lars_apply_q8.1", 700, 90), op("lars_norms_extra.2", 800, 50)]}}
    assert read("lars_norms.device_ms", only) is None
    assert read("lars_apply.device_ms", only) is None


@pytest.mark.parametrize("metric", ["lars_norms.device_ms",
                                    "lars_apply.device_ms"])
def test_nothing_to_read(metric):
    assert read(metric, HAND, steps=0) is None
    assert read(metric, HAND, kind="serve") is None
    assert read(metric, None) is None


@pytest.mark.parametrize("metric", ["lars_norms.device_ms",
                                    "lars_apply.device_ms"])
def test_unnamed_kernels_of_an_older_program_read_nothing(metric):
    """A trace recorded on the chip before the kernels had names calls
    them ``%step.2`` and ``%step.3``: the readers leave the metric out."""
    t = tr.load(str(DATA / "train16_chip_trace.json.gz"))
    assert tr.op_seconds(t, r"^%step\.[23] =")
    assert read(metric, t, steps=1) is None


def test_named_kernels_sum_to_the_update_read_by_shape():
    """Both readers together read what ``lars_update.device_ms`` reads by
    the superbuffer's shape, when the kernels carry their names."""
    t = tr.load(str(DATA / "train16_chip_trace.json.gz"))
    renamed = {**t, "ops": {k: [[n.replace("%step.2 =", "%lars_norms.2 =")
                                  .replace("%step.3 =", "%lars_apply.3 ="),
                                 s, d] for n, s, d in evs]
                            for k, evs in t["ops"].items()}}
    ctx = {"kind": "train", "trace": renamed, "steps": 1,
           "superbuffer": (263144, 512)}
    total = harness.reader("lars_update.device_ms").read(ctx)
    parts = [harness.reader(m).read(ctx)
             for m in ("lars_norms.device_ms", "lars_apply.device_ms")]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(total)

"""``correct`` comes out true for a sound run and false when the timed
path is broken underneath, for each fault its cell can have: a training
step that returns its state unchanged; half of the batch left out, the
mean taken over the rest. And the control (the reference one precision step down, put in the
program's place) fails a training number. Tiny cells on the CPU, the
look for a chip skipped; the limits are the tiny cells' own."""

import jax
import pytest

from perfbench import reference as ref
from perfbench.tests import tiny

TRAIN = "tiny-lm.train"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("faults"))


def test_sound_training_run_is_correct(root, monkeypatch):
    line, rc = tiny.run_cell(root, monkeypatch, TRAIN)
    assert rc == 0 and line["correct"], line
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"loss_gap", "first_grad_gap",
                                   "first_grad_diff", "change_gap"}


def test_state_left_unchanged_is_not_correct(root, monkeypatch):
    import repro.train as rtrain

    class Frozen(rtrain.TrainPipeline):
        def __init__(self, *a, **kw):
            super().__init__(*a, **dict(kw, donate=False))

        def __call__(self, state, batch):
            _, metrics = super().__call__(state, batch)
            return state, metrics

    monkeypatch.setattr(rtrain, "TrainPipeline", Frozen)
    line, rc = tiny.run_cell(root, monkeypatch, TRAIN)
    assert rc == 0 and not line["correct"]
    assert line["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(root, monkeypatch):
    import repro.train as rtrain

    class Half(rtrain.TrainPipeline):
        def __call__(self, state, batch):
            n = batch["tokens"].shape[0] // 2
            return super().__call__(state, {"tokens": batch["tokens"][:n]})

    monkeypatch.setattr(rtrain, "TrainPipeline", Half)
    line, rc = tiny.run_cell(root, monkeypatch, TRAIN)
    assert rc == 0 and not line["correct"]


def test_control_fails_a_training_number(root, monkeypatch):
    from perfbench import harness
    bench = harness.load_benchmark(root)
    cell = harness.workload(bench, TRAIN)
    cfg = harness.config(bench, cell["config"], root)
    mix = harness.traffic(cell["traffic"], root / "perfbench")
    drv = harness.kind("train", root / "perfbench")
    got = dict(drv.calibrate(cfg, mix, 2 ** 31 + 21, 1, control=True))
    assert all(got["program"][k] <= v for k, v in mix["limits"].items())
    assert any(got["control_fp8"][k] > v for k, v in mix["limits"].items())


def test_a_matrix_gradient_turned_the_wrong_way_fails_the_diff():
    """A matrix leaf's first gradient with its norm right and its
    direction wrong: the trust ratio hides it from the norm, not from the
    difference."""
    import numpy as np
    from perfbench import harness
    drv = harness.kind("train")
    rng = np.random.default_rng(0)
    mom = {"w": rng.normal(size=(8, 8)).astype(np.float32),
           "s": rng.normal(size=(8,)).astype(np.float32)}
    norms = {k: float(np.linalg.norm(v)) for k, v in mom.items()}
    refr = {"losses": [1.0], "first_grad": norms, "first_momentum": mom,
            "change": norms}
    turned = dict(mom, w=mom["w"][::-1].copy())
    got = drv.readings({"losses": [1.0], "first_momentum": turned,
                        "change": norms}, refr)
    assert got["first_grad_gap"] < 1e-6
    assert got["first_grad_diff"] > 0.5
    assert got["worst_leaves"][1] == "w"


def test_fp8_rounding_is_one_step_below_bf16():
    x = jax.numpy.float32(1.0 + 2 ** -6)
    assert float(ref.fp8_round(x)) == 1.0

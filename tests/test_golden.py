"""Golden-trajectory regression pins, CNN and token-LM families.

Seeded runs — LeNet/MNIST for sgd and lars at two batch sizes, and a
reduced-smollm token LM for lamb and adamw at batch 32 — pin the first
20 step losses and the final per-layer trust-ratio table in
``tests/golden/*.json``. Any numeric drift in the optimizer substrate,
the packing layout, or the train pipeline trips these immediately —
while legitimate protocol changes regenerate them explicitly::

    PYTHONPATH=src python tests/test_golden.py --regen

The suite asserts the pins under the CURRENT device count in-process,
and re-execs itself under 1 AND 8 forced host devices (subprocess, same
pattern as tests/test_pipeline.py) so both device-count regimes are
pinned. Under 8 devices the lars/b128 run additionally goes through a
(8, 1) data-parallel mesh and must track the same golden within a
looser tolerance. A deliberate 1e-3 lr perturbation must FAIL the
tolerance (sanity-checked as its own test per family: the pins have
teeth).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import adamw, grad_stats, lamb, lars, sgd
from repro.data import (TokenTaskConfig, batch_iterator, synthetic_mnist,
                        token_batches)
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train import TrainPipeline

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
# (family, optimizer, batch) — family selects workload + golden file.
RUNS = [("cnn", "sgd", 32), ("cnn", "sgd", 128),
        ("cnn", "lars", 32), ("cnn", "lars", 128),
        ("cnn", "lars_int8", 32),
        ("lm", "lamb", 32), ("lm", "adamw", 32)]
STEPS = 20
LR = 0.05
LM_LR = 0.01           # adam-family base rate for the LM pins
LM_SEQ = 32
LM_VOCAB = 256
TRUST_COEF = 0.01
WEIGHT_DECAY = 1e-4
# Tolerances are per (family, batch), placed from measurement: the cnn
# b32 runs are bit-stable across forced host device counts (<= 2e-7
# relative drift — the small convs never split across the CPU client's
# thread partitions), while the b128 runs see ~2.6e-3 loss / ~5e-3
# trust-ratio drift between 1 and 8 forced devices (different intra-op
# reduction partitioning, compounded over 20 steps). The 1e-3 lr
# perturbation moves b32 lars losses 1.6e-3 — an order of magnitude
# above the tight tolerance, so the pin keeps teeth where it is
# tightest. The LM b32 runs measured <= 3.2e-6 relative loss drift and
# <= 3.6e-5 trust drift between 1 and 8 forced devices (matmuls over
# the reduced d_model=144 stay within one thread partition), so they
# pin at the same tight 1e-4/1e-3 with ~30x margin; their 1e-3 lr
# perturbation must clear 10x the loss rtol (checked below).
RTOLS = {("cnn", 32): 1e-4, ("cnn", 128): 5e-3, ("lm", 32): 1e-4}
# Trust ratios divide by the grad norm, so once a run trains hard (sgd
# at b128 reaches loss 1.6 by step 20) the ratio amplifies the same
# thread-partitioning noise to a few percent — 10% still catches any
# real norm/packing regression (those shift ratios by factors).
TRUST_RTOLS = {("cnn", 32): 1e-3, ("cnn", 128): 0.1, ("lm", 32): 1e-3}
ATOL = 1e-6
# Data-parallel mesh run (b128): cross-device reduction order differs.
MESH_RTOL = 5e-3
MESH_TRUST_RTOL = 0.1
RTOL = RTOLS[("cnn", 32)]  # the tight pin the perturbation tests probe


def _tols(family: str, opt_name: str, batch: int) -> tuple[float, float]:
    """(loss rtol, trust rtol) for one pinned run. The int8-momentum pin
    (lars_int8) shares the b32 class bars: requantization is a
    deterministic elementwise map, and the measured 1-vs-8-forced-device
    drift matches the f32 b32 runs (~1e-7 relative — the small convs
    never split across thread partitions, so no code ever flips)."""
    return RTOLS[(family, batch)], TRUST_RTOLS[(family, batch)]


def _golden_path(family: str, opt_name: str, batch: int) -> str:
    tag = f"{opt_name}_lm_b{batch}" if family == "lm" \
        else f"{opt_name}_b{batch}"
    return os.path.join(GOLDEN_DIR, f"{tag}.json")


def _make_opt(opt_name: str, lr: float):
    if opt_name == "sgd":
        return sgd(lr, momentum=0.9, weight_decay=WEIGHT_DECAY)
    if opt_name == "lars":
        return lars(lr, momentum=0.9, weight_decay=WEIGHT_DECAY,
                    trust_coefficient=TRUST_COEF)
    if opt_name == "lars_int8":
        # the quantized-state pin: same rule, momentum stored as int8
        # codes + per-block scales (requantized every step)
        return lars(lr, momentum=0.9, weight_decay=WEIGHT_DECAY,
                    trust_coefficient=TRUST_COEF, slot_dtype="int8")
    if opt_name == "lamb":
        return lamb(lr, weight_decay=WEIGHT_DECAY)
    return adamw(lr, weight_decay=WEIGHT_DECAY)


def _workload(family: str, batch: int):
    """(cfg, model, batch iterator) for the pinned run."""
    if family == "cnn":
        cfg = get_config("lenet-mnist")
        x_tr, y_tr, _, _ = synthetic_mnist(256, 8, seed=0)
        raw = batch_iterator(x_tr, y_tr, batch=batch, seed=0)
        it = ({"x": jnp.asarray(b["x"]), "y": jnp.asarray(b["y"])}
              for b in raw)
    else:
        # the lm_smoke reduction of smollm: 2 layers, d_model 144,
        # vocab 256 — the same model family the LM experiment grids run
        cfg = get_config("smollm-135m").reduced(
            max_layers=2, max_d_model=128, max_vocab=LM_VOCAB)
        task = TokenTaskConfig(vocab_size=LM_VOCAB, seed=0)
        raw = token_batches(task, batch=batch, seq_len=LM_SEQ, seed=0)
        it = ({"tokens": jnp.asarray(t)} for t in raw)
    return cfg, build_model(cfg), it


def run_trajectory(family: str, opt_name: str, batch: int, *,
                   lr: float = None, mesh=None, zero: bool = False) -> dict:
    """The pinned workload: 20 seeded steps, losses + final trust table."""
    lr = lr if lr is not None else (LM_LR if family == "lm" else LR)
    cfg, model, it = _workload(family, batch)
    stats_fn = grad_stats.stats_hook(eta=TRUST_COEF,
                                     weight_decay=WEIGHT_DECAY)
    pipe = TrainPipeline(model, _make_opt(opt_name, lr), cfg,
                         donate=False, mesh=mesh, zero=zero,
                         stats_fn=stats_fn)
    state = pipe.init_state(jax.random.key(7))
    losses = []
    metrics = {}
    for _ in range(STEPS):
        state, metrics = pipe(state, next(it))
        losses.append(float(metrics["loss"]))
    # pin trust ratios of ADAPTED layers only (effective rank > 1,
    # i.e. per stacked slice): a bias's or norm-scale's raw ratio
    # divides by a near-zero grad norm — hypersensitive fp noise for a
    # quantity LARS/LAMB never apply (skip_adaptation_1d)
    from repro.treepath import path_str
    marker_fn = getattr(model, "stacked_marker", None)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    marker = marker_fn(shapes) if marker_fn is not None else None
    flat = jax.tree_util.tree_leaves_with_path(state.params)
    stacked = {path_str(p): bool(s) for (p, _), s in zip(
        flat, jax.tree_util.tree_leaves(marker))} if marker is not None \
        else {path_str(p): False for p, _ in flat}
    ranks = {path_str(p): np.ndim(leaf) - (1 if stacked[path_str(p)]
                                           else 0)
             for p, leaf in flat}
    trust = {layer: np.atleast_1d(
                 np.asarray(jax.device_get(t["trust_ratio"]),
                            np.float64)).tolist()
             for layer, t in metrics["stats"].items()
             if ranks[layer] > 1}
    return {"meta": {"family": family, "steps": STEPS, "lr": lr,
                     "batch": batch, "optimizer": opt_name,
                     "trust_coef": TRUST_COEF,
                     "weight_decay": WEIGHT_DECAY},
            "losses": losses, "final_trust": trust}


def _compare(got: dict, golden: dict, *, rtol: float, label: str,
             trust_rtol: float) -> None:
    np.testing.assert_allclose(
        got["losses"], golden["losses"], rtol=rtol, atol=ATOL,
        err_msg=f"{label}: step-loss trajectory drifted from golden")
    assert set(got["final_trust"]) == set(golden["final_trust"]), label
    for layer, vals in golden["final_trust"].items():
        np.testing.assert_allclose(
            got["final_trust"][layer], vals, rtol=trust_rtol, atol=ATOL,
            err_msg=f"{label}: final trust ratio of {layer} drifted")


def _load_golden(family: str, opt_name: str, batch: int) -> dict:
    path = _golden_path(family, opt_name, batch)
    assert os.path.exists(path), \
        f"missing golden {path} — run `python tests/test_golden.py --regen`"
    with open(path) as f:
        return json.load(f)


# -------------------------------------------------------------- pytest

@pytest.mark.parametrize("family,opt_name,batch", RUNS)
def test_golden_trajectory(family, opt_name, batch):
    got = run_trajectory(family, opt_name, batch)
    rtol, trust_rtol = _tols(family, opt_name, batch)
    _compare(got, _load_golden(family, opt_name, batch),
             rtol=rtol, trust_rtol=trust_rtol,
             label=f"{family}/{opt_name}/b{batch}")


def _assert_perturbation_breaks(family: str, opt_name: str, batch: int,
                                lr: float) -> None:
    golden = _load_golden(family, opt_name, batch)
    got = run_trajectory(family, opt_name, batch, lr=lr + 1e-3)
    rel = np.abs(np.asarray(got["losses"]) - np.asarray(golden["losses"])) \
        / np.abs(np.asarray(golden["losses"]))
    rtol, trust_rtol = _tols(family, opt_name, batch)
    assert rel.max() > 10 * rtol, (
        f"lr+1e-3 only moved {family}/{opt_name} losses by "
        f"{rel.max():.2e} relative — the {rtol} tolerance has no teeth")
    with pytest.raises(AssertionError):
        _compare(got, golden, rtol=rtol, trust_rtol=trust_rtol,
                 label=f"perturbed {family}/{opt_name}")


def test_lr_perturbation_breaks_the_pin():
    """A 1e-3 lr perturbation must exceed the tolerance by step 20 —
    otherwise the pin could not catch a real optimizer regression."""
    _assert_perturbation_breaks("cnn", "lars", 32, LR)


def test_lm_lr_perturbation_breaks_the_pin():
    """Same teeth check for the token-LM family's LAMB pin."""
    _assert_perturbation_breaks("lm", "lamb", 32, LM_LR)


def test_int8_lr_perturbation_breaks_the_pin():
    """Teeth check for the quantized-momentum pin: the int8 trajectory
    must still resolve an lr perturbation above its tolerance —
    quantization noise does not wash out the pin's sensitivity."""
    _assert_perturbation_breaks("cnn", "lars_int8", 32, LR)


_SUBPROC_MARKER = "REPRO_GOLDEN_SUBPROC"


@pytest.mark.parametrize("devices", [1, 8])
def test_golden_under_forced_device_count(devices):
    """Re-exec the full check (both families) under N forced host
    devices (plus the 8-device data-parallel mesh variant) — golden
    trajectories must hold in every device-count regime."""
    if os.environ.get(_SUBPROC_MARKER):
        pytest.skip("already in subprocess")
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.pathsep.join(sys.path),
        **{_SUBPROC_MARKER: "1"})
    out = subprocess.run([sys.executable, __file__, "--check"], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout + out.stderr


# ----------------------------------------------------- regen / subproc

def _check_main() -> int:
    failures = []
    for family, opt_name, batch in RUNS:
        got = run_trajectory(family, opt_name, batch)
        rtol, trust_rtol = _tols(family, opt_name, batch)
        try:
            _compare(got, _load_golden(family, opt_name, batch),
                     rtol=rtol, trust_rtol=trust_rtol,
                     label=f"{family}/{opt_name}/b{batch}")
            print(f"ok {family}/{opt_name}/b{batch}")
        except AssertionError as e:
            failures.append(f"{family}/{opt_name}/b{batch}: {e}")
    if len(jax.devices()) >= 8:
        mesh = make_mesh((8, 1), ("data", "model"))
        got = run_trajectory("cnn", "lars", 128, mesh=mesh)
        try:
            _compare(got, _load_golden("cnn", "lars", 128),
                     rtol=MESH_RTOL, trust_rtol=MESH_TRUST_RTOL,
                     label="lars/b128 on (8,1) mesh")
            print("ok lars/b128 on (8,1) mesh")
        except AssertionError as e:
            failures.append(f"lars/b128 mesh: {e}")
    for f in failures:
        print("FAIL", f)
    return 1 if failures else 0


def _regen_main() -> int:
    os.makedirs(GOLDEN_DIR, exist_ok=True)
    for family, opt_name, batch in RUNS:
        got = run_trajectory(family, opt_name, batch)
        with open(_golden_path(family, opt_name, batch), "w") as f:
            json.dump(got, f, indent=1)
        print(f"wrote {_golden_path(family, opt_name, batch)} "
              f"(final loss {got['losses'][-1]:.4f})")
    return 0


if __name__ == "__main__":
    if "--regen" in sys.argv:
        sys.exit(_regen_main())
    if "--check" in sys.argv:
        sys.exit(_check_main())
    print(__doc__)
    sys.exit(2)

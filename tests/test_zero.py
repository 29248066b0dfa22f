"""ZeRO-sharded optimizer-state tests.

The packed substrate's ZeRO mode (``build_layout(shards=N)`` +
``TrainPipeline(zero=True)``) row-shards every optimizer slot buffer
across the mesh ``data`` axis. Its correctness contract has three legs,
each pinned here:

* **placement must not change numbers** — without a mesh a ZeRO layout
  is just a padded replicated buffer, bit-identical to ``shards=1``
  (checked in-process); under an (8, 1) forced-host-device mesh every
  golden run from tests/test_golden.py must reproduce with
  ``zero=True`` at the existing mesh tolerances (subprocess re-exec,
  same pattern as the golden suite);
* **pad rows are inert** — provably zero f32 rows / zero int8 codes
  with unit scales, through arbitrarily many update steps;
* **checkpoints are layout-independent** — a snapshot taken under one
  shard count restores byte-identically under any other (the npz layer
  strips / re-pads the pad rows).

Also pins the lifted ``fuse_update`` mesh gate: explicit ``True`` is
now VALID under any pure data-parallel mesh (and still rejected under
a model-parallel one).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import test_golden  # noqa: E402  (RUNS, tolerances, run_trajectory)

from repro.configs import get_config                     # noqa: E402
from repro.core import lars, packing                     # noqa: E402
from repro.launch.mesh import make_mesh                  # noqa: E402
from repro.models import build_model                     # noqa: E402
from repro.train import TrainPipeline, TrainState        # noqa: E402

SHARDS = 8


def _lenet_params_and_marker():
    model = build_model(get_config("lenet-mnist"))
    params = model.init(jax.random.key(0))
    marker = model.stacked_marker(
        jax.eval_shape(model.init, jax.random.key(0)))
    return params, marker


def _fake_grads(params, step: int):
    """Deterministic, param-shaped, step-varying gradients."""
    leaves = jax.tree_util.tree_leaves(params)
    treedef = jax.tree_util.tree_structure(params)
    grads = [0.01 * (i + 1) * jnp.cos(p.astype(jnp.float32) + step)
             for i, p in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, grads)


def _run_steps(opt, params, marker, *, zero_shards: int, steps: int = 5):
    state = opt.init(params, stacked=marker, zero_shards=zero_shards) \
        if zero_shards > 1 else opt.init(params, stacked=marker)
    p = params
    for i in range(steps):
        p, state = opt.update(_fake_grads(p, i), state, p, stacked=marker)
    return p, state


# ------------------------------------------------------------- layout

def test_layout_pads_rows_to_shard_multiple():
    params, marker = _lenet_params_and_marker()
    from repro.core.optim_base import normalize_stacked
    stacked = normalize_stacked(params, marker)
    base = packing.build_layout(params, stacked)
    lay = packing.build_layout(params, stacked, shards=SHARDS)
    assert lay.shards == SHARDS
    assert lay.base_rows == base.total_rows
    assert lay.total_rows % (SHARDS * lay.block_rows) == 0
    assert lay.pad_rows == lay.total_rows - base.total_rows
    # pack round-trips exactly and the pad region is all zero
    buf = packing.pack(lay, params)
    assert buf.shape == (lay.total_rows, lay.lane)
    np.testing.assert_array_equal(
        np.asarray(buf)[lay.base_rows:], 0.0)
    restored = packing.unpack(lay, buf)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # per-slice norms ignore the pad rows: bit-equal to the unpadded
    # layout's (same f32 partial-sum tree, pad rows masked out)
    np.testing.assert_array_equal(
        np.asarray(packing.slice_sumsq(lay, buf)),
        np.asarray(packing.slice_sumsq(base, packing.pack(base, params))))


@pytest.mark.parametrize("slot_dtype", ["f32", "int8"])
def test_offmesh_zero_update_bit_identical(slot_dtype):
    """Without a mesh the sharding constraints no-op, so a ZeRO layout
    must train the EXACT shards=1 trajectory — padding alone changes
    nothing."""
    params, marker = _lenet_params_and_marker()
    opt = lars(0.05, momentum=0.9, weight_decay=1e-4,
               trust_coefficient=0.01, slot_dtype=slot_dtype)
    p_ref, s_ref = _run_steps(opt, params, marker, zero_shards=1)
    p_z, s_z = _run_steps(opt, params, marker, zero_shards=SHARDS)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_z)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # slot contents agree on the real rows (the padded buffer's tail is
    # checked separately below)
    for k, ref in s_ref.slots.items():
        got = np.asarray(s_z.slots[k])
        np.testing.assert_array_equal(got[:np.asarray(ref).shape[0]],
                                      np.asarray(ref),
                                      err_msg=f"slot {k}")


def test_int8_pad_blocks_stay_inert():
    """Pad rows of a quantized slot stay exactly zero codes with unit
    scales through updates (the amax==0 guard), so cross-shard-count
    restores are byte-identical."""
    params, marker = _lenet_params_and_marker()
    opt = lars(0.05, momentum=0.9, slot_dtype="int8")
    _, opt_state = _run_steps(opt, params, marker, zero_shards=SHARDS)
    lay = opt_state.layout
    base_blocks = lay.base_rows // lay.block_rows
    codes = np.asarray(opt_state.slots["momentum"])
    scales = np.asarray(opt_state.slots["momentum_scale"])
    np.testing.assert_array_equal(codes[lay.base_rows:], 0)
    np.testing.assert_array_equal(scales[base_blocks:], 1.0)
    # the f32 weight buffer's pad rows stay zero too
    wbuf = np.asarray(opt_state.slots[packing.WEIGHT_SLOT])
    np.testing.assert_array_equal(wbuf[lay.base_rows:], 0.0)


# -------------------------------------------------------- checkpoints

@pytest.mark.parametrize("slot_dtype", ["f32", "int8"])
@pytest.mark.parametrize("restore_shards", [1, 4])
def test_checkpoint_restores_across_shard_counts(tmp_path, slot_dtype,
                                                 restore_shards):
    """A snapshot written under shards=8 restores BYTE-identically into
    a template built for a different shard count (incl. unsharded):
    the npz strips pad rows on save and re-pads per the template."""
    from repro.checkpoint import restore_train_state, save_train_state
    params, marker = _lenet_params_and_marker()
    opt = lars(0.05, momentum=0.9, slot_dtype=slot_dtype)
    p, s = _run_steps(opt, params, marker, zero_shards=SHARDS, steps=3)
    path = str(tmp_path / "state.npz")
    save_train_state(path, TrainState(params=p, opt_state=s))

    tmpl_opt = opt.init(params, stacked=marker,
                        zero_shards=restore_shards) \
        if restore_shards > 1 else opt.init(params, stacked=marker)
    template = TrainState(params=params, opt_state=tmpl_opt)
    restored = restore_train_state(path, template)

    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(restored.params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    src_lay, dst_lay = s.layout, restored.opt_state.layout
    for k, src in s.slots.items():
        src_a, dst_a = np.asarray(src), np.asarray(restored.opt_state.slots[k])
        if src_a.ndim == 2 and src_a.shape[0] == src_lay.total_rows:
            src_a, dst_a = src_a[:src_lay.base_rows], dst_a[:dst_lay.base_rows]
        elif src_a.ndim == 2 and src_a.shape[0] == src_lay.num_blocks:
            src_a = src_a[:src_lay.base_rows // src_lay.block_rows]
            dst_a = dst_a[:dst_lay.base_rows // dst_lay.block_rows]
        assert src_a.tobytes() == dst_a.tobytes(), f"slot {k}"
    # the restored state CONTINUES identically to the original
    p2a, _ = _continue(opt, p, s, marker)
    p2b, _ = _continue(opt, restored.params, restored, marker)
    for a, b in zip(jax.tree_util.tree_leaves(p2a),
                    jax.tree_util.tree_leaves(p2b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _continue(opt, p, state, marker, steps: int = 2):
    s = state.opt_state if isinstance(state, TrainState) else state
    for i in range(steps):
        p, s = opt.update(_fake_grads(p, 100 + i), s, p, stacked=marker)
    return p, s


# ------------------------------------------------------- fuse_update

def test_fuse_update_true_valid_on_pure_data_mesh():
    """The old gate rejected explicit fuse_update=True under ANY mesh;
    it is now valid whenever the mesh is pure data-parallel."""
    from repro.data import synthetic_mnist
    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    cfg = get_config("lenet-mnist")
    pipe = TrainPipeline(build_model(cfg), lars(0.05, momentum=0.9), cfg,
                         accum_steps=2, mesh=mesh, fuse_update=True,
                         donate=False)
    x, y, _, _ = synthetic_mnist(32, 8, seed=0)
    state = pipe.init_state(jax.random.key(0))
    state, metrics = pipe(state, {"x": jnp.asarray(x[:16]),
                                  "y": jnp.asarray(y[:16])})
    assert np.isfinite(float(metrics["loss"]))


# ------------------------------------- forced-device-count parity runs

_SUBPROC_MARKER = "REPRO_ZERO_SUBPROC"


def test_zero_parity_under_8_forced_devices():
    """Re-exec the golden parity check under 8 forced host devices:
    every pinned run (sgd/lars f32+int8 on LeNet, lamb/adamw on the
    token LM) must reproduce its golden trajectory with zero=True on an
    (8, 1) mesh at the existing mesh tolerances, the fused-epilogue
    ZeRO step must match the replicated mesh step, and a model-parallel
    mesh must still reject fuse_update=True."""
    if os.environ.get(_SUBPROC_MARKER) \
            or os.environ.get(test_golden._SUBPROC_MARKER):
        pytest.skip("already in subprocess")
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=os.pathsep.join(sys.path),
        **{_SUBPROC_MARKER: "1"})
    out = subprocess.run([sys.executable, __file__, "--check"], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stdout + out.stderr


def _fused_zero_check(mesh) -> None:
    """accum_steps=4 with the fused packed epilogue under ZeRO must
    track the unfused replicated-mesh step (same mesh tolerance as the
    golden parity runs — reduce-scatter re-brackets the reductions)."""
    from repro.data import synthetic_mnist
    cfg = get_config("lenet-mnist")
    model = build_model(cfg)
    x, y, _, _ = synthetic_mnist(256, 8, seed=0)
    losses = {}
    for name, kw in [("zero_fused", dict(zero=True, fuse_update=True)),
                     ("replicated", dict(zero=False, fuse_update=False))]:
        pipe = TrainPipeline(model, lars(0.05, momentum=0.9,
                                         weight_decay=1e-4,
                                         trust_coefficient=0.01),
                             cfg, accum_steps=4, mesh=mesh, **kw,
                             donate=False)
        state = pipe.init_state(jax.random.key(7))
        run = []
        for i in range(10):
            lo, hi = (i * 128) % 256, (i * 128) % 256 + 128
            state, m = pipe(state, {"x": jnp.asarray(x[lo:hi]),
                                    "y": jnp.asarray(y[lo:hi])})
            run.append(float(m["loss"]))
        losses[name] = run
    np.testing.assert_allclose(
        losses["zero_fused"], losses["replicated"],
        rtol=test_golden.MESH_RTOL, atol=test_golden.ATOL,
        err_msg="fused ZeRO step drifted from the replicated mesh step")


def _check_main() -> int:
    assert len(jax.devices()) >= 8, "needs 8 forced host devices"
    mesh = make_mesh((8, 1), ("data", "model"))
    failures = []
    for family, opt_name, batch in test_golden.RUNS:
        got = test_golden.run_trajectory(family, opt_name, batch,
                                         mesh=mesh, zero=True)
        try:
            test_golden._compare(
                got, test_golden._load_golden(family, opt_name, batch),
                rtol=test_golden.MESH_RTOL,
                trust_rtol=test_golden.MESH_TRUST_RTOL,
                label=f"zero {family}/{opt_name}/b{batch}")
            print(f"ok zero {family}/{opt_name}/b{batch}")
        except AssertionError as e:
            failures.append(f"zero {family}/{opt_name}/b{batch}: {e}")
    try:
        _fused_zero_check(mesh)
        print("ok fused zero step vs replicated mesh step")
    except AssertionError as e:
        failures.append(f"fused zero: {e}")
    # model-parallel mesh still rejects the explicit fuse
    mp_mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("lenet-mnist")
    pipe = TrainPipeline(build_model(cfg), lars(0.05, momentum=0.9), cfg,
                         accum_steps=2, mesh=mp_mesh, fuse_update=True,
                         donate=False)
    from repro.data import synthetic_mnist
    x, y, _, _ = synthetic_mnist(32, 8, seed=0)
    state = pipe.init_state(jax.random.key(0))
    try:
        pipe(state, {"x": jnp.asarray(x[:16]), "y": jnp.asarray(y[:16])})
        failures.append("fuse_update=True on a model-parallel mesh "
                        "did not raise")
    except ValueError:
        print("ok fuse_update=True rejected on model-parallel mesh")
    for f in failures:
        print("FAIL", f)
    return 1 if failures else 0


if __name__ == "__main__":
    if "--check" in sys.argv:
        sys.exit(_check_main())
    print(__doc__)
    sys.exit(2)

"""Large-batch TrainPipeline tests: accumulation equivalence (bit-exact
at accum=1/f32, trust-ratio-preserving at accum=k), bf16 master weights,
the prefetching loader, full-TrainState checkpointing, and the paper LR
recipes. The 8-device mesh equivalence re-execs in a subprocess (same
pattern as test_sharding) so this module never pollutes the process
device count.
"""

import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_train_state, save_train_state
from repro.configs import get_config
from repro.core import lars, packing, schedules
from repro.data import Prefetcher, ShardedLoader
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train import (TrainPipeline, create_train_state, make_train_step,
                         train_loop)


def _lenet():
    cfg = get_config("lenet-mnist")
    return cfg, build_model(cfg)


def _mnist_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": jnp.asarray(rng.random((n, 28, 28, 1)), jnp.float32),
            "y": jnp.asarray(rng.integers(0, 10, n), jnp.int32)}


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


# ------------------------------------------------------------ equivalence

def test_accum1_f32_bit_identical_to_make_train_step():
    """The pipeline with accum=1/f32 IS today's step — bit-for-bit over
    several steps (the acceptance contract for the refactor)."""
    cfg, model = _lenet()
    opt = lars(0.05, trust_coefficient=0.01)
    batch = _mnist_batch(32)

    ref_state = create_train_state(model, opt, jax.random.key(0))
    ref_step = jax.jit(make_train_step(model, opt, cfg))
    pipe = TrainPipeline(model, opt, cfg, accum_steps=1, precision="f32",
                         donate=False)
    state = pipe.init_state(jax.random.key(0))

    for _ in range(3):
        ref_state, ref_m = ref_step(ref_state, batch)
        state, m = pipe(state, batch)
    assert np.asarray(ref_m["loss"]).tobytes() == \
        np.asarray(m["loss"]).tobytes()
    for a, b in zip(_leaves(ref_state), _leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("accum", [2, 4])
def test_accum_k_matches_single_step_on_full_batch(accum):
    """accum=k on batch B must match ONE step on the same global batch:
    same mean gradient, hence the same LARS trust ratios — asserted via
    the momentum slots (lr * lambda * (g + beta*w) embeds the ratio)."""
    cfg, model = _lenet()
    opt = lars(0.05, trust_coefficient=0.01)
    batch = _mnist_batch(64, seed=1)

    ref = TrainPipeline(model, opt, cfg, accum_steps=1, donate=False)
    s_ref = ref.init_state(jax.random.key(1))
    acc = TrainPipeline(model, opt, cfg, accum_steps=accum, donate=False)
    s_acc = acc.init_state(jax.random.key(1))

    for _ in range(2):
        s_ref, m_ref = ref(s_ref, batch)
        s_acc, m_acc = acc(s_acc, batch)
    np.testing.assert_allclose(float(m_acc["loss"]), float(m_ref["loss"]),
                               rtol=1e-5)
    for a, b in zip(_leaves(s_ref.params), _leaves(s_acc.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    mom_ref = s_ref.opt_state.slots["momentum"]
    mom_acc = s_acc.opt_state.slots["momentum"]
    for a, b in zip(_leaves(mom_ref), _leaves(mom_acc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("opt_name", ["lars", "lamb"])
def test_fused_epilogue_matches_two_pass(opt_name):
    """fuse_update=True (update reads the scan-accumulated superbuffer
    in place, per-layer grad norms finalized once on it) vs
    fuse_update=False (unpack to a mean-grad pytree, then the two-pass
    update): identical up to summation order in the LARS grad norm
    (measured <= 6e-8 param drift over 4 steps at accum=4; LAMB/SGD are
    bit-identical — pack is linear and exact in f32)."""
    from repro.core import lamb as make_lamb
    cfg, model = _lenet()
    opt = lars(0.05, trust_coefficient=0.01) if opt_name == "lars" \
        else make_lamb(0.01)
    batch = _mnist_batch(64, seed=3)
    states, metrics = {}, {}
    for fuse in (True, False):
        pipe = TrainPipeline(model, opt, cfg, accum_steps=4, donate=False,
                             fuse_update=fuse)
        s = pipe.init_state(jax.random.key(4))
        for _ in range(4):
            s, m = pipe(s, batch)
        states[fuse], metrics[fuse] = s, m
    np.testing.assert_allclose(float(metrics[True]["loss"]),
                               float(metrics[False]["loss"]), rtol=1e-6)
    for a, b in zip(_leaves(states[True].params),
                    _leaves(states[False].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_fused_epilogue_matches_two_pass_bf16_int8():
    """The full large-batch stack — bf16 compute, f32 master, int8
    momentum — fused vs two-pass at accum=4 stays within the same
    tolerance class (quantized slots see identical inputs either way;
    only the LARS norm summation order differs)."""
    cfg, model = _lenet()
    opt = lars(0.05, trust_coefficient=0.01, slot_dtype="int8")
    batch = _mnist_batch(64, seed=5)
    losses = {}
    params = {}
    for fuse in (True, False):
        pipe = TrainPipeline(model, opt, cfg, accum_steps=4,
                             precision="bf16", donate=False,
                             fuse_update=fuse)
        s = pipe.init_state(jax.random.key(6))
        for _ in range(4):
            s, m = pipe(s, batch)
        losses[fuse], params[fuse] = float(m["loss"]), s.params
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
    for a, b in zip(_leaves(params[True]), _leaves(params[False])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-6)


def test_fuse_update_validation():
    """fuse_update=True demands the conditions the fusion needs (packed
    layout, accum>1, no mesh); "auto" silently falls back instead."""
    cfg, model = _lenet()
    with pytest.raises(ValueError, match="fuse_update"):
        TrainPipeline(model, lars(0.05), cfg, fuse_update="sometimes")
    pipe = TrainPipeline(model, lars(0.05), cfg, accum_steps=1,
                         fuse_update=True)
    with pytest.raises(ValueError, match="accum"):
        pipe(pipe.init_state(jax.random.key(0)), _mnist_batch(32))
    # auto at accum=1 runs the unfused (bit-identity) path fine
    pipe = TrainPipeline(model, lars(0.05), cfg, accum_steps=1,
                         donate=False)
    pipe(pipe.init_state(jax.random.key(0)), _mnist_batch(32))


def test_accum_requires_divisible_batch():
    cfg, model = _lenet()
    pipe = TrainPipeline(model, lars(0.05), cfg, accum_steps=3)
    with pytest.raises(ValueError, match="divisible"):
        pipe(pipe.init_state(jax.random.key(0)), _mnist_batch(32))


def test_accum_steps_validation():
    cfg, model = _lenet()
    with pytest.raises(ValueError, match="accum_steps"):
        TrainPipeline(model, lars(0.05), cfg, accum_steps=0)
    with pytest.raises(ValueError, match="precision"):
        TrainPipeline(model, lars(0.05), cfg, precision="f16")


# ------------------------------------------------------------- precision

def test_bf16_policy_keeps_f32_master_in_packed_slot():
    cfg, model = _lenet()
    opt = lars(0.05, trust_coefficient=0.01)
    pipe = TrainPipeline(model, opt, cfg, accum_steps=2, precision="bf16")
    state = pipe.init_state(jax.random.key(2))
    # params stored bf16; master is ONE f32 superbuffer (packed layout)
    assert all(l.dtype == jnp.bfloat16 for l in _leaves(state.params))
    layout = state.opt_state.layout
    assert layout is not None
    master = state.opt_state.slots[packing.MASTER_SLOT]
    assert master.shape == layout.buffer_shape and master.dtype == jnp.float32

    batch = _mnist_batch(32, seed=3)
    losses = []
    for _ in range(5):
        state, m = pipe(state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]       # memorizes a fixed batch in bf16
    # the bf16 params are the rounded view of the f32 master
    master_tree = packing.unpack(layout,
                                 state.opt_state.slots[packing.MASTER_SLOT],
                                 dtype=jnp.float32)
    for p, mw in zip(_leaves(state.params), _leaves(master_tree)):
        np.testing.assert_array_equal(
            np.asarray(p), np.asarray(jnp.asarray(mw).astype(jnp.bfloat16)))


def test_create_train_state_precision_matches_pipeline():
    """The standalone state factory applies the same precision policy
    the pipeline does (bf16 params + f32 master slot)."""
    cfg, model = _lenet()
    opt = lars(0.05)
    state = create_train_state(model, opt, jax.random.key(9),
                               precision="bf16")
    assert all(l.dtype == jnp.bfloat16 for l in _leaves(state.params))
    assert packing.MASTER_SLOT in state.opt_state.slots
    ref = TrainPipeline(model, opt, cfg, precision="bf16").init_state(
        jax.random.key(9))
    for a, b in zip(_leaves(state), _leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_bf16_master_tracks_f32_trajectory():
    """One step from identical (f32-representable) params: the f32
    master must match the pure-f32 trajectory to bf16-forward noise."""
    cfg, model = _lenet()
    opt = lars(0.05, trust_coefficient=0.01)
    f32 = TrainPipeline(model, opt, cfg, donate=False)
    b16 = TrainPipeline(model, opt, cfg, precision="bf16", donate=False)
    s32 = f32.init_state(jax.random.key(4))
    sb = b16.init_state(jax.random.key(4))
    batch = _mnist_batch(32, seed=5)
    s32, _ = f32(s32, batch)
    sb, _ = b16(sb, batch)
    master = packing.unpack(sb.opt_state.layout,
                            sb.opt_state.slots[packing.MASTER_SLOT],
                            dtype=jnp.float32)
    for a, b in zip(_leaves(s32.params), _leaves(master)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.1, atol=0.02)


# ------------------------------------------------------- 8-device mesh

_SUBPROC_MARKER = "REPRO_PIPELINE_SUBPROC"


def test_pipeline_equivalence_on_eight_devices():
    """Mesh-aware donated pipeline on a (4, 2) mesh == host pipeline."""
    if os.environ.get(_SUBPROC_MARKER):
        pytest.skip("already in subprocess")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               **{_SUBPROC_MARKER: "1"},
               PYTHONPATH=os.pathsep.join(sys.path))
    code = subprocess.run(
        [sys.executable, __file__, "--subproc"], env=env,
        capture_output=True, text=True, timeout=600)
    assert code.returncode == 0, code.stdout + code.stderr


def _subproc_main():
    assert len(jax.devices()) == 8
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("qwen3-14b").reduced()
    model = build_model(cfg)
    opt = lars(0.05, trust_coefficient=0.01)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 32)),
        jnp.int32)
    batch = {"tokens": toks}

    host = TrainPipeline(model, opt, cfg, accum_steps=2, donate=False)
    s_host = host.init_state(jax.random.key(0))
    dist = TrainPipeline(model, opt, cfg, accum_steps=2, mesh=mesh)
    s_dist = dist.init_state(jax.random.key(0))
    for _ in range(2):
        s_host, m_host = host(s_host, batch)
        s_dist, m_dist = dist(s_dist, batch)
    np.testing.assert_allclose(float(m_dist["loss"]), float(m_host["loss"]),
                               rtol=2e-4, atol=2e-5)
    for a, b in zip(_leaves(s_host.params), _leaves(s_dist.params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-3, atol=5e-4)

    # batches arrive via the prefetching ShardedLoader
    def gen():
        while True:
            yield {"tokens": np.asarray(toks)}

    loader = ShardedLoader(gen(), mesh, dist.batch_specs(8))
    s_dist, m = dist(s_dist, next(loader))
    loader.close()
    assert np.isfinite(float(m["loss"]))
    print("8-device pipeline == host pipeline: OK")


# ------------------------------------------------------------- prefetch

def test_prefetcher_preserves_order_and_stops():
    pf = Prefetcher(iter(range(20)), transform=lambda x: x * x,
                    buffer_size=2)
    assert list(pf) == [x * x for x in range(20)]


def test_prefetcher_stays_exhausted():
    """Iterator protocol: next() after exhaustion keeps raising
    StopIteration (regression: the sentinel was consumed once and a
    second next() blocked forever)."""
    pf = Prefetcher(iter(range(3)))
    assert list(pf) == [0, 1, 2]
    with pytest.raises(StopIteration):
        next(pf)
    with pytest.raises(StopIteration):
        next(pf)


def test_prefetcher_next_after_close_terminates():
    def forever():
        i = 0
        while True:
            yield i
            i += 1

    pf = Prefetcher(forever(), buffer_size=2)
    next(pf)
    pf.close()
    # drains whatever was buffered, then stops — never hangs
    with pytest.raises(StopIteration):
        for _ in range(8):
            next(pf)


def test_prefetcher_propagates_source_errors():
    def bad():
        yield 1
        raise RuntimeError("boom")

    pf = Prefetcher(bad())
    assert next(pf) == 1
    with pytest.raises(RuntimeError, match="boom"):
        next(pf)


def test_prefetcher_close_unblocks_infinite_source():
    def forever():
        i = 0
        while True:
            yield i
            i += 1

    pf = Prefetcher(forever(), buffer_size=2)
    assert [next(pf) for _ in range(5)] == [0, 1, 2, 3, 4]
    pf.close()   # must not hang


def test_sharded_loader_prefetch_places_on_device():
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh((1, 1), ("data", "model"))

    def batches():
        for i in range(3):
            yield {"x": np.full((4, 2), i, np.float32)}

    loader = ShardedLoader(batches(), mesh, {"x": P("data", None)})
    out = list(loader)
    loader.close()
    assert len(out) == 3
    assert isinstance(out[0]["x"], jax.Array)
    assert float(out[2]["x"][0, 0]) == 2.0


# ----------------------------------------------------------- checkpoint

def test_train_state_checkpoint_resumes_exact_trajectory():
    """Save the FULL state (params + packed slots incl. f32 master +
    step), restore into a fresh template, and both copies must produce
    identical continued trajectories (scheduled LR depends on step)."""
    cfg, model = _lenet()
    opt = lars(schedules.poly_decay_with_warmup(0.05, 40, 5),
               trust_coefficient=0.01)
    pipe = TrainPipeline(model, opt, cfg, accum_steps=2, precision="bf16",
                         donate=False)
    state = pipe.init_state(jax.random.key(6))
    batch = _mnist_batch(32, seed=7)
    for _ in range(3):
        state, _ = pipe(state, batch)

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "state.npz")
        save_train_state(path, state)
        template = pipe.init_state(jax.random.key(99))   # different init
        restored = restore_train_state(path, template)
    assert int(restored.opt_state.step) == 3
    assert restored.opt_state.layout is not None
    for _ in range(2):
        state, m_live = pipe(state, batch)
        restored, m_res = pipe(restored, batch)
        np.testing.assert_allclose(float(m_res["loss"]),
                                   float(m_live["loss"]), rtol=1e-6)
    for a, b in zip(_leaves(state), _leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_train_state_checkpoint_rejects_precision_mismatch():
    """Both directions must fail loudly: an f32 checkpoint misses the
    bf16 template's master slot, and a bf16 checkpoint's master has no
    slot in an f32 template (silently dropping it would change the
    resumed trajectory)."""
    cfg, model = _lenet()
    opt = lars(0.05)
    f32_pipe = TrainPipeline(model, opt, cfg)
    b16_pipe = TrainPipeline(model, opt, cfg, precision="bf16")
    with tempfile.TemporaryDirectory() as d:
        f32_path = os.path.join(d, "f32.npz")
        save_train_state(f32_path, f32_pipe.init_state(jax.random.key(8)))
        with pytest.raises(ValueError):
            restore_train_state(f32_path,
                                b16_pipe.init_state(jax.random.key(8)))
        b16_path = os.path.join(d, "b16.npz")
        save_train_state(b16_path, b16_pipe.init_state(jax.random.key(8)))
        with pytest.raises(ValueError, match="cannot hold"):
            restore_train_state(b16_path,
                                f32_pipe.init_state(jax.random.key(8)))


# ------------------------------------------------------------ schedules

def test_poly_decay_with_warmup_shape():
    sch = schedules.poly_decay_with_warmup(1.0, total_steps=110,
                                           warmup_steps=10)
    vals = [float(sch(jnp.asarray(i))) for i in (0, 5, 10, 60, 110)]
    assert vals[0] < vals[1] < vals[2]          # warming up
    np.testing.assert_allclose(vals[2], 1.0, rtol=1e-6)   # peak at lr0
    np.testing.assert_allclose(vals[3], 0.25, rtol=1e-5)  # (1-.5)^2
    np.testing.assert_allclose(vals[4], 0.0, atol=1e-7)   # decayed out


def test_large_batch_lr_scales_linearly():
    sch = schedules.large_batch_lr(0.1, 256, 4096, total_steps=100,
                                   warmup_steps=10, policy="linear")
    np.testing.assert_allclose(float(sch(jnp.asarray(10))), 1.6, rtol=1e-5)


# ------------------------------------------------------------- overrides

def test_shared_set_parser():
    from repro.launch.overrides import (apply_overrides, parse_overrides,
                                        parse_val)
    assert parse_val("true") is True and parse_val("False") is False
    assert parse_val("8") == 8 and parse_val("0.5") == 0.5
    assert parse_val("cosine") == "cosine"
    assert parse_overrides(["a=1", "b=x=y"]) == {"a": 1, "b": "x=y"}
    with pytest.raises(ValueError, match="FIELD=VALUE"):
        parse_overrides(["oops"])
    cfg = get_config("smollm-135m")
    assert apply_overrides(cfg, ["loss_chunk=8"]).loss_chunk == 8


if __name__ == "__main__" and "--subproc" in sys.argv:
    _subproc_main()

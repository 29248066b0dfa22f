"""Sharding-rule tests: param/cache PartitionSpecs, divisibility guards,
and a real 8-device pjit train step (data x model = 4 x 2) that checks
distributed-vs-single-device numerical equivalence.

This module re-execs itself under XLA_FLAGS to get 8 host devices
without polluting other test modules' device count (spawned subprocess).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import param_pspecs, cache_pspecs
from repro.launch.mesh import make_mesh
from repro.models import build_model


def _leaf(tree, *path):
    for k in path:
        tree = tree[k]
    return tree


def test_dense_param_specs():
    cfg = get_config("qwen3-14b")
    model = build_model(cfg)
    specs = param_pspecs(cfg, jax.eval_shape(model.init, jax.random.key(0)))
    assert _leaf(specs, "embed") == P("model", None)
    assert _leaf(specs, "unembed") == P(None, "model")
    # stacked layers get the leading None (layer axis scanned, not sharded)
    assert _leaf(specs, "layers", "attn", "wq") == P(None, "data", "model")
    assert _leaf(specs, "layers", "attn", "wo") == P(None, "model", "data")
    assert _leaf(specs, "layers", "mlp", "wi") == P(None, "data", "model")
    assert _leaf(specs, "layers", "ln1", "scale") == P(None, None)


def test_moe_param_specs_expert_parallel():
    cfg = get_config("deepseek-v2-236b")
    model = build_model(cfg)
    specs = param_pspecs(cfg, jax.eval_shape(model.init, jax.random.key(0)))
    assert _leaf(specs, "layers", "moe", "wi") == P(None, "model", "data",
                                                    None)
    assert _leaf(specs, "layers", "moe", "router") == P(None, None, None)
    # MLA projections
    assert _leaf(specs, "layers", "attn", "kv_down") == P(None, "data", None)
    assert _leaf(specs, "layers", "attn", "v_up") == P(None, "data", "model")


def test_ssm_param_specs_channel_shard():
    cfg = get_config("falcon-mamba-7b")
    model = build_model(cfg)
    specs = param_pspecs(cfg, jax.eval_shape(model.init, jax.random.key(0)))
    assert _leaf(specs, "layers", "ssm", "in_proj") == P(None, "data",
                                                         "model")
    assert _leaf(specs, "layers", "ssm", "out_proj") == P(None, "model",
                                                          "data")
    assert _leaf(specs, "layers", "ssm", "A_log") == P(None, "model", None)
    assert _leaf(specs, "layers", "ssm", "D") == P(None, "model")


def test_divisibility_guard_drops_axis():
    """whisper vocab 51865 is not divisible by 16 -> replicated."""
    cfg = get_config("whisper-base")
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    # fake a 16-way mesh via explicit shape map
    import repro.distributed.sharding as SH

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    specs = param_pspecs(cfg, jax.eval_shape(model.init, jax.random.key(0)), FakeMesh())
    assert _leaf(specs, "embed") == P(None, None)          # 51865 % 16 != 0
    assert _leaf(specs, "dec_layers", "self_attn", "wq") == \
        P(None, "data", "model")


def test_cache_specs_decode():
    cfg = get_config("qwen2-72b")
    model = build_model(cfg)

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    shapes = jax.eval_shape(lambda: model.init_cache(128, 32768))
    specs = cache_pspecs(cfg, FakeMesh(), shapes, batch=128)
    assert specs["k"] == P(None, ("data",), "model", None, None)
    assert specs["pos"] == P(("data",))


def test_serve_param_pspecs_strip_data_axis_when_tp_fits():
    """qwen2 fits TP-only -> data axis stripped; deepseek doesn't -> kept."""
    from repro.distributed.sharding import serve_param_pspecs

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    cfg = get_config("qwen2-72b")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    specs = serve_param_pspecs(cfg, shapes, FakeMesh())
    assert _leaf(specs, "layers", "attn", "wq") == P(None, None, "model")
    assert _leaf(specs, "layers", "mlp", "wo") == P(None, "model", None)

    big = get_config("deepseek-v2-236b")
    bmodel = build_model(big)
    bspecs = serve_param_pspecs(big, jax.eval_shape(bmodel.init, jax.random.key(0)), FakeMesh())
    # 472 GB / 16-way TP = 30 GB/device > budget -> training sharding kept
    assert _leaf(bspecs, "layers", "attn", "v_up") == P(None, "data",
                                                        "model")


_SUBPROC_MARKER = "REPRO_SHARDING_SUBPROC"


def test_eight_device_pjit_matches_single_device():
    """Full train step under a (4, 2) mesh == single-device step."""
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
    import jax
    import jax.numpy as jnp
    from repro.core import lars
    from repro.distributed import batch_pspecs, state_pspecs, tree_named
    from repro.train import TrainState, create_train_state, make_train_step

    assert len(jax.devices()) == 8
    cfg = get_config("qwen3-14b").reduced()
    model = build_model(cfg)
    opt = lars(0.05, trust_coefficient=0.01)
    state = create_train_state(model, opt, jax.random.key(0))
    step = make_train_step(model, opt, cfg)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 32)),
        jnp.int32)
    batch = {"tokens": toks}

    # single device reference
    ref_state, ref_metrics = jax.jit(step)(state, batch)

    mesh = make_mesh((4, 2), ("data", "model"))
    sspecs = state_pspecs(cfg, jax.eval_shape(lambda: state), mesh)
    bspecs = batch_pspecs(cfg, mesh, batch=8)
    with jax.set_mesh(mesh):
        dist = jax.jit(step,
                       in_shardings=(tree_named(mesh, sspecs),
                                     tree_named(mesh, bspecs)),
                       out_shardings=(tree_named(mesh, sspecs), None))
        d_state, d_metrics = dist(state, batch)
    np.testing.assert_allclose(float(d_metrics["loss"]),
                               float(ref_metrics["loss"]),
                               rtol=2e-4, atol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ref_state.params),
                    jax.tree_util.tree_leaves(d_state.params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-3, atol=5e-4)
    print("8-device pjit == single device: OK")


if __name__ == "__main__" and "--subproc" in sys.argv:
    _subproc_main()

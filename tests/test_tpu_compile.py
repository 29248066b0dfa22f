"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret-mode tests (``test_kernels.py``) pin what the kernels compute;
they cannot see what Mosaic refuses — block shapes off the (8, 128)
tiling, broadcasts it cannot lower, VMEM overruns. Here each kernel is
compiled for one chip of a described (not attached) ``v5e:2x2``
topology at smollm-135m's widths: its whole-model optimizer superbuffer,
its training-attention shapes and its decode-attention shapes. Nothing
runs; a refusal fails the test.
Each kernel must keep its own name in the compiled program, the name a
device trace shows it by.

The topology is described inside a module fixture (never at import):
only one process may load the TPU library, and every test worker
imports this file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import packing
from repro.kernels import causal_attention as CA
from repro.kernels import flash_decode as fd
from repro.kernels import lars_kernels as LK
from repro.models import build_model


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache
    # but never read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smollm_buffer():
    """(rows, lane) and group count of smollm-135m's packed superbuffer."""
    model = build_model(get_config("smollm-135m"))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    layout = packing.build_layout(shapes, model.stacked_marker(shapes))
    return layout.buffer_shape, layout.num_blocks


def _compile_for_chip(fn, *args, kernel):
    text = jax.jit(fn).lower(*args).compile().as_text()
    for name in (kernel,) if isinstance(kernel, str) else kernel:
        assert re.search(rf"^\s*(ROOT )?%{name}\.\d+ = .*custom_call_target="
                         r'"tpu_custom_call"', text, re.M), \
            f"kernel {name} missing from the program"


def test_norms_flat_compiles_for_v5e(one_chip, smollm_buffer):
    shape, _ = smollm_buffer
    buf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    _compile_for_chip(
        lambda w, g: LK.norms_flat(w, g, interpret=False), buf, buf,
        kernel="lars_norms")


def test_apply_flat_compiles_for_v5e(one_chip, smollm_buffer):
    shape, groups = smollm_buffer
    buf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    col = jax.ShapeDtypeStruct((groups, 1), jnp.float32, sharding=one_chip)
    _compile_for_chip(
        lambda lr, w, g, m: LK.apply_flat(
            w, g, m, lr, momentum=0.9, weight_decay=1e-4, interpret=False),
        col, buf, buf, buf, kernel="lars_apply")


def test_apply_flat_q8_compiles_for_v5e(one_chip, smollm_buffer):
    shape, groups = smollm_buffer
    buf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    codes = jax.ShapeDtypeStruct(shape, jnp.int8, sharding=one_chip)
    col = jax.ShapeDtypeStruct((groups, 1), jnp.float32, sharding=one_chip)
    _compile_for_chip(
        lambda lr, sc, w, g, q: LK.apply_flat_q8(
            w, g, q, sc, lr, momentum=0.9, weight_decay=1e-4,
            interpret=False),
        col, col, buf, buf, codes, kernel="lars_apply_q8")


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen3-14b"])
def test_flash_decode_compiles_for_v5e(one_chip, arch):
    H, Hkv, D = get_config(arch).attn_dims
    B, S = 8, 2048

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile_for_chip(
        lambda q, k, v, n: fd.flash_decode_grouped(
            q, k, v, n, scale=D ** -0.5, interpret=False),
        spec((B, Hkv, H // Hkv, D), jnp.bfloat16),
        spec((B, S, Hkv, D), jnp.bfloat16),
        spec((B, S, Hkv, D), jnp.bfloat16),
        spec((B,), jnp.int32), kernel="flash_decode")


@pytest.mark.parametrize("pass_,kernels", [
    pytest.param("forward", ["splash_mqa_fwd_no_residuals"], id="forward"),
    pytest.param("backward", ["splash_mqa_fwd_residuals",
                              "splash_mqa_dkv_no_residuals"], id="backward")])
def test_causal_attention_compiles_for_v5e(one_chip, pass_, kernels):
    """smollm-135m's training attention: 8 x 2048 tokens, 9 query heads
    on 3 KV heads of 64, bf16."""
    H, Hkv, D = get_config("smollm-135m").attn_dims
    B, S = 8, 2048

    def attn(q, k, v):
        return CA.causal_attention(q, k, v, scale=D ** -0.5,
                                   interpret=False)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    fn = attn if pass_ == "forward" else jax.grad(loss, argnums=(0, 1, 2))
    _compile_for_chip(
        fn, jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16,
                                 sharding=one_chip),
        *[jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16,
                               sharding=one_chip)] * 2, kernel=kernels)


def _computations(text: str) -> dict:
    """Name -> instruction lines of each computation of an HLO text."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line == "}":
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _kernels_under(comps: dict, root: str) -> set:
    """The Pallas kernels a computation runs, through the computations
    it calls."""
    seen, todo, kernels = set(), [root], set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            kernels.update(re.findall(
                r"^\s*(?:ROOT )?%(\w+)\.\d+ = .*tpu_custom_call", line))
            for callees in re.findall(
                    r"(?:calls|body|condition|to_apply|branch_computations)"
                    r"=\{?([%\w.\-, ]+)", line):
                todo += [n.strip().lstrip("%") for n in callees.split(",")]
    return kernels


@pytest.mark.parametrize("arch,changes,B,S", [
    # smollm-135m's widths at 2 layers, its cell's microbatch of 8 x 2048
    pytest.param("smollm-135m", dict(num_layers=2), 8, 2048, id="smollm"),
    # DeepSeek-V2-Lite's MLA (q, k of 192, v of 128, YaRN scale folded
    # into q) in 2 dense layers, its cell's microbatch of 1 x 4096
    pytest.param("deepseek-v2-lite", dict(
        family="dense", num_layers=2, first_k_dense=0, num_experts=0,
        vocab_size=12800), 1, 4096, id="mla")])
def test_layer_remat_runs_the_attention_forward_once(one_chip, monkeypatch,
                                                     arch, changes, B, S):
    """The train step's layer scan keeps the splash forward's output and
    logsumexp, so its backward body runs the fused backward kernel and
    no second forward kernel."""
    from repro.train.step import _forward_and_loss

    monkeypatch.setattr(CA, "on_tpu", lambda: True)
    monkeypatch.setattr(CA, "default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config(arch), **changes)
    assert cfg.remat and cfg.dtype == "bfloat16"
    model = build_model(cfg)
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)

    def grads(params, tokens):
        return jax.grad(lambda p: _forward_and_loss(
            model, cfg, p, {"tokens": tokens})[0])(params)

    comps = _computations(
        jax.jit(grads).lower(params, tokens).compile().as_text())
    bodies = [_kernels_under(comps, body) for body in
              re.findall(r" while\(.*body=%?([\w.\-]+)",
                         "\n".join(sum(comps.values(), [])))]
    fwd, bwd = "splash_mqa_fwd_residuals", "splash_mqa_dkv_no_residuals"
    assert sorted(sorted(b) for b in bodies if fwd in b or bwd in b) == \
        [[bwd], [fwd]]


def test_packed_lars_update_compiles_on_four_chip_mesh(topo, monkeypatch):
    """Replicated data parallelism keeps the megakernels: under a (4, 1)
    mesh each chip runs them on its own copy (Mosaic kernels cannot be
    partitioned by GSPMD)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import lars
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh

    monkeypatch.setattr(ops, "_interpret", lambda: False)  # Mosaic, not CPU
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    opt = lars(0.1, use_pallas=True)
    params = {"w": jnp.zeros((256, 512)), "stk": jnp.zeros((3, 64, 128)),
              "b": jnp.zeros((512,))}
    stacked = {"w": False, "stk": True, "b": False}
    state = jax.eval_shape(lambda p: opt.init(p, stacked=stacked), params)
    rep = NamedSharding(mesh, P())

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep)

    args = jax.tree_util.tree_map(spec, (params, state, params))
    with jax.set_mesh(mesh):
        lowered = jax.jit(lambda g, s, p: opt.update(g, s, p)).lower(*args)
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 2


def test_mla_causal_attention_compiles_for_v5e(one_chip):
    """DeepSeek-V2-Lite's training attention, as its cell runs it: a
    microbatch of 1 x 4096 tokens, 16 heads of 192 (q, k) and 128 (v),
    scale folded into q."""
    B, S, H = 1, 4096, 16

    def loss(q, k, v):
        return jnp.sum(CA.causal_attention(q, k, v, scale=1.0,
                                           interpret=False
                                           ).astype(jnp.float32))

    def spec(D):
        return jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16,
                                    sharding=one_chip)

    _compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)), spec(192),
                      spec(192), spec(128),
                      kernel=["splash_mqa_fwd_residuals",
                              "splash_mqa_dkv_no_residuals"])


def test_moe_grouped_matmuls_compile_for_v5e(one_chip):
    """DeepSeek-V2-Lite's held experts, forward and backward, at its
    cell's microbatch: 1 x 4096 tokens x 6 slots over 8 experts of 1408;
    the grouped matmuls keep XLA's ragged-dot kernels."""
    from repro.configs import get_config
    from repro.models import moe
    cfg = get_config("deepseek-v2-lite")
    M, d, ff, E = 4096 * 6, 2048, 1408, 8

    def loss(p, xs, sizes):
        return jnp.sum(moe.grouped_mlp(cfg, p, xs, sizes).astype(
            jnp.float32))

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"wi": spec((E, d, ff)), "wg": spec((E, d, ff)),
         "wo": spec((E, ff, d))}
    _compile_for_chip(jax.grad(loss, argnums=(0, 1)), p, spec((M, d)),
                      spec((E,), jnp.int32),
                      kernel=["ragged-dot-metadata", "ragged-dot-none"])

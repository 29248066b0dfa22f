"""The train step's phases name its compiled ops, and its host step marker
reaches the profiler.

Every name in :mod:`repro.phases` must be a component of the ``op_name``
metadata of some instruction in the compiled step, on the jnp engine that
``use_pallas="auto"`` takes off the TPU and around the Pallas kernels when
they are forced (interpret mode). At the top level of a differentiated
function JAX folds a scope into the transform's name (``jvp(embed)``,
``transpose(jvp(head_loss))``), so components are read with the transform
wrappers taken off.
"""

import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro import phases
from repro.configs import get_config
from repro.core import lars
from repro.models import build_model
from repro.train import TrainPipeline

PHASES = [v for k, v in vars(phases).items() if k.isupper()]
MOE_PHASES = [phases.ROUTER, phases.MOE_DISPATCH, phases.MOE_EXPERTS]
DENSE_PHASES = [p for p in PHASES if p not in MOE_PHASES]   # smollm's step
WRAPPER = re.compile(r"^(?:[\w-]+\()+|\)+$")


def op_names(text: str) -> set:
    """Every op_name of a compiled HLO text (fused ops list several)."""
    return {n for joined in re.findall(r'op_name="([^"]*)"', text)
            for n in joined.split(";")}


def components(op_name: str) -> list:
    return [WRAPPER.sub("", c) for c in op_name.split("/")]


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """The persistent cache's key leaves op_name metadata out, so a cached
    executable of the same program without scopes would be handed back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_step(accum_steps: int, use_pallas, slot_dtype: str = "f32",
                  arch: str = "smollm-135m"):
    cfg = get_config(arch).reduced()
    assert cfg.remat
    model = build_model(cfg)
    pipe = TrainPipeline(model, lars(0.1, use_pallas=use_pallas,
                                     slot_dtype=slot_dtype),
                         cfg, accum_steps=accum_steps, precision="bf16")
    state = jax.eval_shape(pipe.init_state, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
    return op_names(pipe.lower(state, batch).compile().as_text())


def with_phase(names: set, phase: str) -> list:
    return [n for n in names if phase in components(n)]


def test_phase_names_are_distinct_and_plain():
    assert len(set(PHASES)) == len(PHASES) == 13
    assert all(re.fullmatch(r"[a-z_]+", p) for p in PHASES)


@pytest.mark.parametrize("accum_steps", [1, 2])
def test_every_phase_names_ops_of_the_compiled_step(accum_steps):
    """accum_steps 2 packs each microbatch's gradients in the scan body
    (the fused accumulation); 1 packs them in the optimizer's engine."""
    names = compiled_step(accum_steps, "auto")
    missing = [p for p in DENSE_PHASES if not with_phase(names, p)]
    assert not missing, missing
    for p in (phases.ATTENTION_CORE, phases.MLP):
        mine = with_phase(names, p)
        assert any("transpose(" in n for n in mine), p         # backward
        assert any("rematted_computation" in n for n in mine), p  # remat
        assert any("transpose(" not in n and "rematted_computation" not in n
                   for n in mine), p                            # forward
    # attention_core nests inside attention
    assert all(phases.ATTENTION in components(n)
               for n in with_phase(names, phases.ATTENTION_CORE))
    grad_pack = with_phase(names, phases.GRAD_PACK)
    assert any(("while/body" in n) == (accum_steps > 1) for n in grad_pack)


@pytest.mark.parametrize("slot_dtype,apply_kernel",
                         [("f32", "lars_apply"), ("int8", "lars_apply_q8")])
def test_lars_phases_wrap_the_pallas_kernels(slot_dtype, apply_kernel):
    """Forced onto the Pallas kernels (interpret mode here), the norms
    and apply phases hold the kernels' own ops, named by the kernels."""
    names = compiled_step(2, True, slot_dtype)
    missing = [p for p in DENSE_PHASES if not with_phase(names, p)]
    assert not missing, missing
    for phase, kernel in ((phases.LARS_NORMS, "lars_norms"),
                          (phases.LARS_APPLY, apply_kernel)):
        assert any(f"/{phase}/{kernel}/" in n for n in names), kernel
    assert any("/lars_apply_q8/" in n for n in names) == (
        slot_dtype == "int8")


def test_moe_phases_name_ops_of_the_compiled_step():
    """An MoE model's step (DeepSeek-V2-Lite, reduced: a dense layer and
    an MoE layer) has every phase: the router, dispatch and grouped
    matmuls nest in the MLP phase, forward, remat and backward."""
    names = compiled_step(2, "auto", arch="deepseek-v2-lite")
    missing = [p for p in PHASES if not with_phase(names, p)]
    assert not missing, missing
    for p in MOE_PHASES:
        mine = with_phase(names, p)
        assert all(phases.MLP in components(n) for n in mine), p
        assert any("transpose(" in n for n in mine), p
        assert any("rematted_computation" in n for n in mine), p


def test_pipeline_marks_each_step_for_the_profiler(tmp_path):
    from jax.profiler import ProfileData
    cfg = get_config("smollm-135m").reduced()
    pipe = TrainPipeline(build_model(cfg), lars(0.1), cfg)
    state = pipe.init_state(jax.random.key(0))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    state, _ = pipe(state, batch)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            state, _ = pipe(state, batch)
        jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    steps = [dict(e.stats)["step_num"]
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "train_step"]
    assert sorted(steps) == [1, 2]

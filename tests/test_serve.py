"""Continuous-batching serve stack tests: per-family slot decode vs
teacher-forced forward, masked (heterogeneous-length) prefill exactness,
mid-flight admission, the one-jitted-donated-decode-call-per-token
contract, sampling semantics, the flash-decode interpret fix, and an
8-device mesh-sharded engine equivalence (subprocess re-exec, same
pattern as test_sharding).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import flash_decode as fd
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serve import (DecodeEngine, QueueFull, SamplerConfig, ServeEngine,
                         parse_sampler, sample)
from repro.serve import sampling

SERVE_ARCHS = ["qwen3-14b", "deepseek-v2-236b", "falcon-mamba-7b",
               "zamba2-7b"]   # dense GQA / MLA / SSM / hybrid

_MODELS = {}


def _model(arch):
    if arch not in _MODELS:
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(1))
        _MODELS[arch] = (cfg, model, params)
    return _MODELS[arch]


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (l,)) for l in lens]


def _teacher_forced_check(cfg, model, params, prompt, generated):
    """Every generated token must equal forward()'s argmax at the
    position preceding it (greedy replay)."""
    seq = jnp.asarray(np.concatenate([prompt, generated[:-1]]),
                      jnp.int32)[None]
    logits, _ = model.forward(params, seq)
    ref = np.asarray(jnp.argmax(logits[0, len(prompt) - 1:], -1))
    np.testing.assert_array_equal(ref, generated)


# --------------------------------------------------- per-family consistency

@pytest.mark.parametrize("arch", SERVE_ARCHS)
def test_slot_decode_matches_teacher_forced(arch):
    """Slot-wise prefill+decode greedy == teacher-forced forward argmax,
    with more requests than slots (slot retirement + reuse)."""
    cfg, model, params = _model(arch)
    engine = ServeEngine(model, params, cfg, slots=2, capacity=64)
    prompts = _prompts(cfg, [5, 9, 7, 5], seed=3)
    outs = engine.generate(prompts, max_new_tokens=6)
    for p, g in zip(prompts, outs):
        assert g.shape == (6,)
        _teacher_forced_check(cfg, model, params, p, g)


@pytest.mark.parametrize("arch", SERVE_ARCHS)
def test_masked_prefill_matches_exact(arch):
    """prefill(lengths=) on a right-padded batch == per-row exact-length
    prefill: logits AND the decode state a step later."""
    cfg, model, params = _model(arch)
    rng = np.random.default_rng(4)
    lens, cap, s_pad = [5, 12, 9], 48, 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, s_pad)),
                       jnp.int32)
    lg_pad, cache_pad = model.prefill(params, toks, cache_len=cap,
                                      lengths=jnp.asarray(lens))
    for b, l in enumerate(lens):
        lg_ref, cache_ref = model.prefill(params, toks[b:b + 1, :l],
                                          cache_len=cap)
        np.testing.assert_allclose(np.asarray(lg_pad[b]),
                                   np.asarray(lg_ref[0]),
                                   rtol=1e-4, atol=1e-4)
        nxt = jnp.argmax(lg_ref, -1).astype(jnp.int32)[:, None]
        d_ref, _ = model.decode_step(params, cache_ref, nxt)
        row = {k: (v[b:b + 1] if k == "pos" else v[:, b:b + 1])
               for k, v in cache_pad.items()}
        d_pad, _ = model.decode_step(params, row, nxt)
        np.testing.assert_allclose(np.asarray(d_pad), np.asarray(d_ref),
                                   rtol=1e-4, atol=1e-4)


def test_prefill_bucket_padding_end_to_end():
    """Bucketed (padded) admission produces the same greedy tokens as
    exact-length admission (the masked-prefill path, engine-level)."""
    for arch in ("qwen3-14b", "zamba2-7b"):
        cfg, model, params = _model(arch)
        prompts = _prompts(cfg, [5, 11, 3], seed=5)
        exact = ServeEngine(model, params, cfg, slots=3, capacity=64,
                            prefill_bucket=1).generate(prompts, 5)
        padded = ServeEngine(model, params, cfg, slots=3, capacity=64,
                             prefill_bucket=8).generate(prompts, 5)
        for a, b in zip(exact, padded):
            np.testing.assert_array_equal(a, b)


# ----------------------------------------------------- scheduler semantics

def test_mid_flight_admission_keeps_decoding():
    """New requests join while resident slots keep decoding; outputs are
    identical to a drained run (admission timing cannot change tokens)."""
    cfg, model, params = _model("qwen3-14b")
    prompts = _prompts(cfg, [6, 9, 4, 7], seed=6)

    ref = ServeEngine(model, params, cfg, slots=2, capacity=64
                      ).generate(prompts, 8)

    engine = ServeEngine(model, params, cfg, slots=2, capacity=64)
    rids = [engine.submit(prompts[0], 8), engine.submit(prompts[1], 8)]
    finished = []
    for _ in range(3):                      # decode with slots occupied
        finished.extend(engine.step())
    steps_before = engine.stats["decode_steps"]
    rids += [engine.submit(prompts[2], 8),  # submitted mid-flight
             engine.submit(prompts[3], 8)]
    while engine.scheduler.has_work():
        finished.extend(engine.step())
    assert steps_before >= 3                # decoding happened pre-arrival
    assert engine.stats["admit_calls"] >= 2  # admission resumed after
    by_rid = {f.request.rid: f.tokens for f in finished}
    for rid, r in zip(rids, ref):
        np.testing.assert_array_equal(by_rid[rid], r)


def test_queue_bound_and_capacity_guard():
    cfg, model, params = _model("qwen3-14b")
    engine = ServeEngine(model, params, cfg, slots=1, capacity=32,
                         max_queue=2)
    with pytest.raises(ValueError, match="capacity"):
        engine.submit(np.zeros(30, np.int32), 8)   # 30 + 8 > 32
    engine.submit(np.zeros(4, np.int32), 4)
    engine.submit(np.zeros(4, np.int32), 4)
    with pytest.raises(QueueFull):
        engine.submit(np.zeros(4, np.int32), 4)
    out = engine.run()
    assert len(out) == 2


def test_eos_retires_slot_early():
    cfg, model, params = _model("qwen3-14b")
    engine = ServeEngine(model, params, cfg, slots=1, capacity=64)
    p = _prompts(cfg, [6])[0]
    full = engine.generate([p], 8)[0]
    eos = int(full[2])                      # force EOS at the 3rd token
    engine2 = ServeEngine(model, params, cfg, slots=1, capacity=64)
    rid = engine2.submit(p, 8, eos_id=eos)
    fin = engine2.run()
    assert fin[0].request.rid == rid
    assert fin[0].tokens.size == 3
    np.testing.assert_array_equal(fin[0].tokens, full[:3])
    assert engine2.cache.free_slots == 1    # slot released


# ------------------------------------------- one-call-per-token + donation

def test_one_jitted_decode_call_per_token_with_donated_cache():
    """The decode hot path traces ONCE for a whole serve run (admissions
    included), the step is lowered with input-output aliasing (donated
    cache), and the donated buffers are actually consumed."""
    cfg, model, params = _model("qwen3-14b")
    engine = ServeEngine(model, params, cfg, slots=2, capacity=64)
    prompts = _prompts(cfg, [5, 9, 7], seed=7)
    engine.generate(prompts, max_new_tokens=6)
    assert engine.traces["decode"] == 1
    assert engine.stats["decode_steps"] >= 6

    # donation consumes the pre-step cache buffers in place
    leaf = jax.tree_util.tree_leaves(engine.cache.data)[0]
    engine.submit(prompts[0], 2)
    engine.run()
    assert engine.traces["decode"] == 1     # still one trace
    assert leaf.is_deleted()                # old buffer donated away


def test_decode_step_lowering_declares_donation():
    """Pin the aliasing at the IR level (works on every backend)."""
    cfg, model, params = _model("qwen3-14b")
    engine = ServeEngine(model, params, cfg, slots=2, capacity=32)
    toks = jnp.zeros((2, 1), jnp.int32)
    keys = jnp.zeros((2, 2), jnp.uint32)
    txt = engine._decode.lower(params, engine.cache.data, toks,
                               keys).as_text()
    assert "tf.aliasing_output" in txt


# ----------------------------------------------------------------- sampling

def test_temperature_to_zero_converges_to_greedy():
    cfg, model, params = _model("falcon-mamba-7b")
    prompts = _prompts(cfg, [5, 8], seed=8)
    greedy = ServeEngine(model, params, cfg, slots=2, capacity=64
                         ).generate(prompts, 6)
    cold = ServeEngine(model, params, cfg, slots=2, capacity=64,
                       sampler=SamplerConfig("temperature",
                                             temperature=1e-6)
                       ).generate(prompts, 6)
    for a, b in zip(greedy, cold):
        np.testing.assert_array_equal(a, b)


def test_sampling_deterministic_and_slot_invariant():
    """fold_in(request key, position) makes stochastic output a pure
    function of (seed, rid, position) — slot count / admission order
    cannot change it."""
    cfg, model, params = _model("qwen3-14b")
    prompts = _prompts(cfg, [5, 9, 7], seed=9)
    scfg = SamplerConfig("top_k", top_k=8, temperature=0.8)
    a = ServeEngine(model, params, cfg, slots=1, capacity=64, seed=11,
                    sampler=scfg).generate(prompts, 5)
    b = ServeEngine(model, params, cfg, slots=3, capacity=64, seed=11,
                    sampler=scfg).generate(prompts, 5)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y) for x, y in zip(
        a, ServeEngine(model, params, cfg, slots=3, capacity=64, seed=12,
                       sampler=scfg).generate(prompts, 5)))


def test_top_k_top_p_restrict_support():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -8.0]] * 256, jnp.float32)
    keys = sampling.make_keys(0, np.arange(256))
    tk = np.asarray(sample(SamplerConfig("top_k", top_k=2), logits, keys))
    assert tk.max() <= 1
    tp = np.asarray(sample(SamplerConfig("top_p", top_p=0.9), logits, keys))
    assert tp.max() <= 1                    # tail outside the nucleus
    assert len(np.unique(tk)) == 2          # both nucleus tokens drawn
    g = np.asarray(sample(SamplerConfig("greedy"), logits, keys))
    assert (g == 0).all()


def test_sliding_window_prompt_longer_than_ring():
    """A windowed arch admits prompts LONGER than its KV ring (the ring
    keeps each row's newest window) — greedy still matches teacher-
    forced windowed forward."""
    import dataclasses
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              sliding_window=8)
    model = build_model(cfg)
    params = model.init(jax.random.key(2))
    engine = ServeEngine(model, params, cfg, slots=2, capacity=64)
    prompts = _prompts(cfg, [20, 13], seed=14)   # 20 > ring of 8
    outs = engine.generate(prompts, max_new_tokens=5)
    for p, g in zip(prompts, outs):
        _teacher_forced_check(cfg, model, params, p, g)


def test_parse_sampler():
    assert parse_sampler("greedy").kind == "greedy"
    s = parse_sampler("top_k:40:0.8")
    assert (s.kind, s.top_k, s.temperature) == ("top_k", 40, 0.8)
    assert parse_sampler("top_p:0.9").top_p == 0.9
    assert parse_sampler("temperature:0.7").temperature == 0.7
    with pytest.raises(ValueError):
        parse_sampler("nucleus:0.9")
    with pytest.raises(ValueError):        # truncated spec, no IndexError
        parse_sampler("temperature")
    with pytest.raises(ValueError):
        parse_sampler("top_k")
    with pytest.raises(ValueError):
        SamplerConfig("top_k", top_k=0)


# -------------------------------------------------------------- flash path

def test_flash_decode_interpret_defaults_from_backend():
    """The kernel picks interpret from the backend (TPU compiles the
    Mosaic kernel; CPU/GPU interpret) and the override still wins."""
    assert fd.default_interpret() == (jax.default_backend() != "tpu")
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
    lengths = jnp.asarray([13, 64], jnp.int32)
    auto = ops.flash_decode(q, k, v, lengths, block_size=32)
    forced = ops.flash_decode(q, k, v, lengths, block_size=32,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(forced),
                               rtol=1e-6, atol=1e-6)


def test_engine_flash_path_matches_jnp_core():
    """use_flash routes decode attention through the Pallas megakernel
    with real per-slot lengths — same greedy tokens (dense + hybrid)."""
    for arch in ("qwen3-14b", "zamba2-7b"):
        cfg, model, params = _model(arch)
        prompts = _prompts(cfg, [5, 9], seed=10)
        base = ServeEngine(model, params, cfg, slots=2, capacity=64,
                           use_flash=False).generate(prompts, 5)
        flash = ServeEngine(model, params, cfg, slots=2, capacity=64,
                            use_flash=True).generate(prompts, 5)
        for a, b in zip(base, flash):
            np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------- mesh (8 dev)

_SUBPROC_MARKER = "REPRO_SERVE_SUBPROC"


def test_eight_device_mesh_serve_matches_single_device():
    """Mesh-sharded engine (cache_pspecs + serve param specs, 4x2 mesh)
    produces the exact single-device greedy tokens."""
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
    for arch in ("qwen3-14b", "falcon-mamba-7b"):
        cfg, model, params = _model(arch)
        prompts = _prompts(cfg, [5, 9, 7, 6, 11, 5], seed=13)
        ref = ServeEngine(model, params, cfg, slots=4, capacity=64
                          ).generate(prompts, 5)
        mesh = make_mesh((4, 2), ("data", "model"))
        eng = ServeEngine(model, params, cfg, slots=4, capacity=64,
                          mesh=mesh)
        out = eng.generate(prompts, 5)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)
        assert eng.traces["decode"] == 1
        print(f"{arch}: 8-device mesh serve == single device: OK")


if __name__ == "__main__" and "--subproc" in sys.argv:
    _subproc_main()

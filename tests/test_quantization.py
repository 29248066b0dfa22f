"""Property tests for int8 optimizer-state quantization (hypothesis
when installed, deterministic single examples otherwise — see
tests/_hypothesis_compat.py).

Pinned invariants:

* per-block symmetric int8 round-trip error is bounded by scale/2
  (scale = block absmax / 127), on arbitrary shape mixes including the
  f32 ``MASTER_SLOT`` buffer; all-zero blocks round-trip EXACTLY (the
  unit-scale guard);
* quantize(dequantize(quantize(x))) reproduces the codes bit-exactly
  (scales to ~ulp — the fixed point of the quantizer);
* scales are absmax/127 where a block is nonzero, 1.0 where it is all
  zero (so zero rows never divide by zero), and the tree-engine leaf
  scales depend only on the leaf's leading axis — never on values'
  positions;
* the FIRST update from freshly-initialized slots is bit-identical
  between ``slot_dtype="f32"`` and ``"int8"`` on both engines for all
  four optimizers (quantized zeros dequantize to exact zeros);
* LARS first-update scale equivariance survives int8 slots on both
  engines (the trust ratio never sees codes);
* Adam bias correction under a constant gradient holds at int8 within
  the quantizer's measured drift (mu <= 9.6e-3, nu <= 2.8e-2 relative
  after 3 requantization steps — bars placed at ~3x);
* backend-aware dispatch: ``use_pallas="auto"`` resolves to the jnp
  engine on CPU (0 launches), ``True`` forces the megakernels (2
  launches — with int8 slots the second is the fused
  dequant-update-requant kernel) and matches the jnp int8 path;
* int8 codes + scales survive the npz TrainState round-trip
  byte-identically (the substrate of mid-cell kill/resume).
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from _hypothesis_compat import given, settings, st  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.checkpoint import restore_train_state, save_train_state  # noqa: E402
from repro.core import adamw, lamb, lars, packing, sgd  # noqa: E402
from repro.core.optim_base import SCALE_SUFFIX, normalize_stacked  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels.introspect import count_pallas_launches  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.train import TrainPipeline  # noqa: E402

tree_map = jax.tree_util.tree_map
tree_leaves = jax.tree_util.tree_leaves

OPTS = {"sgd": lambda dt: sgd(0.05, momentum=0.9, slot_dtype=dt),
        "lars": lambda dt: lars(0.05, slot_dtype=dt),
        "lamb": lambda dt: lamb(0.01, slot_dtype=dt),
        "adamw": lambda dt: adamw(0.01, slot_dtype=dt)}


def _rand(key, shape, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(key), shape,
                                     jnp.float32)


def _zoo(seed: int, zero_leaf: bool = False):
    """Shape zoo: scalar, vector, matrix, layer stack, a >1-row leaf,
    optionally an all-zero leaf (exercises the unit-scale guard)."""
    tree = {
        "scalar": jnp.asarray(float(seed % 97), jnp.float32),
        "vec": _rand(seed, (1 + seed % 23,)),
        "mat": _rand(seed + 1, (5 + seed % 13, 3)),
        "stack": _rand(seed + 2, (2 + seed % 3, 4, 3 + seed % 7)),
        "odd": _rand(seed + 3, (513,)),
    }
    if zero_leaf:
        tree["dead"] = jnp.zeros((6, 9), jnp.float32)
    marker = {k: k == "stack" for k in tree}
    return tree, marker


def _layout(tree, marker):
    return packing.build_layout(tree, normalize_stacked(tree, marker))


# ------------------------------------------------------- packed quantizer

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**16),
       zero_leaf=st.sampled_from([True, False]))
def test_q8_roundtrip_bounded_and_scales_correct(seed, zero_leaf):
    tree, marker = _zoo(seed, zero_leaf)
    layout = _layout(tree, marker)
    buf = packing.pack(layout, tree)
    q, scale = packing.quantize_q8(layout, buf)
    assert q.dtype == jnp.int8 and q.shape == layout.buffer_shape
    assert scale.shape == (layout.num_blocks, 1)

    grouped = np.asarray(buf, np.float64).reshape(layout.num_blocks, -1)
    amax = np.max(np.abs(grouped), axis=1, keepdims=True)
    expect = np.where(amax > 0.0, amax / 127.0, 1.0)
    np.testing.assert_allclose(np.asarray(scale, np.float64), expect,
                               rtol=1e-6)

    dq = np.asarray(packing.dequantize_q8(layout, q, scale),
                    np.float64).reshape(layout.num_blocks, -1)
    err = np.abs(dq - grouped)
    # round-to-nearest on the code grid: at most half a step per element
    assert np.all(err <= np.asarray(scale, np.float64) * 0.5 * (1 + 1e-5))
    # all-zero blocks (incl. padding rows) round-trip exactly
    zero_rows = amax[:, 0] == 0.0
    assert np.all(dq[zero_rows] == 0.0)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**16))
def test_q8_idempotent_on_quantized_rows(seed):
    tree, marker = _zoo(seed)
    layout = _layout(tree, marker)
    q, scale = packing.quantize_q8(layout, packing.pack(layout, tree))
    dq = packing.dequantize_q8(layout, q, scale)
    q2, scale2 = packing.quantize_q8(layout, dq)
    assert np.asarray(q2).tobytes() == np.asarray(q).tobytes()
    np.testing.assert_allclose(np.asarray(scale2), np.asarray(scale),
                               rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**16))
def test_q8_master_slot_buffer_roundtrip_bounded(seed):
    """The f32 master superbuffer (MASTER_SLOT) through the same
    quantizer: bounded round-trip, exact zero padding."""
    tree, marker = _zoo(seed)
    layout = _layout(tree, marker)
    master = packing.init_master(layout, tree)
    q, scale = packing.quantize_q8(layout, master)
    dq = np.asarray(packing.dequantize_q8(layout, q, scale), np.float64)
    grouped = np.asarray(master, np.float64).reshape(layout.num_blocks, -1)
    err = np.abs(dq.reshape(layout.num_blocks, -1) - grouped)
    assert np.all(err <= np.asarray(scale, np.float64) * 0.5 * (1 + 1e-5))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**16))
def test_q8_leaf_quantizer_scale_shape_and_bound(seed):
    """Tree-engine leaf quantizer: one scale per leading index (scalar
    for 0-d leaves) — a shape that depends only on the leaf's own shape,
    never on a stacked marker — with the same half-step bound."""
    tree, _ = _zoo(seed, zero_leaf=True)
    for name, x in tree.items():
        q, scale = packing.quantize_leaf_q8(x)
        assert q.dtype == jnp.int8 and q.shape == x.shape, name
        want = (x.shape[:1] + (1,) * (x.ndim - 1)) if x.ndim else ()
        assert scale.shape == want, name
        dq = np.asarray(packing.dequantize_leaf_q8(q, scale), np.float64)
        err = np.abs(dq - np.asarray(x, np.float64))
        assert np.all(err <= np.asarray(scale, np.float64) * 0.5
                      * (1 + 1e-5)), name
        # idempotence per leaf
        q2, scale2 = packing.quantize_leaf_q8(
            packing.dequantize_leaf_q8(q, scale))
        assert np.asarray(q2).tobytes() == np.asarray(q).tobytes(), name
        np.testing.assert_allclose(np.asarray(scale2), np.asarray(scale),
                                   rtol=1e-6, err_msg=name)


# ------------------------------------------------- optimizer invariants

def _tree_and_marker():
    params = {"w": _rand(0, (9, 6)), "stack": _rand(1, (3, 4, 5)),
              "b": _rand(2, (7,))}
    marker = {"w": False, "stack": True, "b": False}
    return params, marker


@settings(max_examples=8, deadline=None)
@given(opt_name=st.sampled_from(sorted(OPTS)),
       packed=st.sampled_from([False, True]))
def test_first_update_bit_identical_across_slot_dtypes(opt_name, packed):
    """Fresh int8 slots dequantize to exact zeros, so step 1 must be
    bit-for-bit the f32 step on both engines — divergence can only
    start where requantized state is read back (step 2)."""
    params, marker = _tree_and_marker()
    grads = tree_map(lambda p: 0.1 * p + 0.01, params)
    out = {}
    for dt in ("f32", "int8"):
        opt = OPTS[opt_name](dt)
        state = opt.init(params, stacked=marker if packed else None)
        new, _ = opt.update(grads, state, params,
                            stacked=None if packed else marker)
        out[dt] = new
    for a, b in zip(tree_leaves(out["f32"]), tree_leaves(out["int8"])):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@settings(max_examples=10, deadline=None)
@given(c=st.floats(min_value=0.25, max_value=16.0),
       packed=st.sampled_from([False, True]))
def test_lars_first_update_scale_equivariant_at_int8(c, packed):
    """delta(c*w, c*g) == c * delta(w, g) for the LARS first update with
    int8 slots — the trust ratio reads norms of w and g, never the
    quantized momentum, so the invariance the f32 property test pins
    survives quantized state on both engines."""
    params, marker = _tree_and_marker()
    grads = tree_map(lambda p: 0.1 * p + 0.01, params)
    opt = lars(0.1, weight_decay=1e-4, slot_dtype="int8")

    def delta(scale):
        p = tree_map(lambda x: scale * x, params)
        g = tree_map(lambda x: scale * x, grads)
        state = opt.init(p, stacked=marker if packed else None)
        new, _ = opt.update(g, state, p,
                            stacked=None if packed else marker)
        return tree_map(lambda a, b: np.asarray(a) - np.asarray(b), new, p)

    d1, dc = delta(1.0), delta(c)
    for a, b in zip(tree_leaves(d1), tree_leaves(dc)):
        # rtol bounded by f32 cancellation in (w' - w), same bar as the
        # f32 lr-homogeneity property
        np.testing.assert_allclose(b, c * a, rtol=1e-3, atol=1e-7)


@settings(max_examples=8, deadline=None)
@given(packed=st.sampled_from([False, True]),
       opt_name=st.sampled_from(["adamw", "lamb"]))
def test_adam_bias_correction_holds_at_int8(packed, opt_name):
    """The f32 property — corrected moments equal the constant gradient
    (and its square) every step — re-run with slot_dtype="int8". The
    moments now pass through the code grid each step, so exactness
    relaxes to the quantizer's measured drift: mu <= 9.6e-3 and
    nu <= 2.8e-2 relative after 3 steps (identical across engines and
    both Adam-family rules); bars at ~3x measured."""
    lr, eps, b1, b2 = 0.01, 1e-8, 0.9, 0.999
    params, marker = _tree_and_marker()
    params = tree_map(lambda p: 0.05 * p, params)
    grads = tree_map(lambda p: 0.2 * p + 0.05, params)
    make = adamw if opt_name == "adamw" else lamb
    opt = make(lr, weight_decay=0.0, eps=eps, slot_dtype="int8")
    state = opt.init(params, stacked=marker if packed else None)
    p = params
    for t in range(1, 4):
        p, state = opt.update(grads, state, p,
                              stacked=None if packed else marker)
        slots = state.slots
        if packed:
            layout = state.layout
            mu = packing.unpack(layout, packing.dequantize_q8(
                layout, slots["mu"], slots["mu" + SCALE_SUFFIX]))
            nu = packing.unpack(layout, packing.dequantize_q8(
                layout, slots["nu"], slots["nu" + SCALE_SUFFIX]))
        else:
            mu = tree_map(packing.dequantize_leaf_q8, slots["mu"],
                          slots["mu" + SCALE_SUFFIX])
            nu = tree_map(packing.dequantize_leaf_q8, slots["nu"],
                          slots["nu" + SCALE_SUFFIX])
        for m, n, g in zip(tree_leaves(mu), tree_leaves(nu),
                           tree_leaves(grads)):
            g_np = np.asarray(g, np.float64)
            np.testing.assert_allclose(
                np.asarray(m, np.float64) / (1 - b1 ** t), g_np,
                rtol=3e-2, err_msg=f"mu bias correction, step {t}")
            np.testing.assert_allclose(
                np.asarray(n, np.float64) / (1 - b2 ** t), g_np ** 2,
                rtol=8e-2, err_msg=f"nu bias correction, step {t}")


def _slot_nbytes(state) -> int:
    """Bytes of the rule's own slots and their scales; the master and
    packed weights are not governed by ``slot_dtype``."""
    skip = {packing.MASTER_SLOT, packing.WEIGHT_SLOT}
    return sum(x.nbytes for k, v in state.slots.items() if k not in skip
               for x in tree_leaves(v))


@pytest.mark.parametrize("packed", [False, True], ids=["per_leaf", "packed"])
@pytest.mark.parametrize("opt_name", sorted(OPTS))
def test_int8_slots_take_under_0_30x_the_f32_bytes(opt_name, packed):
    """int8 codes plus their f32 scales hold the slots of a model-shaped
    tree (embeddings, a layer stack) in at most 0.30x the f32 bytes."""
    L, d = 4, 128
    params = {"embed": jnp.zeros((512, d)), "unembed": jnp.zeros((d, 512)),
              "layers": {"wq": jnp.zeros((L, d, d)),
                         "wi": jnp.zeros((L, d, 4 * d)),
                         "scale": jnp.ones((L, d))}}
    marker = {"embed": False, "unembed": False,
              "layers": {"wq": True, "wi": True, "scale": True}}
    nbytes = {dt: _slot_nbytes(OPTS[opt_name](dt).init(
        params, stacked=marker if packed else None))
        for dt in ("f32", "int8")}
    assert 0 < nbytes["int8"] <= 0.30 * nbytes["f32"], nbytes


# ------------------------------------------------------ kernel dispatch

def test_resolve_use_pallas_modes():
    backend = jax.default_backend()
    assert kops.resolve_use_pallas("auto") == (backend == "tpu")
    assert kops.resolve_use_pallas(True) is True
    assert kops.resolve_use_pallas(False) is False


def test_auto_dispatch_takes_jnp_engine_off_tpu():
    """lars() defaults to use_pallas="auto": on this CPU host the whole
    update must trace with ZERO pallas_call launches (the interpreted
    kernels are ~100x the jnp engine)."""
    if jax.default_backend() == "tpu":
        import pytest
        pytest.skip("auto resolves to the compiled kernels on TPU")
    params, marker = _tree_and_marker()
    grads = tree_map(lambda p: 0.1 * p, params)
    opt = lars(0.05)  # use_pallas="auto"
    state = opt.init(params, stacked=marker)
    assert count_pallas_launches(
        lambda g, s, p: opt.update(g, s, p), grads, state, params) == 0


def test_int8_pallas_path_is_two_launches_and_matches_jnp():
    """With int8 slots and use_pallas=True the step is still exactly 2
    launches — the norms kernel plus the fused dequant-update-requant
    apply — and tracks the jnp int8 engine (measured <= 2e-6 relative
    param drift over 4 steps; asserted at 10x)."""
    params, marker = _tree_and_marker()
    grads = tree_map(lambda p: 0.1 * p + 0.01, params)
    runs = {}
    for pallas in (True, False):
        opt = lars(0.05, weight_decay=1e-4, slot_dtype="int8",
                   use_pallas=pallas)
        state = opt.init(params, stacked=marker)
        if pallas:
            assert count_pallas_launches(
                lambda g, s, p: opt.update(g, s, p),
                grads, state, params) == 2
        p = params
        for _ in range(4):
            p, state = opt.update(grads, state, p)
        runs[pallas] = p
    for a, b in zip(tree_leaves(runs[True]), tree_leaves(runs[False])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-8)


def test_int8_apply_kernel_matches_dequant_apply_requant_across_tiles():
    """The fused int8 apply over a buffer of more than one grid tile
    (264 rows: a full 256-row tile, then an 8-row one) equals
    dequantize -> f32 momentum update -> requantize, group by group:
    identical codes, scales and weights."""
    from repro.kernels import lars_kernels
    rows, lane = 264, packing.LANE
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    w, g, m = (jax.random.normal(k, (rows, lane)) for k in ks[:3])
    lr = jax.random.uniform(ks[3], (rows // packing.BLOCK_ROWS, 1))
    layout = packing.build_layout({"x": w}, {"x": False})
    q, scale = packing.quantize_q8(layout, m)
    w_new, q_new, s_new = lars_kernels.apply_flat_q8(
        w, g, q, scale, lr, momentum=0.9, weight_decay=1e-4)
    m_new = 0.9 * packing.dequantize_q8(layout, q, scale) + jnp.repeat(
        lr, packing.BLOCK_ROWS, axis=0) * (g + 1e-4 * w)
    q_ref, s_ref = packing.quantize_q8(layout, m_new)
    np.testing.assert_array_equal(np.asarray(q_new), np.asarray(q_ref))
    np.testing.assert_allclose(s_new, s_ref, rtol=1e-6)
    np.testing.assert_allclose(w_new, w - m_new, rtol=1e-6, atol=1e-6)


# -------------------------------------------------- checkpoint substrate

def test_int8_slots_roundtrip_npz_byte_identical(tmp_path):
    """int8 codes + f32 scales through save/restore_train_state: every
    slot byte-identical — the substrate the mid-cell kill/resume
    contract stands on."""
    cfg = get_config("lenet-mnist")
    pipe = TrainPipeline(build_model(cfg),
                         lars(0.05, slot_dtype="int8"), cfg, donate=False)
    state = pipe.init_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.random((8, 28, 28, 1)), jnp.float32),
             "y": jnp.asarray(rng.integers(0, 10, 8), jnp.int32)}
    state, _ = pipe(state, batch)  # one step -> nonzero codes
    slots = state.opt_state.slots
    assert slots["momentum"].dtype == jnp.int8
    assert "momentum" + SCALE_SUFFIX in slots

    path = str(tmp_path / "state.npz")
    save_train_state(path, state)
    restored = restore_train_state(path,
                                   pipe.init_state(jax.random.key(1)))
    for k, v in slots.items():
        a, b = np.asarray(v), np.asarray(restored.opt_state.slots[k])
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), k

"""Optimizer micro-benchmark: per-step overhead of SGD / LARS / LAMB /
AdamW over realistic parameter pytrees, per-leaf vs flat-packed.

The paper's §6 'challenges' are optimizer-side overheads in SystemML
(per-layer norm passes in the runtime). Here we quantify the analogous
JAX-side cost on both substrate layouts:

  * ``per-leaf``     — slots mirror the param pytree; per-leaf norms
                       (the pjit/sharded reference path);
  * ``flat-packed``  — the whole pytree lives in one superbuffer; norms
                       are one segment-reduced pass;
  * ``flat-packed+pallas`` (LARS) — the two megakernels: exactly 2
                       kernel launches per step regardless of leaf count.

Each row reports wall-clock ms/step AND the traced ``pallas_call``
launch count (0 for pure-jnp paths) so the launch-count-vs-pytree-size
story is measurable, not anecdotal.

With >= 2 devices (nightly forces 8 host devices) a ``zero_sharding``
section additionally pins the ZeRO contract: per-device slot bytes
under ``TrainPipeline(zero=True)`` must be an ndev-way split of the
replicated footprint for every optimizer x slot dtype, and the sharded
step must stay within 1.2x of the replicated mesh step on CPU.

Usage: PYTHONPATH=src python -m benchmarks.optimizer_bench [--quick]
       [--out BENCH_optimizer.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import adamw, lamb, lars, packing, sgd
from repro.core.optim_base import PackedGrads
from repro.kernels import ops
from repro.kernels.introspect import count_pallas_launches
from repro.launch.compile_cache import enable_compile_cache


def make_tree(n_layers: int, d: int, key) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "embed": jax.random.normal(ks[0], (512, d), jnp.float32) * 0.02,
        "layers": {
            "wq": jax.random.normal(ks[1], (n_layers, d, d), jnp.float32),
            "wi": jax.random.normal(ks[2], (n_layers, d, 4 * d), jnp.float32),
            "scale": jnp.ones((n_layers, d), jnp.float32),
        },
        "unembed": jax.random.normal(ks[3], (d, 512), jnp.float32) * 0.02,
    }


STACKED = {"embed": False,
           "layers": {"wq": True, "wi": True, "scale": True},
           "unembed": False}


class _Setup:
    """One compiled, warmed (optimizer, layout) measurement target.

    The step donates state + params — what the train pipeline does
    (``donate_argnums=(0,)`` on the TrainState) — so XLA may update the
    packed slot buffers in place instead of double-buffering them.
    """

    def __init__(self, opt, params, stacked, *, packed: bool,
                 fused: bool = False):
        self.grads = jax.tree_util.tree_map(lambda p: 0.01 * p, params)
        # donation consumes the param buffers — work on a private copy so
        # the caller's tree survives for the other setups
        self.p = jax.tree_util.tree_map(jnp.copy, params)
        self.s = opt.init(self.p, stacked=stacked if packed else None)
        marker = None if packed else stacked  # packed states carry layout
        if fused:
            # the fused-epilogue contract: the accumulation scan hands
            # the mean gradient already packed, so the update skips its
            # own pack pass (the "two-pass" being benchmarked away)
            self.grads = PackedGrads(
                packing.pack(self.s.layout, self.grads))
        self.launches = count_pallas_launches(
            lambda g, s, p: opt.update(g, s, p, stacked=marker),
            self.grads, self.s, self.p)
        self.step = jax.jit(
            lambda g, s, p: opt.update(g, s, p, stacked=marker),
            donate_argnums=(1, 2))
        self.p, self.s = self.step(self.grads, self.s, self.p)  # warmup
        jax.block_until_ready(self.p)
        self.best = float("inf")

    def time_chunk(self, iters: int) -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            self.p, self.s = self.step(self.grads, self.s, self.p)
        jax.block_until_ready(self.p)
        dt = (time.perf_counter() - t0) / iters
        self.best = min(self.best, dt)
        return dt


def bench_paths(opt_factory, params, stacked, *, paths, iters: int,
                reps: int = 9
                ) -> tuple[dict[str, tuple[float, int]],
                           Optional[dict[str, float]]]:
    """Per-path (best seconds/step, launches) + packed-vs-leaf ratio.

    Reps are INTERLEAVED across paths and the asserted ratio is the MIN
    over per-rep pairwise ratios (adjacent chunks see the same machine
    load). See the inline comment for the sensitivity trade-off; the
    MEDIAN pair ratio is also reported in the JSON for trend-watching
    but is too noisy on shared runners to assert on."""
    setups = {path: _Setup(opt_factory(), params, stacked,
                           packed=(path == "flat-packed"))
              for path in paths}
    times: dict[str, list[float]] = {path: [] for path in paths}
    for _ in range(reps):
        for path, setup in setups.items():
            times[path].append(setup.time_chunk(iters))
    ratio = None
    if "per-leaf" in times and "flat-packed" in times:
        # Min over load-paired chunk ratios: scheduler noise on a shared
        # runner corrupts individual pairs (either direction), but a
        # STRUCTURAL packed-path regression — the 4x per-step-pack bug
        # this estimator pins — inflates every pair, so the cleanest
        # pair still reads it. Deliberately downward-biased (a spike on
        # the per-leaf side of one pair deflates the min): trades
        # sensitivity (catches >= ~2x, not 1.1x, under heavy noise) for
        # a flake-free CI assertion. The median pair ratio rides along
        # in the JSON for humans watching the trend.
        pair = sorted(p / l for p, l in zip(times["flat-packed"],
                                            times["per-leaf"]))
        ratio = {"min_pair": pair[0],
                 "median_pair": pair[len(pair) // 2]}
    return {path: (s.best, s.launches) for path, s in setups.items()}, ratio


# --------------------------------------------------- quantized states

# optimizer factories with a slot_dtype knob, shared by the
# quantized-states sections below
_OPT_FACTORIES = {
    "sgd": lambda dt: sgd(0.01, momentum=0.9, slot_dtype=dt),
    "lars": lambda dt: lars(0.01, slot_dtype=dt),
    "lamb": lambda dt: lamb(0.001, slot_dtype=dt),
    "adamw": lambda dt: adamw(0.001, slot_dtype=dt),
}

# the int8 slot-bytes contract: codes are 1/4 the f32 bytes and the
# per-group scales add 1 f32 per 4096 values (packed) or per leading
# index (tree) — well under the 0.30x bar either way
SLOT_BYTES_MAX_RATIO = 0.30

# hypothetical accelerator budget for the accumulation-free batch probe
# (small enough that the optimizer-state share of the budget is visible
# at bench-model scale; the probe is about the DELTA between dtypes)
PROBE_BUDGET_BYTES = 256 * 1024 ** 2


def _slot_nbytes(state) -> int:
    """Bytes of the rule's own slots (momentum/moments + any scale
    siblings) — master weights and the packed weight buffer are excluded
    because ``slot_dtype`` does not govern them."""
    skip = {packing.MASTER_SLOT, packing.WEIGHT_SLOT}
    return sum(x.nbytes
               for k, v in state.slots.items() if k not in skip
               for x in jax.tree_util.tree_leaves(v))


def bench_slot_bytes(params, stacked) -> dict:
    """Measured optimizer-slot bytes per optimizer x engine x dtype,
    with the int8/f32 ratio asserted <= SLOT_BYTES_MAX_RATIO."""
    out: dict = {}
    for name, make in _OPT_FACTORIES.items():
        for path, marker in (("per-leaf", None), ("flat-packed", stacked)):
            nbytes = {dt: _slot_nbytes(make(dt).init(params, stacked=marker))
                      for dt in ("f32", "int8")}
            ratio = nbytes["int8"] / nbytes["f32"]
            assert ratio <= SLOT_BYTES_MAX_RATIO, (
                f"{name}/{path}: int8 slots are {ratio:.3f}x the f32 "
                f"bytes (limit {SLOT_BYTES_MAX_RATIO}) — quantized-state "
                f"memory contract broken")
            out[f"{name}/{path}"] = {
                "f32_bytes": nbytes["f32"], "int8_bytes": nbytes["int8"],
                "ratio": round(ratio, 4),
                "reduction_x": round(nbytes["f32"] / nbytes["int8"], 2)}
    return out


def _pipeline_peak(optimizer, batch_n: int, *, packed: bool) -> Optional[int]:
    """Compiled peak bytes of one lenet train step (fresh pipeline per
    call — ``compiled_peak_bytes`` caches per pipeline)."""
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.train import TrainPipeline

    cfg = get_config("lenet-mnist")
    pipe = TrainPipeline(build_model(cfg), optimizer, cfg, donate=False,
                         packed=packed)
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.random((batch_n, 28, 28, 1)),
                              jnp.float32),
             "y": jnp.asarray(rng.integers(0, 10, batch_n), jnp.int32)}
    return pipe.compiled_peak_bytes(batch)


def bench_compiled_peak(batch_n: int) -> dict:
    """``TrainPipeline.compiled_peak_bytes`` per optimizer x path x
    state dtype on the lenet step."""
    out: dict = {}
    for name, make in _OPT_FACTORIES.items():
        for path, packed in (("per-leaf", False), ("flat-packed", True)):
            for dt in ("f32", "int8"):
                peak = _pipeline_peak(make(dt), batch_n, packed=packed)
                out[f"{name}/{path}/{dt}"] = peak
                print(f"peak {name:6s} {path:12s} {dt:4s} "
                      f"{'n/a' if peak is None else f'{peak:,} B'}",
                      flush=True)
    return out


def bench_batch_probe() -> dict:
    """Max accumulation-free batch under PROBE_BUDGET_BYTES, f32 vs int8
    states: two compiled-peak samples per dtype give bytes/sample and
    the batch-independent fixed cost (params + optimizer state +
    compiler scratch); the probe is their linear extrapolation. LAMB
    carries the largest state (two moments + master), so it bounds the
    dtype delta from above."""
    b_lo, b_hi = 32, 128
    out: dict = {"budget_bytes": PROBE_BUDGET_BYTES,
                 "model": "lenet-mnist", "optimizer": "lamb"}
    for dt in ("f32", "int8"):
        lo = _pipeline_peak(_OPT_FACTORIES["lamb"](dt), b_lo, packed=True)
        hi = _pipeline_peak(_OPT_FACTORIES["lamb"](dt), b_hi, packed=True)
        if lo is None or hi is None:
            out[dt] = None
            continue
        per_sample = (hi - lo) / (b_hi - b_lo)
        fixed = lo - per_sample * b_lo
        out[dt] = {
            "peak_bytes_b32": lo, "peak_bytes_b128": hi,
            "bytes_per_sample": int(per_sample), "fixed_bytes": int(fixed),
            "max_accum_free_batch": int(
                (PROBE_BUDGET_BYTES - fixed) // per_sample)}
    return out


def bench_fused_epilogue(params, stacked, *, iters: int, reps: int = 9
                         ) -> dict:
    """Fused-epilogue step time vs the two-pass update, per trust-ratio
    optimizer. 'two-pass' is today's update on a mean-gradient pytree
    (packs the grads, then updates); 'fused' receives the gradient
    already packed by the accumulation scan and updates in place. Reps
    interleave and the recorded ratio is the min over load-paired
    chunks (same estimator as the packed-vs-leaf pin)."""
    out: dict = {}
    for name in ("lars", "lamb"):
        make = _OPT_FACTORIES[name]
        setups = {
            "two-pass": _Setup(make("f32"), params, stacked, packed=True),
            "fused": _Setup(make("f32"), params, stacked, packed=True,
                            fused=True),
        }
        times: dict[str, list[float]] = {k: [] for k in setups}
        for _ in range(reps):
            for key, setup in setups.items():
                times[key].append(setup.time_chunk(iters))
        pair = sorted(f / t for f, t in zip(times["fused"],
                                            times["two-pass"]))
        out[name] = {
            "two_pass_ms_per_step": setups["two-pass"].best * 1e3,
            "fused_ms_per_step": setups["fused"].best * 1e3,
            "fused_vs_two_pass_min_pair": pair[0],
            "fused_vs_two_pass_median_pair": pair[len(pair) // 2]}
        print(f"fused-epilogue {name:5s}: two-pass "
              f"{out[name]['two_pass_ms_per_step']:.2f} ms, fused "
              f"{out[name]['fused_ms_per_step']:.2f} ms "
              f"(min-pair {pair[0]:.2f}x)", flush=True)
        if jax.default_backend() == "cpu":
            assert pair[0] <= 1.0, (
                f"fused {name} epilogue is {pair[0]:.2f}x the two-pass "
                f"update even in its cleanest load-paired sample — the "
                f"fusion must not cost more than the pass it removes")
    return out


# ------------------------------------------------------- ZeRO sharding

# per-device slot bytes under ZeRO must be an ndev-way split of the
# replicated footprint, with 10% headroom for the row padding that
# makes the superbuffer divide evenly (pad <= shards * block_rows rows)
ZERO_SLOT_BYTES_MAX_RATIO = 1.1
# CPU-proxy step-time bar: the reduce-scatter + all-gather pair may not
# cost more than 20% over the replicated mesh step at bench scale
ZERO_STEP_TIME_MAX_RATIO = 1.2


def _per_device_slot_nbytes(state) -> int:
    """Bytes of the rule's own slots ON ONE DEVICE (the placed arrays'
    shard shapes — 1/ndev of the global bytes for row-sharded ZeRO
    slots, the full bytes for replicated ones)."""
    skip = {packing.MASTER_SLOT, packing.WEIGHT_SLOT}
    total = 0
    for k, v in state.slots.items():
        if k in skip:
            continue
        for x in jax.tree_util.tree_leaves(v):
            shard = x.sharding.shard_shape(x.shape)
            n = 1
            for s in shard:
                n *= s
            total += n * x.dtype.itemsize
    return total


def bench_zero_sharding(params, stacked, batch_n: int, *, iters: int,
                        reps: int = 9) -> Optional[dict]:
    """ZeRO-sharded vs replicated optimizer state on an (ndev, 1)
    data-parallel mesh (nightly forces 8 host devices): asserts the
    per-device slot bytes are an ndev-way split (x1.1 pad headroom) for
    every optimizer x slot dtype on the bench-scale tree, records the
    lenet compiled-peak comparison, and pins the step-time ratio on the
    CPU proxy."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.train import TrainPipeline

    ndev = len(jax.devices())
    if ndev < 2:
        print("zero_sharding: skipped (needs >= 2 devices; run under "
              "--xla_force_host_platform_device_count=8)", flush=True)
        return None
    mesh = make_mesh((ndev, 1), ("data", "model"))
    out: dict = {"ndev": ndev, "mesh": f"{ndev}x1",
                 "slot_bytes_per_device": {}}

    # Slot memory on the bench tree (the pad headroom is meaningful at
    # this scale; a toy model's fixed <= shards*block_rows pad rows
    # would dominate it). Every slot buffer is placed with the ZeRO row
    # spec — device_put itself verifies the rows really divide.
    row_sharded = NamedSharding(mesh, PartitionSpec("data", None))
    bound = ZERO_SLOT_BYTES_MAX_RATIO / ndev
    for name, make in _OPT_FACTORIES.items():
        for dt in ("f32", "int8"):
            rep = make(dt).init(params, stacked=stacked)
            zero = make(dt).init(params, stacked=stacked,
                                 zero_shards=ndev)
            placed = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, row_sharded), zero.slots)
            nbytes = {"replicated": _slot_nbytes(rep),
                      "zero": _per_device_slot_nbytes(
                          dataclasses.replace(zero, slots=placed))}
            ratio = nbytes["zero"] / nbytes["replicated"]
            assert ratio <= bound, (
                f"zero_sharding {name}/{dt}: per-device slot bytes are "
                f"{ratio:.4f}x the replicated footprint (limit "
                f"{bound:.4f} = {ZERO_SLOT_BYTES_MAX_RATIO}/{ndev}) — "
                f"the ZeRO row shard is not an ndev-way split")
            out["slot_bytes_per_device"][f"{name}/{dt}"] = {
                "replicated_bytes": nbytes["replicated"],
                "zero_bytes": nbytes["zero"],
                "ratio": round(ratio, 5)}
            print(f"zero {name:6s} {dt:4s} per-device slots "
                  f"{nbytes['replicated']:>11,} -> {nbytes['zero']:>10,} "
                  f"B ({ratio:.4f}x, bound {bound:.4f})", flush=True)

    cfg = get_config("lenet-mnist")
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.random((batch_n, 28, 28, 1)),
                              jnp.float32),
             "y": jnp.asarray(rng.integers(0, 10, batch_n), jnp.int32)}

    # compiled peaks + step time, replicated vs ZeRO (lars, f32)
    peaks, steppers = {}, {}
    for z in (False, True):
        pipe = TrainPipeline(model, _OPT_FACTORIES["lars"]("f32"), cfg,
                             mesh=mesh, zero=z, donate=False)
        peaks["zero" if z else "replicated"] = \
            pipe.compiled_peak_bytes(batch)
        state = pipe.init_state(jax.random.key(0))
        state, _ = pipe(state, batch)  # compile + warm

        def chunk(n, pipe=pipe, box=[state]):
            t0 = time.perf_counter()
            for _ in range(n):
                box[0], _ = pipe(box[0], batch)
            jax.block_until_ready(box[0].params)
            return (time.perf_counter() - t0) / n
        steppers["zero" if z else "replicated"] = chunk
    times: dict[str, list[float]] = {k: [] for k in steppers}
    for _ in range(reps):
        for key, chunk in steppers.items():
            times[key].append(chunk(iters))
    pair = sorted(z / r for z, r in zip(times["zero"],
                                        times["replicated"]))
    out["compiled_peak_bytes"] = peaks
    out["step_time"] = {
        "optimizer": "lars", "batch": batch_n,
        "replicated_ms_per_step": min(times["replicated"]) * 1e3,
        "zero_ms_per_step": min(times["zero"]) * 1e3,
        "zero_vs_replicated_min_pair": pair[0],
        "zero_vs_replicated_median_pair": pair[len(pair) // 2]}
    print(f"zero step time: replicated "
          f"{out['step_time']['replicated_ms_per_step']:.2f} ms, zero "
          f"{out['step_time']['zero_ms_per_step']:.2f} ms "
          f"(min-pair {pair[0]:.2f}x)", flush=True)
    if jax.default_backend() == "cpu":
        assert pair[0] <= ZERO_STEP_TIME_MAX_RATIO, (
            f"ZeRO step is {pair[0]:.2f}x the replicated mesh step even "
            f"in its cleanest load-paired sample (limit "
            f"{ZERO_STEP_TIME_MAX_RATIO}x) — the reduce-scatter/"
            f"all-gather pair regressed")
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_optimizer.json",
                    help="JSON output path ('' to skip)")
    args = ap.parse_args()
    n_layers, d = (4, 128) if args.quick else (16, 512)
    # chunks must be long enough that per-chunk medians beat dispatch
    # jitter on shared CI runners (the 1.5x assertion depends on it)
    iters = 25 if args.quick else 20

    params = make_tree(n_layers, d, jax.random.key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    n_leaves = len(jax.tree_util.tree_leaves(params))
    print(f"# optimizer bench: {n:,} params, {n_leaves} leaves, "
          f"{iters} iters")
    records = []
    ratios: dict[str, float] = {}
    for name, make in [
        ("sgd", lambda: sgd(0.01, momentum=0.9)),
        ("lars", lambda: lars(0.01)),
        ("lars+pallas", lambda: lars(0.01, use_pallas=True)),
        ("lamb", lambda: lamb(0.001)),
        ("adamw", lambda: adamw(0.001)),
    ]:
        # the megakernels require the packed layout
        paths = (("flat-packed",) if name == "lars+pallas"
                 else ("per-leaf", "flat-packed"))
        timed, ratio = bench_paths(make, params, STACKED, paths=paths,
                                   iters=iters)
        if ratio is not None:
            ratios[name] = ratio
        for path in paths:
            dt, launches = timed[path]
            # rows that actually launch Pallas kernels are tagged with
            # how those kernels ran on this backend: "compiled" (TPU) or
            # "interpret" (the CPU/GPU Pallas interpreter — a
            # correctness path whose timings must never gate perf)
            mode = (None if launches == 0 else
                    ("compiled" if ops.resolve_use_pallas("auto")
                     else "interpret"))
            records.append({"optimizer": name, "path": path,
                            "ms_per_step": dt * 1e3,
                            "pallas_launches": launches,
                            "pallas_mode": mode,
                            "gparam_per_s": n / dt / 1e9})
            print(f"{name:12s} {path:12s} {dt*1e3:8.2f} ms/step "
                  f"{launches:3d} launches "
                  f"({n / dt / 1e9:6.2f} Gparam/s)"
                  + (f" [{mode}]" if mode else ""), flush=True)

    by = {(r["optimizer"], r["path"]): r["ms_per_step"] for r in records}
    base = by[("sgd", "per-leaf")]
    print(f"LARS (per-leaf) overhead vs SGD: "
          f"{(by[('lars', 'per-leaf')] / base - 1) * 100:+.1f}%")
    print(f"LARS flat-packed vs per-leaf: "
          f"{(by[('lars', 'flat-packed')] / by[('lars', 'per-leaf')] - 1) * 100:+.1f}%")

    # Perf contract (regression pin): the packed substrate keeps weights
    # + slots resident in superbuffers, so on CPU the flat-packed path
    # must stay within 2x of the per-leaf reference for EVERY optimizer
    # — matched to the estimator's documented sensitivity (it reliably
    # reads >= ~2x structural regressions like the per-step-pack bug;
    # at --quick scale small-core runners measure seed-level min-pairs
    # up to ~1.8x, so a tighter bar flakes on machine choice, not code).
    # (lars+pallas is excluded: on CPU the Mosaic kernels run in
    # interpret mode, which is a correctness path, not a perf path.)
    if jax.default_backend() == "cpu":
        # interpret-mode rows are correctness runs of the TPU kernels —
        # structurally excluded from every perf assertion
        interpret = {r["optimizer"] for r in records
                     if r.get("pallas_mode") == "interpret"}
        for name, ratio in ratios.items():
            if name in interpret:
                continue
            assert ratio["min_pair"] <= 2.0, (
                f"flat-packed {name} is {ratio['min_pair']:.2f}x the "
                f"per-leaf path even in its cleanest load-paired sample "
                f"(limit 2.0x) — packed-substrate perf regression "
                f"(suspect: a per-step superbuffer pack crept back in)")
        print("packed-vs-leaf ratios (min-pair <= 2.0x, median in "
              "parens): " +
              ", ".join(f"{k} {v['min_pair']:.2f}x ({v['median_pair']:.2f})"
                        for k, v in ratios.items()))

    # quantized optimizer states: slot memory, compiled peaks, the
    # accumulation-free batch probe and the fused-epilogue timing pin
    slot_bytes = bench_slot_bytes(params, STACKED)
    lars_leaf = slot_bytes["lars/flat-packed"]
    print(f"int8 slot bytes (lars, flat-packed): "
          f"{lars_leaf['reduction_x']:.2f}x reduction "
          f"(ratio {lars_leaf['ratio']:.4f})")
    quantized = {
        "slot_bytes": slot_bytes,
        "compiled_peak_bytes": bench_compiled_peak(32 if args.quick
                                                   else 64),
        "accum_free_batch_probe": bench_batch_probe(),
        "fused_epilogue": bench_fused_epilogue(params, STACKED,
                                               iters=iters),
    }

    # ZeRO-sharded optimizer states: per-device memory split + step-time
    # pin on an (ndev, 1) mesh (None on single-device runs)
    zero_sharding = bench_zero_sharding(params, STACKED,
                                        32 if args.quick else 64,
                                        iters=iters)

    if args.out:
        payload = {
            "bench": "optimizer",
            "params": n, "leaves": n_leaves,
            "n_layers": n_layers, "d_model": d, "iters": iters,
            "backend": jax.default_backend(),
            "results": records,
            "packed_vs_leaf_ratio": ratios,
            "quantized_states": quantized,
            "zero_sharding": zero_sharding,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()

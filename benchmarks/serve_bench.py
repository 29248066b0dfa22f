"""Serving benchmark: static vs continuous batching, plus the serve
scenario suite (prefix-sharing + chunked prefill) via the serve-side
spec/record/report path in :mod:`repro.serve.report`.

Part 1 (legacy baseline): heterogeneous request mix through the static
DecodeEngine (pads every sequence to the batch max) vs the ServeEngine
(admits queued requests into freed slots mid-flight), per capacity.

Part 2 (scenarios): declarative traffic scenarios with per-request
TTFT/latency percentiles and useful tok/s, pinning two claims:

  * S1_shared_prefix_speedup — session traffic sharing a long system
    prompt runs >= 1.5x the tok/s of the same engine without the prefix
    store (suffix-only prefill after a radix-index hit);
  * S2_chunked_cuts_p99_ttft — with short requests arriving while long
    prefills are in flight, chunked prefill (``prefill_chunk``) gives a
    lower p99 TTFT than monolithic prefill under identical wall-clock
    traffic timing.

Both claims are asserted. Every scenario also asserts the decode hot
path stayed ONE traced call per emitted token.

Part 3 (library): the SLO scenario-library shapes (steady / bursty /
diurnal / heavy-tail, priority-tiered) through the priority engine as
configured by the serve experiment grid — throughput/tail/preemption
rows only; the A1-A3 SLO claims on these shapes are checked by
``repro.launch.serve_experiment`` (EXPERIMENTS_serve.json).

Usage: PYTHONPATH=src python -m benchmarks.serve_bench [--quick]
       [--arch qwen3-14b] [--out BENCH_serve.json] [--skip-baseline]
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import DecodeEngine, ServeEngine
from repro.serve.report import (ServeScenario, format_scenarios,
                                mixed_length_traffic, run_scenario,
                                shared_prefix_traffic, write_serve_report)


def make_requests(cfg, n, rng, *, prompt_rng=(4, 20), new_rng=(4, 40)):
    return [(rng.integers(0, cfg.vocab_size,
                          (int(rng.integers(*prompt_rng)),)),
             int(rng.integers(*new_rng)))
            for _ in range(n)]


def bench_static(model, params, cfg, requests, slots, capacity,
                 *, warmup: bool = True) -> dict:
    """Static batching: fixed batches of ``slots`` sequences, padded to
    the batch max prompt, decoded to the batch max output length."""
    engine = DecodeEngine(model, params, cfg)
    if warmup:   # compile the prefill/decode shapes out of the timing
        _run_static(engine, requests, slots, capacity)
    return _run_static(engine, requests, slots, capacity)


def _run_static(engine, requests, slots, capacity) -> dict:
    useful = 0
    lane_steps = busy_steps = 0
    t0 = time.perf_counter()
    for i in range(0, len(requests), slots):
        chunk = requests[i:i + slots]
        max_p = max(p.size for p, _ in chunk)
        max_n = max(n for _, n in chunk)
        toks = np.zeros((len(chunk), max_p), np.int32)
        for j, (p, _) in enumerate(chunk):
            # static batch has no per-row lengths: left-pad so every
            # prompt ends at the same position (standard workaround)
            toks[j, max_p - p.size:] = p
        out = engine.generate({"tokens": jax.numpy.asarray(toks)},
                              max_new_tokens=max_n, cache_len=capacity)
        out.block_until_ready()
        useful += sum(n for _, n in chunk)
        lane_steps += len(chunk) * max_n
        busy_steps += sum(n for _, n in chunk)
    wall = time.perf_counter() - t0
    return {"tok_per_s": useful / wall, "wall_s": wall,
            "occupancy": busy_steps / lane_steps, "tokens": useful}


def bench_continuous(model, params, cfg, requests, slots, capacity,
                     *, warmup: bool = True) -> dict:
    engine = ServeEngine(model, params, cfg, slots=slots,
                         capacity=capacity, prefill_bucket=8)
    if warmup:   # compile decode + the admit shape buckets, then reset
        engine.run(requests)
        engine.reset_stats()
    t0 = time.perf_counter()
    finished = engine.run(requests)
    wall = time.perf_counter() - t0
    useful = int(sum(f.tokens.size for f in finished))
    return {"tok_per_s": useful / wall, "wall_s": wall,
            "occupancy": engine.occupancy, "tokens": useful,
            "decode_steps": engine.stats["decode_steps"],
            "decode_traces": engine.traces["decode"]}


def run_baseline(model, params, cfg, args, n_req, caps) -> list:
    rng = np.random.default_rng(0)
    requests = make_requests(cfg, n_req, rng)
    rows = []
    print(f"{cfg.name} ({cfg.family}) — {n_req} requests, "
          f"slots={args.slots}")
    print(f"{'capacity':>9s} {'engine':>11s} {'tok/s':>8s} {'occ':>6s} "
          f"{'wall s':>8s}")
    for cap in caps:
        st = bench_static(model, params, cfg, requests, args.slots, cap)
        co = bench_continuous(model, params, cfg, requests, args.slots, cap)
        assert co["decode_traces"] == 1, co["decode_traces"]
        for name, r in (("static", st), ("continuous", co)):
            print(f"{cap:9d} {name:>11s} {r['tok_per_s']:8.1f} "
                  f"{r['occupancy']:6.2f} {r['wall_s']:8.2f}")
        rows.append({"capacity": cap, "static": st, "continuous": co,
                     "speedup": co["tok_per_s"] / st["tok_per_s"]})
    return rows


def run_scenarios(model, params, cfg, args) -> tuple[dict, dict]:
    """The scenario suite: returns ({name: row}, {claim: bool})."""
    q = args.quick
    chunk = 16 if q else 32
    slots = args.slots

    # -- S1: shared system prompt. The prefix length is a multiple of
    # the chunk size so a primer's chunk-boundary snapshot lands exactly
    # on the shared prefix; followers then prefill only the suffix.
    prefix_len = 4 * chunk if q else 5 * chunk
    sp = dict(sessions=2 if q else 3, per_session=3 if q else 4,
              prefix_len=prefix_len, suffix_len=8, max_new=8, seed=0)
    cap1 = -(-(prefix_len + 8 + 8 + 8) // 64) * 64
    base1 = dict(slots=slots, capacity=cap1, prefill_bucket=8,
                 prefill_chunk=chunk, seed=0)
    waves1 = shared_prefix_traffic(cfg.vocab_size, **sp)
    scen_cold = ServeScenario("cold_prefill", dict(base1), waves1)
    scen_shared = ServeScenario(
        "shared_prefix",
        # the pool must hold the traffic's full steady-state key set
        # (boundary + retirement snapshots) so warm-run inserts dedup to
        # no-ops instead of thrashing the LRU with device copies
        dict(base1, prefix_entries=16 * slots, prefix_min_tokens=8),
        waves1)

    # -- S2: mixed long+short traffic under concurrent decode. Both
    # engines see the SAME wall-clock arrival schedule (time_scale is
    # shared), so the only variable is monolithic vs chunked admission.
    # The long prompt must dwarf one chunk tick (a full-width slots x C
    # call) for chunking to pay off, hence the small chunk here.
    long_len = 1536 if q else 2560
    chunk2 = 48 if q else 64
    ml = dict(n_long=2 if q else 3, n_short=8 if q else 10,
              long_len=long_len, short_len=8, long_new=8,
              short_new=8, seed=1)
    cap2 = -(-(long_len + 8 + 8) // 64) * 64
    # slots cover the whole mix: TTFT then measures the admission path
    # (waiting out a monolithic prefill vs joining the next chunk tick),
    # not slot queueing, which is a throughput property. admit_limit=1
    # keeps admission group shapes stable under bursty arrivals in both
    # engines.
    slots2 = ml["n_long"] + ml["n_short"]
    base2 = dict(slots=slots2, capacity=cap2, prefill_bucket=8,
                 admit_limit=1, seed=0)
    waves2 = mixed_length_traffic(cfg.vocab_size, **ml)
    scen_mono = ServeScenario("mono_prefill", dict(base2), waves2)
    scen_chunked = ServeScenario("chunked_prefill",
                                 dict(base2, prefill_chunk=chunk2), waves2)

    rows = {}
    rows["cold_prefill"] = run_scenario(model, params, scen_cold,
                                        time_scale=0.0)
    rows["shared_prefix"] = run_scenario(model, params, scen_shared,
                                         time_scale=0.0)
    rows["mono_prefill"] = run_scenario(model, params, scen_mono)
    # identical traffic timing: reuse the monolithic run's time scale
    rows["chunked_prefill"] = run_scenario(
        model, params, scen_chunked,
        time_scale=rows["mono_prefill"]["time_scale_s"])

    for name, r in rows.items():
        assert r["decode_traces"] <= 1, (name, r["decode_traces"])

    speedup = (rows["shared_prefix"]["tok_per_s"]
               / rows["cold_prefill"]["tok_per_s"])
    # the TTFT claim is pinned on the interactive (short) class: that is
    # what chunked prefill protects — the long request's own first token
    # arrives LATER under chunking (reported in by_class, the tradeoff)
    mono_p99 = rows["mono_prefill"]["by_class"]["short"]["ttft"]["p99"]
    chunk_p99 = rows["chunked_prefill"]["by_class"]["short"]["ttft"]["p99"]
    claims = {
        "S1_shared_prefix_speedup": bool(speedup >= 1.5),
        "S1_speedup_x": round(speedup, 3),
        "S2_chunked_cuts_p99_ttft": bool(chunk_p99 < mono_p99),
        "S2_ttft_p99_mono_s": mono_p99,
        "S2_ttft_p99_chunked_s": chunk_p99,
        "contract_one_trace_per_token": all(
            r["decode_traces"] <= 1 for r in rows.values()),
    }
    return rows, claims


def run_library(model, params, cfg, args) -> dict:
    """Scenario-library rows: each SLO traffic shape (steady / bursty /
    diurnal / heavy-tail) through the priority engine exactly as the
    serve experiment grid configures it — the bench records throughput,
    occupancy, per-tier tails, and preemption counts; the A1-A3 claim
    checks on these shapes live in the experiment harness
    (EXPERIMENTS_serve.json)."""
    from repro.experiments.serve_grid import ServeCellSpec, get_serve_grid

    grid = get_serve_grid("serve_slo_smoke")
    repeats = 1 if args.quick else grid.repeats
    rows = {}
    for scen in ("steady", "bursty", "diurnal", "heavy_tail"):
        cell = ServeCellSpec(grid.name, scen, "priority", args.slots)
        row = run_scenario(model, params,
                           grid.scenario_for(cell, cfg.vocab_size),
                           time_scale=grid.time_scale_s, repeats=repeats)
        assert row["decode_traces"] <= 1, (scen, row["decode_traces"])
        rows[cell.cell_id] = row
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=0,
                    help="0 = 24 (quick: 12)")
    ap.add_argument("--capacities", default="",
                    help="comma list; default '64,128,256' (quick: "
                    "'64,96')")
    ap.add_argument("--skip-baseline", action="store_true",
                    help="only run the scenario suite")
    ap.add_argument("--no-assert", action="store_true",
                    help="report claims without asserting them")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    n_req = args.requests or (12 if args.quick else 24)
    caps = ([int(c) for c in args.capacities.split(",")] if args.capacities
            else ([64, 96] if args.quick else [64, 128, 256]))

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rows = [] if args.skip_baseline else run_baseline(
        model, params, cfg, args, n_req, caps)

    scenarios, claims = run_scenarios(model, params, cfg, args)
    print()
    print(format_scenarios(scenarios))
    print("claims:", {k: v for k, v in claims.items()})

    library = run_library(model, params, cfg, args)
    print()
    print(format_scenarios(library))

    payload = {"arch": cfg.name, "family": cfg.family, "slots": args.slots,
               "requests": n_req, "backend": jax.default_backend(),
               "rows": rows, "scenarios": scenarios, "library": library,
               "claims": claims}
    if args.out:
        write_serve_report(args.out, payload)
        print(f"wrote {args.out}")

    if not args.no_assert:
        assert claims["S1_shared_prefix_speedup"], (
            f"shared-prefix speedup {claims['S1_speedup_x']}x < 1.5x")
        assert claims["S2_chunked_cuts_p99_ttft"], (
            f"chunked p99 TTFT {claims['S2_ttft_p99_chunked_s']}s not "
            f"below monolithic {claims['S2_ttft_p99_mono_s']}s")
        assert claims["contract_one_trace_per_token"]


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes, print memory/cost analyses, emit roofline records.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices stand in for 2 pods of 256 v5e
chips. The XLA flag above MUST precede every other import (jax locks the
device count on first init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
      --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun.json
"""

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_config, get_shape, param_count  # noqa: E402
from repro.core import lars  # noqa: E402
from repro.distributed import (batch_pspecs, cache_pspecs, param_pspecs,  # noqa: E402
                               state_pspecs, tree_named)
from repro.launch import roofline as RL  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import (adapt_config, cache_shapes,  # noqa: E402
                                decode_token_specs, param_shapes,
                                train_batch_specs)
from repro.models import build_model  # noqa: E402
from repro.serve import make_prefill_step, make_serve_step  # noqa: E402
from repro.train import TrainState, make_train_step  # noqa: E402


def _model_flops(cfg, shape) -> float:
    """6*N*D (train) / 2*N*D (inference) with N = FLOPs-relevant active
    params: the embedding LOOKUP table does no matmul work, so one V*d is
    subtracted for untied models (tied models' single table IS the logits
    matmul and stays counted)."""
    total, active = param_count(cfg)
    n_flops = active - (0 if cfg.tie_embeddings
                        else cfg.vocab_size * cfg.d_model)
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_flops * tokens
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.mode == "prefill" else 1)
    return 2.0 * n_flops * tokens


def _compile_step(cfg, shape, mesh, optimizer: str = "lars"):
    """Lower + compile the mode-appropriate step for cfg on mesh."""
    from repro.core import get_optimizer
    model = build_model(cfg)
    p_shapes = param_shapes(model)
    pspecs = param_pspecs(cfg, p_shapes, mesh)

    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        if shape.mode == "train":
            opt = get_optimizer(optimizer, learning_rate=0.01)
            state_shapes = jax.eval_shape(
                lambda p: TrainState(p, opt.init(p)), p_shapes)
            sspecs = state_pspecs(cfg, state_shapes, mesh)
            batch = train_batch_specs(cfg, shape)
            bspecs = batch_pspecs(cfg, mesh, batch=shape.global_batch)
            step = make_train_step(model, opt, cfg)
            mspecs = {"loss": P(), "aux_loss": P(), "step": P()}
            jitted = jax.jit(
                step,
                in_shardings=(tree_named(mesh, sspecs),
                              tree_named(mesh, bspecs)),
                out_shardings=(tree_named(mesh, sspecs),
                               tree_named(mesh, mspecs)))
            lowered = jitted.lower(state_shapes, batch)
        elif shape.mode == "prefill":
            batch = train_batch_specs(cfg, shape)
            bspecs = batch_pspecs(cfg, mesh, batch=shape.global_batch)
            step = make_prefill_step(model, cfg)
            c_shapes = jax.eval_shape(
                lambda p, b: step(p, b, cache_len=shape.seq_len),
                p_shapes, batch)[1]
            cspecs = cache_pspecs(cfg, mesh, c_shapes,
                                  batch=shape.global_batch)
            jitted = jax.jit(
                lambda p, b: step(p, b, cache_len=shape.seq_len),
                in_shardings=(tree_named(mesh, pspecs),
                              tree_named(mesh, bspecs)),
                out_shardings=(None, tree_named(mesh, cspecs)))
            lowered = jitted.lower(p_shapes, batch)
        else:  # decode
            if cfg.serve_pure_tp:
                from repro.distributed.sharding import serve_param_pspecs
                pspecs = serve_param_pspecs(cfg, p_shapes, mesh)
            c_shapes = cache_shapes(model, shape)
            cspecs = cache_pspecs(cfg, mesh, c_shapes,
                                  batch=shape.global_batch)
            toks = decode_token_specs(shape)
            ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            bsz = 1
            for a in ba:
                bsz *= mesh.shape[a]
            tok_spec = P(ba if shape.global_batch % bsz == 0 else None, None)
            step = make_serve_step(model, cfg)
            jitted = jax.jit(
                step,
                in_shardings=(tree_named(mesh, pspecs),
                              tree_named(mesh, cspecs),
                              NamedSharding(mesh, tok_spec)),
                out_shardings=(None, tree_named(mesh, cspecs)))
            lowered = jitted.lower(p_shapes, c_shapes, toks)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
    return compiled, round(t_lower, 1), round(t_compile, 1)


def _probe_costs(cfg, shape, mesh, optimizer: str = "lars") -> dict:
    """FLOPs / bytes / collective bytes via UNROLLED shallow probes.

    ``compiled.cost_analysis()`` counts a `while` body once, so the
    scan-over-layers production module under-reports per-layer work by
    ~L x. We compile the same step UNROLLED at two shallow depths and
    extrapolate linearly (transformer cost is exactly linear in depth at
    fixed shapes): C(L) = C(k1) + (L - k1) * (C(k2) - C(k1))/(k2 - k1).
    For hybrids the probe depths are multiples of ``attn_every`` so each
    probe block holds exactly one shared-attention application.
    """
    import dataclasses as dc
    ae = cfg.attn_every or 1
    k1, k2 = ae, 2 * ae
    L = cfg.num_layers
    costs = []
    for k in (k1, k2):
        # remat stays ON so probe flops include the production config's
        # backward-recompute work
        changes = dict(num_layers=k, scan_layers=False)
        if cfg.encoder_layers:
            changes["encoder_layers"] = k   # whisper: L_enc == L_dec scaling
        pcfg = dc.replace(cfg, **changes)
        compiled, _, _ = _compile_step(pcfg, shape, mesh, optimizer)
        cost = compiled.cost_analysis() or {}
        costs.append({
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": RL.parse_collectives(compiled.as_text()),
        })

    def extrap(a, b):
        return a + (L - k1) * (b - a) / (k2 - k1)

    coll = {}
    for op in set(costs[0]["coll"]) | set(costs[1]["coll"]):
        coll[op] = max(0, int(extrap(costs[0]["coll"].get(op, 0),
                                     costs[1]["coll"].get(op, 0))))
    return {"flops": extrap(costs[0]["flops"], costs[1]["flops"]),
            "bytes": extrap(costs[0]["bytes"], costs[1]["bytes"]),
            "coll": coll,
            "probe_depths": [k1, k2]}


def lower_pair(arch: str, shape_name: str, mesh, mesh_name: str,
               *, verbose: bool = True, probe: bool = True,
               overrides: dict | None = None,
               optimizer: str = "lars") -> dict:
    """Pass A: compile the production (scan) module — proves the sharding
    config, yields peak memory + the HLO artifact. Pass B (probe=True):
    unrolled shallow probes for loop-corrected roofline terms.

    ``overrides``: config fields replaced AFTER shape adaptation — the
    §Perf hillclimb knob (e.g. {"flash_vjp": True, "attn_q_chunk": 2048}).
    """
    import dataclasses as dc
    shape = get_shape(shape_name)
    cfg = adapt_config(get_config(arch), shape)
    if overrides:
        cfg = dc.replace(cfg, **overrides)
    chips = mesh.size

    compiled, t_lower, t_compile = _compile_step(cfg, shape, mesh,
                                                  optimizer)
    rec = RL.analyze(compiled, arch=arch, shape=shape_name,
                     mesh_name=mesh_name, chips=chips,
                     model_flops=_model_flops(cfg, shape)).row()
    rec["t_lower_s"] = t_lower
    rec["t_compile_s"] = t_compile
    rec["raw_scan_flops_per_dev"] = rec["hlo_flops_total"] / chips
    try:
        rec["memory_analysis"] = str(compiled.memory_analysis())
    except Exception:
        rec["memory_analysis"] = None

    if probe:
        pc = _probe_costs(cfg, shape, mesh, optimizer)
        ro = RL.Roofline(
            arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
            flops_per_device=pc["flops"], bytes_per_device=pc["bytes"],
            collective_bytes=sum(pc["coll"].values()), per_type=pc["coll"],
            model_flops=_model_flops(cfg, shape),
            peak_memory_bytes=rec["peak_memory_bytes_per_device"])
        probe_row = ro.row()
        probe_row["probe_depths"] = pc["probe_depths"]
        for key in ("t_compute_s", "t_memory_s", "t_collective_s",
                    "dominant", "useful_flops_ratio", "hlo_flops_total",
                    "collective_bytes_by_type"):
            rec[key] = probe_row[key]
        rec["probe_depths"] = pc["probe_depths"]

    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s  "
              f"dom={rec['dominant']}  "
              f"t=({RL.fmt_seconds(rec['t_compute_s'])}, "
              f"{RL.fmt_seconds(rec['t_memory_s'])}, "
              f"{RL.fmt_seconds(rec['t_collective_s'])})  "
              f"useful={rec['useful_flops_ratio']*100:.1f}%  "
              f"mem/dev={RL.fmt_bytes(rec['peak_memory_bytes_per_device'])}",
              flush=True)
        print(f"  memory_analysis: {rec['memory_analysis']}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None,
                    help="arch id (repeatable); default: all assigned")
    ap.add_argument("--shape", action="append", default=None,
                    choices=list(SHAPES), help="input shape (repeatable)")
    ap.add_argument("--mesh", default="pod", choices=("pod", "multipod",
                                                      "both"))
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--keep-going", action="store_true")
    args = ap.parse_args()

    archs = args.arch or [a for a in ARCHS if a != "lenet-mnist"]
    shapes = args.shape or list(SHAPES)
    meshes = []
    if args.mesh in ("pod", "both"):
        meshes.append(("pod", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multipod", "both"):
        meshes.append(("multipod", make_production_mesh(multi_pod=True)))

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mesh_name, mesh in meshes:
                try:
                    # roofline probes are single-pod only (§Roofline);
                    # the multipod pass proves the pod axis shards
                    rec = lower_pair(arch, shape_name, mesh, mesh_name,
                                     probe=(mesh_name == "pod"))
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(json.dumps(rec) + "\n")
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape_name, mesh_name, repr(e)))
                    print(f"[{arch} x {shape_name} x {mesh_name}] FAILED: "
                          f"{e}", flush=True)
                    traceback.print_exc()
                    if not args.keep_going:
                        raise SystemExit(1)
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nDRY-RUN OK")


if __name__ == "__main__":
    main()

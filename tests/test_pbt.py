"""PBT controller + bugfix-sweep tests: the kill-at-every-step-boundary
resume regression (including the final-step boundary), strict-JSON
recording under forced divergence, trajectory contiguity validation,
checkpoint clone/perturb semantics, and the population controller's
kill / early-stop / exploit / resume contracts."""

import dataclasses
import glob
import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.checkpoint import clone_checkpoint, restore_train_state
from repro.experiments import (GridRunner, GridSpec, PopulationController,
                               aggregate, cell_from_json, pbt_section,
                               read_trajectory, write_pbt_report)
from repro.experiments.controller import (slice_mean_loss,
                                          trailing_median_spike)
from repro.experiments.record import (TrajectoryRecorder, load_json,
                                      truncate_trajectory)
from repro.experiments.runner import ABORT_ENV

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 3-step single-cell grid for the boundary sweep (1 epoch x 96 / b32).
BOUNDARY = GridSpec(name="boundary_grid", batches=(32,),
                    optimizers=("lars",), trust_coef=0.02,
                    epochs=1, n_train=96, n_test=64)

# 4-step grid the clone/perturb tests extend from.
CLONE = GridSpec(name="clone_grid", batches=(32,), optimizers=("lars",),
                 trust_coef=0.02, epochs=1, n_train=128, n_test=64)

# The population the controller tests drive: 2 optimizers x 2 member
# slots, 4 steps each, 2-step rounds.
POP = GridSpec(name="pbt_tiny", batches=(32,), optimizers=("sgd", "lars"),
               trust_coef=0.02, seeds=(0, 1),
               epochs=1, n_train=128, n_test=64)


def _strict_loads(text: str):
    def _reject(token):
        raise ValueError(f"non-strict JSON token {token!r}")
    return json.loads(text, parse_constant=_reject)


def _stripped(path: str) -> list:
    return read_trajectory(path, strip_timing=True)


# ----------------------------------------------------- record hardening

def test_recorder_nulls_nonfinite_and_flags_diverged(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with TrajectoryRecorder(path) as rec:
        rec.record({"step": 0, "loss": 1.25})
        rec.record({"step": 1, "loss": float("nan"),
                    "trust": {"trust_min": float("inf")}})
    text = open(path).read()
    assert "NaN" not in text and "Infinity" not in text
    records = [_strict_loads(line) for line in text.splitlines()]
    assert "diverged" not in records[0]
    assert records[1]["loss"] is None
    assert records[1]["trust"]["trust_min"] is None
    assert records[1]["diverged"] is True


def test_truncate_rejects_gapped_and_duplicate_steps(tmp_path):
    gapped = str(tmp_path / "gap.jsonl")
    with open(gapped, "w") as f:
        for step in (0, 1, 3, 4):
            f.write(json.dumps({"step": step, "loss": 1.0}) + "\n")
    with pytest.raises(ValueError, match=r"corrupted run directory.*"
                                         r"line 3 has step 3, expected 2"):
        truncate_trajectory(gapped, keep_below_step=4)
    dup = str(tmp_path / "dup.jsonl")
    with open(dup, "w") as f:
        for step in (0, 1, 1):
            f.write(json.dumps({"step": step, "loss": 1.0}) + "\n")
    with pytest.raises(ValueError, match="corrupted run directory"):
        truncate_trajectory(dup, keep_below_step=3)
    # gaps at/after the truncation point are never scanned: the rewind
    # discards them anyway
    late_gap = str(tmp_path / "late.jsonl")
    with open(late_gap, "w") as f:
        for step in (0, 1, 5):
            f.write(json.dumps({"step": step, "loss": 1.0}) + "\n")
    assert truncate_trajectory(late_gap, keep_below_step=2) == 2


def test_truncate_keeps_events_at_or_below_boundary(tmp_path):
    """PBT event records ride along at round boundaries: they are kept
    iff their step is at/below the rewind point, and they do not count
    toward the step-contiguity check."""
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"step": 0, "loss": 3.0}) + "\n")
        f.write(json.dumps({"step": 1, "loss": 2.0}) + "\n")
        f.write(json.dumps({"event": "exploit", "step": 2,
                            "base_lr": 0.02}) + "\n")
        f.write(json.dumps({"step": 2, "loss": 1.5}) + "\n")
        f.write(json.dumps({"event": "exploit", "step": 3,
                            "base_lr": 0.04}) + "\n")
        f.write(json.dumps({"step": 3, "loss": 1.0}) + "\n")
    assert truncate_trajectory(path, keep_below_step=2) == 2
    records = read_trajectory(path)
    assert [r.get("step") for r in records] == [0, 1, 2]
    assert records[-1] == {"event": "exploit", "step": 2, "base_lr": 0.02}


def test_forced_divergence_cell_stays_strict_json(tmp_path):
    """A cell at lr=1e6 goes NaN within a few steps: the trajectory and
    the manifest must stay strict JSON (null + diverged flags), and the
    report must aggregate without crashing on the nulled loss."""
    grid = dataclasses.replace(BOUNDARY, name="div_grid",
                               optimizers=("sgd",), base_lr=1e6,
                               n_train=128)  # 4 steps
    runner = GridRunner(grid, str(tmp_path), log=None,
                        record_memory=False)
    runner.run()
    cell = grid.cells()[0]
    traj_text = open(os.path.join(
        str(tmp_path), cell.cell_id, "trajectory.jsonl")).read()
    assert "NaN" not in traj_text and "Infinity" not in traj_text
    records = [_strict_loads(line) for line in traj_text.splitlines()]
    assert records[-1]["loss"] is None          # not exp(NaN) either
    assert records[-1]["diverged"] is True
    assert any(r.get("diverged") for r in records)
    manifest_text = open(os.path.join(str(tmp_path),
                                      "manifest.json")).read()
    row = _strict_loads(manifest_text)["cells"][cell.cell_id]
    assert row["loss"] is None and row["diverged"] is True
    payload = aggregate(grid, {"cells": {cell.cell_id: row}})
    assert payload["completed_cells"] == 1      # no crash on null loss


def test_committed_reports_are_strict_json():
    """Every committed EXPERIMENTS_* json must parse under a strict
    reader (json.load accepts NaN/Infinity tokens by default, so this is
    a REAL check, not a formality)."""
    paths = sorted(glob.glob(os.path.join(REPO_ROOT, "EXPERIMENTS_*.json")))
    assert paths, "no committed report files found"
    for path in paths:
        _strict_loads(open(path).read())


# ------------------------------------------- resume boundary regression

def test_kill_at_every_step_boundary_resume_sweep(tmp_path):
    """Kill a 3-step cell after EVERY recorded step — including the
    final one, where the kill lands between the last training step and
    the manifest row — and resume. Each resume must complete with a
    trajectory identical to the uninterrupted run and a well-formed
    summary row (the final-boundary case recomputes the row from the
    restored state + last trajectory record instead of crashing on
    empty metrics)."""
    cell = BOUNDARY.cells()[0]
    assert cell.steps == 3
    ref_dir = tmp_path / "ref"
    ref_manifest = GridRunner(BOUNDARY, str(ref_dir), log=None,
                              record_memory=False,
                              checkpoint_every=1).run()
    ref_traj = _stripped(os.path.join(str(ref_dir), cell.cell_id,
                                      "trajectory.jsonl"))
    ref_row = ref_manifest["cells"][cell.cell_id]

    for kill_after in (1, 2, 3):
        run_dir = tmp_path / f"kill{kill_after}"
        os.environ[ABORT_ENV] = str(kill_after)
        try:
            with pytest.raises(KeyboardInterrupt):
                GridRunner(BOUNDARY, str(run_dir), log=None,
                           record_memory=False, checkpoint_every=1).run()
        finally:
            os.environ.pop(ABORT_ENV, None)
        # the kill left a boundary checkpoint and NO manifest row
        assert os.path.exists(os.path.join(str(run_dir), cell.cell_id,
                                           "state.npz"))
        assert load_json(os.path.join(str(run_dir),
                                      "manifest.json"))["cells"] == {}

        manifest = GridRunner(BOUNDARY, str(run_dir), log=None,
                              record_memory=False,
                              checkpoint_every=1).run(resume=True)
        got = _stripped(os.path.join(str(run_dir), cell.cell_id,
                                     "trajectory.jsonl"))
        assert got == ref_traj, f"kill_after={kill_after}"
        row = manifest["cells"][cell.cell_id]
        # the resumed row matches the reference on every deterministic
        # summary key it carries (the final-boundary resume has no live
        # metrics, so the full per-layer table is absent there — by
        # design; the scalar summary must still be complete and equal)
        for key in ("cell_id", "steps", "loss", "test_acc", "train_acc",
                    "gen_error", "trust_final"):
            assert row[key] == ref_row[key], (kill_after, key)
        assert not os.path.exists(os.path.join(str(run_dir), cell.cell_id,
                                               "state.npz"))


# --------------------------------------------------------- clone/perturb

def _clone_cell_dir(runner, cell, dst_name):
    src = runner.cell_dir(cell)
    dst = os.path.join(runner.out_dir, dst_name)
    os.makedirs(dst, exist_ok=True)
    clone_checkpoint(os.path.join(src, "state.npz"),
                     os.path.join(dst, "state.npz"))
    shutil.copyfile(os.path.join(src, "trajectory.jsonl"),
                    os.path.join(dst, "trajectory.jsonl"))
    return dst


def test_clone_perturb_restores_and_uses_new_hyperparams(tmp_path):
    """The PBT exploit path end to end: a checkpoint cloned into another
    lineage restores into a pipeline built with DIFFERENT optimizer
    hyperparameters (slot shapes validate), and the first post-clone
    step already uses the NEW base_lr/trust_coef — pinned byte-identical
    against a fresh runner continuing at those hyperparameters."""
    cell = CLONE.cells()[0]
    runner = GridRunner(CLONE, str(tmp_path / "a"), log=None,
                        record_memory=False, checkpoint_every=0)
    state, start = runner.open_cell(cell)
    runner.run_cell_segment(cell, state, start=start, until_step=2,
                            checkpoint_at_end=True)

    mutant = cell.perturbed(base_lr=0.05, trust_coef=0.08)
    assert mutant.generation == 1
    assert mutant.cell_id == cell.cell_id + "-g1"
    assert mutant.cell_seed() == cell.cell_seed()
    assert mutant.cell_base_lr == 0.05 and mutant.cell_trust_coef == 0.08

    # continue the clone under the MUTATED hypers
    _clone_cell_dir(runner, cell, "clone_m")
    state_m, start_m = runner.open_cell(mutant, resume=True,
                                        dir_name="clone_m")
    assert start_m == 2
    runner.run_cell_segment(mutant, state_m, start=start_m, until_step=4,
                            dir_name="clone_m")
    traj_m = _stripped(os.path.join(runner.out_dir, "clone_m",
                                    "trajectory.jsonl"))

    # continue an identical clone under the ORIGINAL hypers
    _clone_cell_dir(runner, cell, "clone_o")
    state_o, _ = runner.open_cell(cell, resume=True, dir_name="clone_o")
    runner.run_cell_segment(cell, state_o, start=2, until_step=4,
                            dir_name="clone_o")
    traj_o = _stripped(os.path.join(runner.out_dir, "clone_o",
                                    "trajectory.jsonl"))
    assert traj_m[:2] == traj_o[:2]             # shared pre-clone history
    assert [r["loss"] for r in traj_m[2:]] != \
        [r["loss"] for r in traj_o[2:]]         # new hypers took effect

    # pin: a FRESH runner (fresh pipelines/compilation) continuing the
    # same clone at the mutated hypers reproduces traj_m exactly
    fresh = GridRunner(CLONE, str(tmp_path / "b"), log=None,
                       record_memory=False, checkpoint_every=0)
    os.makedirs(fresh.out_dir, exist_ok=True)
    dst = os.path.join(fresh.out_dir, "clone_f")
    os.makedirs(dst, exist_ok=True)
    clone_checkpoint(os.path.join(runner.cell_dir(cell), "state.npz"),
                     os.path.join(dst, "state.npz"))
    shutil.copyfile(os.path.join(runner.cell_dir(cell),
                                 "trajectory.jsonl"),
                    os.path.join(dst, "trajectory.jsonl"))
    state_f, _ = fresh.open_cell(mutant, resume=True, dir_name="clone_f")
    fresh.run_cell_segment(mutant, state_f, start=2, until_step=4,
                           dir_name="clone_f")
    traj_f = _stripped(os.path.join(fresh.out_dir, "clone_f",
                                    "trajectory.jsonl"))
    assert traj_f == traj_m


def test_clone_restore_int8_scale_siblings_survive(tmp_path):
    """Cloning a quantized-slot checkpoint keeps the int8 codes AND
    their f32 scale siblings, and the clone restores into a mutated
    pipeline (trust_coef changed) without shape/dtype complaints."""
    grid = dataclasses.replace(CLONE, name="clone_int8",
                               opt_state_dtypes=("int8",))
    cell = grid.cells()[0]
    runner = GridRunner(grid, str(tmp_path), log=None,
                        record_memory=False, checkpoint_every=0)
    state, _ = runner.open_cell(cell)
    runner.run_cell_segment(cell, state, start=0, until_step=2,
                            checkpoint_at_end=True)
    src = os.path.join(runner.cell_dir(cell), "state.npz")
    dst = os.path.join(str(tmp_path), "lineage2", "state.npz")
    clone_checkpoint(src, dst)
    with np.load(dst) as arrs:
        assert any(arrs[k].dtype == np.int8 for k in arrs.files)
        assert any("scale" in k for k in arrs.files)
    shutil.copyfile(os.path.join(runner.cell_dir(cell),
                                 "trajectory.jsonl"),
                    os.path.join(str(tmp_path), "lineage2",
                                 "trajectory.jsonl"))
    mutant = cell.perturbed(base_lr=0.03, trust_coef=0.05)
    state_m, start_m = runner.open_cell(mutant, resume=True,
                                        dir_name="lineage2")
    assert start_m == 2
    state_m, metrics, _ = runner.run_cell_segment(
        mutant, state_m, start=start_m, until_step=3, dir_name="lineage2")
    assert math.isfinite(float(metrics["loss"]))


def test_restore_rejects_wrong_optimizer_slots(tmp_path):
    """A checkpoint restored into a pipeline whose optimizer needs
    different slot buffers fails loudly instead of silently mangling
    state (the clone path's validation)."""
    import jax
    grid = dataclasses.replace(CLONE, name="clone_mix",
                               optimizers=("sgd", "adamw"))
    sgd_cell, adamw_cell = grid.cells()
    runner = GridRunner(grid, str(tmp_path), log=None,
                        record_memory=False, checkpoint_every=0)
    state, _ = runner.open_cell(sgd_cell)
    state, _, _ = runner.run_cell_segment(sgd_cell, state, start=0,
                                          until_step=1,
                                          checkpoint_at_end=True)
    ckpt = os.path.join(runner.cell_dir(sgd_cell), "state.npz")
    template = runner.pipeline(adamw_cell).init_state(
        jax.random.key(adamw_cell.cell_seed()))
    with pytest.raises(ValueError, match="missing keys|cannot hold"):
        restore_train_state(ckpt, template)


# ----------------------------------------------------------- controller

def test_spike_and_slice_helpers():
    assert trailing_median_spike([1.0, 1.1, 0.9, 1.0, 9.0], spike_k=3.0)
    assert not trailing_median_spike([1.0, 1.1, 0.9, 1.0, 1.2],
                                     spike_k=3.0)
    assert not trailing_median_spike([1.0, 9.0], spike_k=3.0)  # too short
    # None (diverged) entries don't crash the spike detector
    assert not trailing_median_spike([1.0, None, 1.1, 1.0], spike_k=3.0)
    assert slice_mean_loss([{"step": 0, "loss": 2.0},
                            {"step": 1, "loss": 4.0},
                            {"event": "exploit", "step": 1}],
                           lo=0, hi=2) == 3.0
    assert slice_mean_loss([{"step": 0, "loss": None}],
                           lo=0, hi=1) == math.inf
    assert slice_mean_loss([], lo=0, hi=4) == math.inf


def test_controller_kills_on_diverged_flag(tmp_path):
    """The kill rule consumes the recorder's diverged flag: a member
    whose slice went non-finite is terminated with reason recorded in
    the manifest."""
    runner = GridRunner(POP, str(tmp_path), log=None, record_memory=False)
    ctl = PopulationController(runner, exploit_every=2)
    st = ctl._init_members()
    lineage = next(iter(st["members"]))
    member = st["members"][lineage]
    member["step"] = 2
    with TrajectoryRecorder(ctl._traj_path(lineage)) as rec:
        rec.record({"step": 0, "loss": 2.0})
        rec.record({"step": 1, "loss": float("nan")})
    ctl._apply_kills(st, 0)
    assert member["status"] == "killed"
    assert member["reason"] == "diverged"
    assert st["events"][-1]["event"] == "kill"


def test_controller_kills_on_loss_spike(tmp_path):
    runner = GridRunner(POP, str(tmp_path), log=None, record_memory=False)
    ctl = PopulationController(runner, exploit_every=6, spike_k=3.0)
    st = ctl._init_members()
    lineage = next(iter(st["members"]))
    member = st["members"][lineage]
    member["step"] = 6
    with TrajectoryRecorder(ctl._traj_path(lineage)) as rec:
        for i, loss in enumerate([2.0, 1.8, 1.9, 1.7, 1.8, 40.0]):
            rec.record({"step": i, "loss": loss})
    ctl._apply_kills(st, 0)
    assert member["status"] == "killed"
    assert member["reason"] == "loss_spike"


def test_pbt_population_end_to_end(tmp_path):
    """The population runs to completion through the controller:
    exploit events fire with lineage-tagged generations, mutated
    members finish under their perturbed hypers, the exploit event is
    recorded in the adopting lineage's trajectory, and the pbt report
    block merges under its own key without clobbering the study file."""
    runner = GridRunner(POP, str(tmp_path / "run"), log=None,
                        record_memory=False, checkpoint_every=0)
    ctl = PopulationController(runner, exploit_every=2, seed=0)
    st = ctl.run()

    members = st["members"]
    assert len(members) == 4
    assert all(m["status"] in ("done", "killed", "early_stopped")
               for m in members.values())
    exploits = [e for e in st["events"] if e["event"] == "exploit"]
    assert exploits, "no exploit fired — population never evolved"
    mutated = [m for m in members.values()
               if m["cell"]["generation"] >= 1]
    assert mutated
    for m in mutated:
        cell = cell_from_json(m["cell"])
        assert cell.cell_id.endswith(f"-g{cell.generation}")
        # the adoption is recorded in the lineage's trajectory too
        traj = read_trajectory(ctl._traj_path(m["lineage"]))
        events = [r for r in traj if r.get("event") == "exploit"]
        assert events and events[0]["generation"] >= 1
        if m["status"] == "done":
            assert m["row"]["cell_id"] == cell.cell_id
    # every finished member ran its full budget and left no checkpoint
    for m in members.values():
        if m["status"] == "done":
            steps = [r for r in read_trajectory(ctl._traj_path(
                m["lineage"])) if "event" not in r]
            assert len(steps) == cell_from_json(m["cell"]).steps

    # the on-disk manifest is strict json and matches the return value
    disk = _strict_loads(open(ctl.manifest_path).read())
    assert disk == json.loads(json.dumps(st))

    # report merge: the pbt block lands UNDER "pbt", existing keys stay
    report = str(tmp_path / "report.json")
    with open(report, "w") as f:
        json.dump({"claims": {"C3": True}}, f)
    payload = write_pbt_report(report, POP, st, out_dir=runner.out_dir)
    assert payload["claims"] == {"C3": True}
    section = payload["pbt"]
    assert section["events"]["exploit"] == len(exploits)
    for g in section["groups"].values():
        if "best" in g:
            assert len(g["best"]["loss_curve"]) == 4
    assert "P1_tuned_sgd_closes_gap_b32" in section["claims"]
    _strict_loads(open(report).read())


def test_pbt_kill_resume_is_byte_identical(tmp_path):
    """Kill the population run twice (mid-round-0 segment and
    mid-round-1, after the first exploit clone) and resume each time:
    the completed run's trajectories and controller manifest must be
    IDENTICAL to an uninterrupted run — decisions are pure functions of
    boundary trajectories + a statically seeded rng, and clone file-ops
    are journaled."""
    def controller(d):
        runner = GridRunner(POP, str(d), log=None, record_memory=False,
                            checkpoint_every=0)
        return PopulationController(runner, exploit_every=2, seed=0)

    ref_dir = tmp_path / "ref"
    ref = controller(ref_dir).run()
    ref_traj = {lin: _stripped(os.path.join(str(ref_dir), lin,
                                            "trajectory.jsonl"))
                for lin in ref["members"]}

    int_dir = tmp_path / "interrupted"
    # abort 5: mid round-0 segments (a member dies without a boundary
    # checkpoint and must redo its slice). abort 9: mid round-1, AFTER
    # the round-0 exploit clone (the resumed run re-enters mutated
    # lineages). Tick counts are per-process, so the second abort's
    # budget covers the work remaining after the first resume.
    for abort in ("5", "9"):
        os.environ[ABORT_ENV] = abort
        try:
            with pytest.raises(KeyboardInterrupt):
                controller(int_dir).run(resume=True)
        finally:
            os.environ.pop(ABORT_ENV, None)
    got = controller(int_dir).run(resume=True)

    assert json.loads(json.dumps(got)) == json.loads(json.dumps(ref))
    for lin, want in ref_traj.items():
        assert _stripped(os.path.join(str(int_dir), lin,
                                      "trajectory.jsonl")) == want, lin


def test_pbt_manifest_protocol_mismatch_rejected(tmp_path):
    runner = GridRunner(POP, str(tmp_path), log=None, record_memory=False)
    ctl = PopulationController(runner, exploit_every=2)
    ctl._load(resume=False)      # initializes pbt.json
    with pytest.raises(ValueError, match="resume"):
        PopulationController(runner, exploit_every=2)._load(resume=False)
    with pytest.raises(ValueError, match="different"):
        PopulationController(runner, exploit_every=3)._load(resume=True)


# ------------------------------------------------------------------ CLI

def _cli(args, env_extra=None, timeout=1200):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.experiment"] + args,
        capture_output=True, text=True, timeout=timeout, env=env)


def test_cli_pbt_interrupt_and_resume(tmp_path):
    """--pbt through the CLI: a mid-population kill exits 130, --resume
    completes the run, and the report file carries the pbt block."""
    args = ["--grid", "pbt_smoke", "--pbt", "--population", "2",
            "--exploit-every", "1", "--epochs", "4", "--n-train", "512",
            "--checkpoint-every", "0",
            "--out-dir", str(tmp_path / "run"),
            "--out", str(tmp_path / "report.json")]
    first = _cli(args, env_extra={ABORT_ENV: "3"})
    assert first.returncode == 130, first.stdout + first.stderr
    assert "--resume" in first.stdout
    second = _cli(args + ["--resume"])
    assert second.returncode == 0, second.stdout + second.stderr
    report = _strict_loads(open(tmp_path / "report.json").read())
    section = report["pbt"]
    assert len(section["members"]) == 4
    assert all(m["status"] in ("done", "killed", "early_stopped")
               for m in section["members"].values())
    assert "P1_tuned_sgd_closes_gap_b1024" in section["claims"]
    assert "claim pbt.P1_tuned_sgd_closes_gap_b1024" in second.stdout

"""The trace reduction: busy/idle union, kernel time by name, idle gaps
by open span, and the breakdown; on a hand-made trace and on a trace
recorded on the chip (trimmed, committed under perfbench/data)."""

from pathlib import Path

import pytest

from perfbench import trace as tr

DATA = Path(__file__).parents[1] / "data"

# window [0, 100) ns; ops overlap on one plane, a gap 30-50 under "step",
# a gap 80-100 under no span; a second plane busy 0-100.
HAND = {
    "window_ns": [0, 100],
    "ops": {"/device:TPU:0": [["fusion.1", 0, 20], ["norms", 10, 20],
                              ["apply", 50, 30], ["fusion.1", 120, 5]],
            "/device:TPU:1": [["fusion.2", -10, 110]]},
    "spans": [["pb.window", 0, 100], ["pb.pipeline", 0, 60],
              ["pb.step", 25, 30]],
}


def test_busy_is_the_union_inside_the_window():
    assert tr.busy_intervals(HAND["ops"]["/device:TPU:0"], 0, 100) == [
        [0, 30], [50, 80]]
    # plane 0 busy 60 ns, plane 1 busy 100 ns (clipped): mean 80 ns
    assert tr.busy_s(HAND) == pytest.approx(80e-9)
    assert tr.window_s(HAND) == pytest.approx(100e-9)


def test_kernel_time_by_name():
    secs = tr.op_seconds(HAND, r"norms|apply")
    assert secs == {"norms": pytest.approx(10e-9), "apply": pytest.approx(15e-9)}


def test_idle_gaps_by_open_span():
    gaps = tr.idle_gaps(HAND)
    # plane 0: 30-50 (mid 40: "pb.step" is the innermost open span) and
    # 80-100 (no span); plane 1 never idle; averaged over 2 planes
    assert gaps == {"pb.step": pytest.approx(10e-9),
                    "none": pytest.approx(10e-9)}
    assert sum(gaps.values()) == pytest.approx(
        tr.window_s(HAND) - tr.busy_s(HAND))


def test_breakdown_lists_at_most_ten():
    b = tr.breakdown(HAND)
    assert [k for k, _ in b["device_ops"]][0] == "fusion.2"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_trim_keeps_the_events_inside():
    t = tr.trim(HAND, 40, 60)
    assert t["window_ns"] == [40, 60]
    assert [e[0] for e in t["ops"]["/device:TPU:0"]] == ["apply"]


# ------------------------------------------------ traces recorded on chip

def _chip(name):
    return tr.load(str(DATA / name))


def _reader(name):
    from perfbench import harness
    return harness.reader(name)


def _timeline_busy(trace, res_ns=10):
    """Busy seconds of one plane by marking a fine timeline (independent
    of the interval union)."""
    import numpy as np
    t0, t1 = trace["window_ns"]
    (evs,) = trace["ops"].values()
    on = np.zeros(int((t1 - t0) // res_ns) + 1, bool)
    for _, s, d in evs:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            on[int((a - t0) // res_ns):int((b - t0) // res_ns)] = True
    return on.sum() * res_ns * 1e-9


def test_chip_trace_busy_and_idle():
    t = _chip("train16_chip_trace.json.gz")
    busy, win = tr.busy_s(t), tr.window_s(t)
    assert 0 < busy <= win
    assert busy == pytest.approx(_timeline_busy(t), abs=2e-4)
    assert sum(tr.idle_gaps(t).values()) == pytest.approx(win - busy,
                                                           abs=1e-9)
    b = tr.breakdown(t)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(len(k) <= tr.NAME_CHARS and not tr.CONTAINER.match(k)
               for k, _ in b["device_ops"])


def test_chip_trace_lars_update():
    """One optimizer step's update on the chip: two Pallas kernels that
    take the (263144, 512) f32 superbuffer of smollm-135m."""
    t = _chip("train16_chip_trace.json.gz")
    evs = [d for evs in t["ops"].values() for n, s, d in evs
           if "tpu_custom_call" in n and "f32[263144,512]" in n]
    assert len(evs) == 2
    ctx = {"kind": "train", "trace": t, "steps": 1,
           "superbuffer": (263144, 512), "lars_bytes": 4.04e9,
           "peak": {"hbm_bytes_per_s": 819e9}}
    ms = _reader("lars_update.device_ms").read(ctx)
    assert ms == pytest.approx(sum(evs) * 1e-6)
    pct = _reader("lars_update_roofline").read(ctx)
    assert pct == pytest.approx(100 * 4.04e9 / 819e9 / (sum(evs) * 1e-9))
    assert 0 < pct <= 100


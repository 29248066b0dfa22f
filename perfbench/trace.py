"""Profiler trace: capture, extraction to a compact form, and the
reduction from that form to device busy time, kernel times and idle gaps.

Compact form (what the readers and the tests see)::

    {"window_ns": [t0, t1],
     "ops": {"<device plane>": [[name, start_ns, dur_ns], ...]},
     "spans": [[name, start_ns, dur_ns], ...]}

``ops`` are the device's operations (the "XLA Ops" line of each
``/device:TPU:n`` plane); ``spans`` are the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` names that start with ``SPAN_PREFIX``).
The window is the span named ``SPAN_PREFIX + "window"``.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from collections import defaultdict

SPAN_PREFIX = "pb."
WINDOW = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
# ops that hold other ops (a loop's events nest its body's events)
CONTAINER = re.compile(r"^%?(while|conditional)\.")
NAME_CHARS = 160        # an op's name in the breakdown: its HLO text, cut


def extract(xplane_path: str) -> dict:
    """Read an ``.xplane.pb`` file into the compact form."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
    win = [s for s in spans if s[0] == WINDOW]
    if not win:
        raise RuntimeError("trace holds no window span")
    t0 = win[0][1]
    return {"window_ns": [t0, t0 + win[0][2]], "ops": ops, "spans": spans}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def trim(trace: dict, t0: float, t1: float) -> dict:
    """The part of a trace inside [t0, t1]: window, ops and spans."""
    def inside(evs):
        return [e for e in evs if e[1] + e[2] > t0 and e[1] < t1]
    return {"window_ns": [max(t0, trace["window_ns"][0]),
                          min(t1, trace["window_ns"][1])],
            "ops": {k: inside(v) for k, v in trace["ops"].items()},
            "spans": inside(trace["spans"])}


# -------------------------------------------------------------- reduction

def _clip(evs, t0, t1):
    for name, s, d in evs:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def busy_intervals(evs, t0, t1) -> list:
    """Union of the intervals in which an operation ran, inside [t0, t1]."""
    out = []
    for _, a, b in sorted(_clip(evs, t0, t1), key=lambda x: x[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def window_s(trace: dict) -> float:
    t0, t1 = trace["window_ns"]
    return (t1 - t0) * 1e-9


def busy_s(trace: dict) -> float:
    """Seconds some operation ran, averaged over the device planes."""
    t0, t1 = trace["window_ns"]
    per = [sum(b - a for a, b in busy_intervals(evs, t0, t1)) * 1e-9
           for evs in trace["ops"].values()]
    return sum(per) / len(per) if per else 0.0


def op_seconds(trace: dict, pattern: str | None = None) -> dict:
    """{op name: seconds inside the window}, summed over the device
    planes and divided by their number; only names matching ``pattern``
    when one is given."""
    t0, t1 = trace["window_ns"]
    rx = re.compile(pattern) if pattern else None
    tot = defaultdict(float)
    for evs in trace["ops"].values():
        for name, a, b in _clip(evs, t0, t1):
            if rx is None or rx.search(name):
                tot[name] += (b - a) * 1e-9
    n = max(len(trace["ops"]), 1)
    return {k: v / n for k, v in tot.items()}


def idle_gaps(trace: dict) -> dict:
    """{label: idle seconds}: every stretch of the window in which no
    operation ran on a device, labelled by the innermost benchmark span
    open at its middle ("none" where no span was open), averaged over
    the device planes."""
    t0, t1 = trace["window_ns"]
    spans = sorted((s for s in trace["spans"] if s[0] != WINDOW),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    tot = defaultdict(float)
    for evs in trace["ops"].values():
        edge = t0
        gaps = []
        for a, b in busy_intervals(evs, t0, t1):
            if a > edge:
                gaps.append((edge, a))
            edge = b
        if t1 > edge:
            gaps.append((edge, t1))
        for a, b in gaps:
            tot[_open_span(spans, starts, (a + b) / 2)] += (b - a) * 1e-9
    n = max(len(trace["ops"]), 1)
    return {k: v / n for k, v in tot.items()}


def _open_span(spans, starts, t) -> str:
    """Name of the latest-started span that covers ``t`` (spans nest, so
    that is the innermost), or "none"."""
    i = bisect.bisect_right(starts, t)
    for name, s, d in reversed(spans[max(0, i - 64):i]):
        if s + d > t:
            return name
    return "none"


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations (loops left out, since their bodies' ops are
    listed) that took most time, and the idle time by what the host was
    doing, each a list of [name, seconds]."""
    ops = sorted(((k[:NAME_CHARS], v) for k, v in op_seconds(trace).items()
                  if not CONTAINER.match(k)), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def pallas_kernel(operand: str) -> str:
    """Pattern of a Pallas (Mosaic) kernel's op that takes an operand of
    the given HLO shape text, e.g. ``f32[263144,512]``."""
    return re.escape(operand) + r".*custom_call_target=\"tpu_custom_call\""

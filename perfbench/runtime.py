"""What every kind of traffic shares: host spans, the compile counter, the traced
window, device memory, and the comparison record."""

from __future__ import annotations

import contextlib
import shutil
import sys
import tempfile

import jax

from perfbench import trace as tr

_EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
           "/jax/core/compile/jaxpr_trace_duration": "traces"}
# JAX's compile events are process-wide, and so is this count of them
_counts: dict = {}


def _on_event(name, _secs, **_kw):
    if name in _EVENTS:
        _counts[_EVENTS[name]] += 1


def _count(kind: str) -> int:
    if not _counts:
        _counts.update(compiles=0, traces=0)
        jax.monitoring.register_event_duration_secs_listener(_on_event)
    return _counts[kind]


def compiles() -> int:
    """Backend compiles since the first count in this process (a
    persistent-cache hit is not one)."""
    return _count("compiles")


def traces() -> int:
    """Functions traced to jaxprs since the first count in this process."""
    return _count("traces")


def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    return jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + name)


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def window(traced: bool, keep: str | None = None):
    """The measured window, traced or not. Yields a dict that holds
    ``compiles`` (inside the window) and, traced, the compact ``trace``."""
    out = {}
    log_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if traced else None
    if traced:
        jax.profiler.start_trace(log_dir)
    c0, r0 = compiles(), traces()
    try:
        with span("window"):
            yield out
    finally:
        out["compiles"] = compiles() - c0
        out["traces"] = traces() - r0
        if traced:
            jax.profiler.stop_trace()
            try:
                out["trace"] = tr.extract(tr.find_xplane(log_dir))
            finally:
                shutil.rmtree(log_dir, ignore_errors=True)
            if keep:
                tr.save(out["trace"], keep)


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class Checks:
    """Numbers compared with the reference, each with its own limit."""

    def __init__(self):
        self.items = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.items.append((name, float(value), float(limit)))

    @property
    def ok(self) -> bool:
        return bool(self.items) and all(v <= lim for _, v, lim in self.items)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.items}

    def print_stderr(self) -> None:
        for n, v, lim in self.items:
            print(f"check {n} {v!r} limit {lim!r} "
                  f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
        sys.stderr.flush()


"""Attention forward kernels run per layer application: the traced
window's executions of the splash forward with residuals (the one that
feeds the backward) over steps x layers x microbatches, averaged over
devices. 2 when the backward recomputes each layer's forward, 1 when the
forward's output and logsumexp are kept; nothing where none ran."""

import re

from perfbench.counts import dims

FWD = re.compile(r"^%splash_mqa_fwd_residuals(\.\d+)? =")


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "train" or not t or not ctx.get("steps"):
        return None
    t0, t1 = t["window_ns"]
    runs = sum(1 for evs in t["ops"].values() for name, s, _ in evs
               if t0 <= s < t1 and FWD.search(name))
    if not runs:
        return None
    runs /= len(t["ops"])
    per_step = dims(ctx["cfg"])["L"] * ctx["mix"]["accum_steps"]
    return runs / (ctx["steps"] * per_step)

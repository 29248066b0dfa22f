"""Share of the step's attention applications whose backward ran in the
dk/dv attention kernel, in percent: that kernel's executions in the
traced window over steps x layers x microbatches. 100 when every layer
of every microbatch takes the kernel; nothing where none ran."""

import re

from perfbench.counts import dims

DKV = re.compile(r"^%splash_mqa_dkv_no_residuals(\.\d+)? =")


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "train" or not t or not ctx.get("steps"):
        return None
    t0, t1 = t["window_ns"]
    runs = sum(1 for evs in t["ops"].values() for name, s, _ in evs
               if t0 <= s < t1 and DKV.search(name))
    if not runs:
        return None
    runs /= len(t["ops"])
    per_step = dims(ctx["cfg"])["L"] * ctx["mix"]["accum_steps"]
    return 100.0 * runs / (ctx["steps"] * per_step)

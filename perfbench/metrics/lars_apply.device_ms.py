"""Device time of the LARS update's apply kernel per optimizer step, in
ms: the ops the trace names after the Pallas kernel ``lars_apply`` (the
f32 momentum's; ``lars_apply_q8``, the int8 momentum's, is not read)."""

from perfbench import trace as tr

KERNEL = r"^%lars_apply(\.\d+)? ="


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "train" or not t or not ctx.get("steps"):
        return None
    secs = sum(tr.op_seconds(t, KERNEL).values())
    if secs <= 0:
        return None
    return 1e3 * secs / ctx["steps"]

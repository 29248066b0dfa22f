"""Device time of the LARS update's kernels (the Pallas kernels that take
the packed f32 superbuffer) per optimizer step, in ms."""

from perfbench import trace as tr


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "train" or not t or not ctx.get("steps"):
        return None
    rows, lane = ctx["superbuffer"]
    secs = sum(tr.op_seconds(t, tr.pallas_kernel(
        f"f32[{rows},{lane}]")).values())
    if secs <= 0:
        return None
    return 1e3 * secs / ctx["steps"]

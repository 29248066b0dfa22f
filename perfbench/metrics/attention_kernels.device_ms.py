"""Device time of the causal attention kernels per optimizer step, in ms:
the ops the trace names after the splash-attention Pallas kernels, its
forward (with or without the residuals of autodiff), dq and dk/dv."""

from perfbench import trace as tr

KERNELS = r"^%splash_mqa_(fwd|dq|dkv)_(no_)?residuals(\.\d+)? ="


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "train" or not t or not ctx.get("steps"):
        return None
    secs = sum(tr.op_seconds(t, KERNELS).values())
    if secs <= 0:
        return None
    return 1e3 * secs / ctx["steps"]

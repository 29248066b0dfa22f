"""Share of the traced training window in which no operation ran on the
device (averaged over the chips), in percent."""

from perfbench import trace as tr


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "train" or not t or not t["ops"]:
        return None
    return 100.0 * (1.0 - tr.busy_s(t) / tr.window_s(t))

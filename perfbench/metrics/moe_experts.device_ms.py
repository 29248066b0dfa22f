"""Device time of the routed experts' grouped matmuls per optimizer step,
in ms: the ops the chip's compiled program names after XLA's ragged dot
(``jax.lax.ragged_dot``), its group metadata and its forward and
backward kernels alike. Nothing where none ran."""

from perfbench import trace as tr

KERNELS = r"^%ragged-dot"


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "train" or not t or not ctx.get("steps"):
        return None
    secs = sum(tr.op_seconds(t, KERNELS).values())
    if secs <= 0:
        return None
    return 1e3 * secs / ctx["steps"]

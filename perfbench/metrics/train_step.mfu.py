"""Model FLOPs of the optimizer steps in the traced window (forward and
backward, causal attention, no recomputation) over window x chips x the
chip's bf16 peak, in percent."""

from perfbench import trace as tr


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "train" or not t or not ctx.get("steps"):
        return None
    return 100.0 * ctx["step_flops"] * ctx["steps"] / (
        tr.window_s(t) * ctx["chips"] * ctx["peak"]["bf16_flops"])

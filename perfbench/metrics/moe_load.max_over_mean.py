"""Rows of the busiest held expert over the mean rows of the held
experts, per MoE layer, averaged over layers, microbatches and the
window's steps: the program's ``moe_load_max_over_mean`` counter. 1 is
a perfectly even load."""


def read(ctx):
    v = ctx.get("moe_load_max_over_mean")
    if ctx.get("kind") != "train" or not v:
        return None
    return float(v)

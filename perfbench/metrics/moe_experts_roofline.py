"""Model FLOPs of the routed experts' grouped matmuls over their device
time at the chip's bf16 peak, in percent: 2 x 3 x d x ff x rows x 3
(gate, up and down projections, forward and backward, no recompute) per
step, with rows the (token, expert) rows the held experts computed (the
program's ``moe_rows`` counter), over the device time of the ops named
after the ragged dot."""

from perfbench import trace as tr
from perfbench.counts_deepseek_v2 import expert_rows_flops

KERNELS = r"^%ragged-dot"


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "train" or not t or not ctx.get("steps") \
            or not ctx.get("moe_rows"):
        return None
    secs = sum(tr.op_seconds(t, KERNELS).values())
    if secs <= 0:
        return None
    flops = expert_rows_flops(ctx["cfg"], ctx["moe_rows"]) * ctx["steps"]
    return 100.0 * flops / (secs * ctx["peak"]["bf16_flops"])

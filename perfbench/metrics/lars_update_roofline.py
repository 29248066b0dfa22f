"""Bytes the momentum-LARS update must move per step (counts.
lars_update_bytes: two passes over the packed f32 buffers plus the bf16
params written) over the chip's HBM bandwidth, divided by the device time
of the update's kernels, in percent. Bandwidth bounds this update.

The update's kernels are the Pallas kernels that take the packed f32
superbuffer as an operand."""

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
    ideal = ctx["lars_bytes"] * ctx["steps"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * ideal / secs

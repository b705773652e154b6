"""Decode attention kernel's share of its roofline, %: the least time the
chip needs for the live-context KV bytes and FLOPs of the traced decode
calls (context lengths from the engine's iteration hook), over the device
time of the custom calls (the Pallas kernel) inside those calls."""
import roofline


def read(ctx):
    t = ctx.trace
    if t is None or t.kernel_s["decode"] <= 0:
        return None
    least = 0.0
    for r in ctx.traced_rows:
        if r.decode_ctx:
            f, b = roofline.decode_attn(ctx.dims, r.decode_ctx)
            least += roofline.least_time(f, b, ctx.peak)
    if least <= 0:
        return None
    return 100.0 * least / t.kernel_s["decode"]

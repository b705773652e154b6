"""Decode attention kernel's share of its roofline, %: the least time the
chip needs for the live-context KV bytes and FLOPs of the traced decode
calls (context lengths from the engine's iteration hook), over the device
time of the kernel named ``paged_decode_attention`` inside those calls."""
import roofline

KERNEL = "paged_decode_attention"


def read(ctx):
    t = ctx.trace
    kernel_s = t.kernels["decode"].get(KERNEL, 0.0) if t is not None else 0.0
    if kernel_s <= 0:
        return None
    least = 0.0
    for r in ctx.traced_rows:
        if r.decode_ctx:
            f, b = roofline.decode_attn(ctx.dims, r.decode_ctx)
            least += roofline.least_time(f, b, ctx.peak)
    if least <= 0:
        return None
    return 100.0 * least / kernel_s

"""Chunked-prefill attention kernel's share of its roofline, %: the least
time the chip needs for the live context's KV bytes and causal FLOPs of
the traced prefill chunks, over the device time of the custom calls (the
Pallas kernel) inside those calls."""
import roofline


def read(ctx):
    t = ctx.trace
    if t is None or t.kernel_s["prefill"] <= 0:
        return None
    least = 0.0
    for r in ctx.traced_rows:
        for start, n in r.prefill:
            f, b = roofline.prefill_attn(ctx.dims, start, n)
            least += roofline.least_time(f, b, ctx.peak)
    if least <= 0:
        return None
    return 100.0 * least / t.kernel_s["prefill"]

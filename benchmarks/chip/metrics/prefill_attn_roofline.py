"""Chunked-prefill attention kernel's share of its roofline, %: the least
time the chip needs for the live context's KV bytes and causal FLOPs of
the traced prefill chunks, over the device time of the kernel named
``chunked_prefill_attention`` inside those calls."""
import roofline

KERNEL = "chunked_prefill_attention"


def read(ctx):
    t = ctx.trace
    kernel_s = t.kernels["prefill"].get(KERNEL, 0.0) if t is not None else 0.0
    if kernel_s <= 0:
        return None
    least = 0.0
    for r in ctx.traced_rows:
        for start, n in r.prefill:
            f, b = roofline.prefill_attn(ctx.dims, start, n)
            least += roofline.least_time(f, b, ctx.peak)
    if least <= 0:
        return None
    return 100.0 * least / kernel_s

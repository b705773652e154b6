"""Model FLOP utilization of the traced steps, %: model FLOPs of the
tokens computed in the traced span (2 x matmul weights per token,
attention at each token's context, the output head per logit row) over
the span's length times the chip's peak bf16 rate."""
import roofline


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or not ctx.traced_rows:
        return None
    spans, rows = [], 0
    for r in ctx.traced_rows:
        spans += r.prefill
        spans += [(c - 1, 1) for c in r.decode_ctx]
        rows += len(r.prefill) + len(r.decode_ctx)
    flops = roofline.step_flops(ctx.dims, spans, rows)
    if flops <= 0:
        return None
    return 100.0 * flops / (t.window_s * ctx.peak["bf16_flops"])

"""Device time per prefill-chunk call, ms: device program time inside the
runner's ``prefill_chunk`` spans over the number of those calls (trace)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.calls["prefill"] == 0:
        return None
    return 1e3 * t.program_s["prefill"] / t.calls["prefill"]

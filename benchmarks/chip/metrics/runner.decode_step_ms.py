"""Device time per decode call, ms: device program time inside the
runner's ``decode`` spans over the number of those calls (trace)."""


def read(ctx):
    t = ctx.trace
    if t is None or t.calls["decode"] == 0:
        return None
    return 1e3 * t.program_s["decode"] / t.calls["decode"]

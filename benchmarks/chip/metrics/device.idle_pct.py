"""Device idle share of the traced span, %: one minus the union of device
op intervals over the span (first traced step's start to the last one's
end), averaged over the chips used."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)

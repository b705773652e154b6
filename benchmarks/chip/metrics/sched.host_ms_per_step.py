"""Scheduler host time per engine step, ms: mean of the engine's
``IterationDetail.schedule_wall`` over the window's steps."""


def read(ctx):
    if not ctx.rows:
        return None
    return 1e3 * sum(r.schedule_wall for r in ctx.rows) / len(ctx.rows)

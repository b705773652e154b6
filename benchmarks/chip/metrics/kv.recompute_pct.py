"""Recomputed prefill, %: prefill tokens re-done after a preemption
(``Request.recomputed_tokens``) over all prefill tokens computed, over the
window's steps."""


def read(ctx):
    computed = sum(n for r in ctx.rows for _, n in r.prefill)
    if computed <= 0:
        return None
    return 100.0 * sum(r.recomputed for r in ctx.rows) / computed

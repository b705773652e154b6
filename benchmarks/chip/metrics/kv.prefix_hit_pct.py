"""Offline prefix hits, %: of the prompt tokens of the offline requests
first admitted in the window, the share already in the KV cache at that
first admission (where the request's first prefill chunk starts). A
re-admission after preemption, which re-finds the request's own blocks,
is not counted."""


def read(ctx):
    admitted = sum(n for r in ctx.rows for _, n in r.offline_first)
    if admitted <= 0:
        return None
    return 100.0 * sum(s for r in ctx.rows for s, _ in r.offline_first) \
        / admitted

"""Host spans on the profiler's clock, with wall-time counters beside them.

``span(name, acc, key, **ids)`` opens a ``jax.profiler.TraceAnnotation``.
Inside a profiler session (``jax.profiler.start_trace`` or
``start_server``) it lands in the trace's host plane, on the same clock as
the device's ops, with ``ids`` (``rid``, ``rows``) as the event's stats.
Given an accumulator it also adds its ``time.perf_counter`` duration to
``acc.<key>``, so one call gives the span and the counter at the same
boundary. Outside a session the annotation records nothing and costs about
a microsecond, so the program's spans are always on.

``spanned(name)`` puts every call of a method inside ``span(name)``: the
span then sits inside any wrapper a caller puts around that method, so
the innermost span of a trace is the program's own.

``jax.profiler`` is imported at first use, not with this module, which the
engine and the runners import.
"""
from __future__ import annotations

import functools
import time

_profiler = None


def _jax_profiler():
    global _profiler
    if _profiler is None:
        import jax.profiler
        _profiler = jax.profiler
    return _profiler


class span:
    """Context manager: a profiler host span named ``name`` and, given
    ``acc``, its wall seconds added to ``acc.<key>``."""

    __slots__ = ("_ann", "_acc", "_key", "_t0")

    def __init__(self, name: str, acc=None, key: str = "", **ids):
        self._ann = _jax_profiler().TraceAnnotation(name, **ids)
        self._acc, self._key = acc, key

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._acc is not None:
            setattr(self._acc, self._key, getattr(self._acc, self._key)
                    + time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)


def step_span(name: str, step: int):
    """The profiler's step marker (``StepTraceAnnotation``) for step number
    ``step``: a host span that trace viewers group steps by."""
    return _jax_profiler().StepTraceAnnotation(name, step_num=step)


def spanned(name: str):
    """Method decorator: each call runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*a, **k):
            with span(name):
                return fn(*a, **k)
        return inner
    return wrap

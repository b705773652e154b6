#!/usr/bin/env python3
"""The program's own spans and counters in one run of a cell.

    python benchmarks/chip/spans.py --workload qwen3-4b.docqa-chat \\
        --seed 1 --seconds 50 --trace 1 [--annotation-cost] [--out f.json]

One run as ``run.py`` makes it (``cell.run``), with what the benchmark's
own files do not read yet:

- each step's row (``cell.IterRow``) carries its ``StepTimes`` (the wall
  seconds of the program's ``echo.*`` spans, its launches and syncs) and
  the front-door stamps of the online requests the step admits for the
  first time, and the readers below read them;
- in a traced run the device trace keeps the program's ``echo.*`` host
  spans beside the benchmark's ``bench.*`` ones, so each idle gap goes to
  the innermost span of either. The ``bench.*`` spans, and so the ten
  per-layer metrics, read as they do in ``run.py``.

From the window's steps it reads ``frontdoor.queue_wait_ms``,
``engine.host_ms_per_step``, ``runner.syncs_per_step`` and
``runner.sample_ms_per_step`` (the readers below), the per-phase split of
the host time, and, traced, the share of device idle time under ``echo.*``
spans and the host time of the traced steps over the traced span.
``--annotation-cost`` also times one span with the profiler off and on.
The last line of stdout is one JSON object.
"""
from __future__ import annotations

import json
import statistics
import sys
import tempfile
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

PROGRAM_PREFIX = "echo."
PHASES = ("schedule", "swap", "prep", "launch", "wait", "fetch", "argmax",
          "commit", "emit", "threshold", "observe")


# ------------------------------------------------------------- readers
def queue_wait(rows: List["cell.IterRow"]) -> Optional[dict]:
    """``frontdoor.queue_wait_ms``: median over the online requests first
    admitted in ``rows`` of the wall time from front-door submit to the
    start of the admitting step, with its p95, the sample count and the
    medians of its two parts: submit to intake drain (the front door's
    queue) and intake to admission (the engine's)."""
    from cell import p95
    waits = [(a - s, i - s, a - i) for r in rows
             for s, i, a in r.online_admits]
    if not waits:
        return None
    total = [w[0] for w in waits]
    return {"median_ms": 1e3 * statistics.median(total),
            "p95_ms": 1e3 * p95(total), "n": len(waits),
            "intake_median_ms": 1e3 * statistics.median(w[1] for w in waits),
            "sched_median_ms": 1e3 * statistics.median(w[2] for w in waits)}


def host_ms_per_step(rows: List["cell.IterRow"]) -> Optional[float]:
    """``engine.host_ms_per_step``: mean over the steps of the step's wall
    time less the time it waited for the device."""
    if not rows:
        return None
    return 1e3 * sum(r.times.host for r in rows) / len(rows)


def syncs_per_step(rows: List["cell.IterRow"]) -> Optional[float]:
    """``runner.syncs_per_step``: mean blocking device-to-host fetches per
    step."""
    if not rows:
        return None
    return sum(r.times.n_syncs for r in rows) / len(rows)


def sample_ms_per_step(rows: List["cell.IterRow"]) -> Optional[float]:
    """``runner.sample_ms_per_step``: mean per step of the logits copy to
    the host and the host argmax."""
    if not rows:
        return None
    return 1e3 * sum(r.times.fetch + r.times.argmax for r in rows) / len(rows)


def phase_ms(rows: List["cell.IterRow"]) -> Dict[str, float]:
    """Mean ms per step of each phase, and of the step's wall time not
    under any phase (``other``)."""
    n = max(len(rows), 1)
    out = {k: 1e3 * sum(getattr(r.times, k) for r in rows) / n
           for k in PHASES}
    out["wall"] = 1e3 * sum(r.times.wall for r in rows) / n
    out["other"] = out["wall"] - sum(out[k] for k in PHASES
                                     if k != "observe")
    return out


def base_name(name: str) -> str:
    """A host event's name without the ``#key=value#`` metadata suffix a
    profiler may append."""
    return name.split("#", 1)[0]


def idle_by_span(idle: List[Tuple[str, float]]) -> Dict[str, float]:
    """Idle seconds by span name, suffixes merged."""
    out: Dict[str, float] = {}
    for name, s in idle:
        out[base_name(name)] = out.get(base_name(name), 0.0) + s
    return dict(sorted(out.items(), key=lambda x: -x[1]))


def program_share(idle: Dict[str, float]) -> Optional[float]:
    """Share of idle seconds under a program span below the step: every
    ``echo.*`` name but ``echo.step`` itself (whose self time, like
    ``bench.*`` self time and ``none``, says nothing of what the host
    did)."""
    total = sum(idle.values())
    if total <= 0:
        return None
    inner = sum(s for n, s in idle.items()
                if n.startswith(PROGRAM_PREFIX) and n != "echo.step")
    return inner / total


# ------------------------------------------------------------- the run
@contextmanager
def program_spans(cell, devtrace, top: int = 40):
    """For the length of one ``cell.run``: the counters listener and the
    step hook are kept, and the device trace keeps the program's spans
    (and ``top`` entries of each breakdown)."""
    made = {}
    saved = (cell.Counters, cell.StepHook, devtrace.HOST_PREFIX,
             devtrace.reduce)
    reduce = devtrace.reduce

    class Counters(saved[0]):
        def __init__(self, clock):
            super().__init__(clock)
            made["counters"] = self

    class StepHook(saved[1]):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made["hook"] = self

    cell.Counters, cell.StepHook = Counters, StepHook
    devtrace.HOST_PREFIX = (devtrace.HOST_PREFIX, PROGRAM_PREFIX)
    devtrace.reduce = lambda compact: reduce(compact, top=top)
    try:
        yield made
    finally:
        (cell.Counters, cell.StepHook, devtrace.HOST_PREFIX,
         devtrace.reduce) = saved
        made.clear()        # the hook holds the engine


def annotation_cost(n: int = 100_000) -> Dict[str, float]:
    """Microseconds per ``span`` (with two ids and an accumulator, as the
    runner opens them) with the profiler off, then on."""
    import jax
    from repro.core.engine import StepTimes
    from repro.obs.spans import span

    def per_call(k: int) -> float:
        acc = StepTimes()
        t0 = time.perf_counter()
        for _ in range(k):
            with span("echo.cost", acc, "wait", rows=8, rid=1):
                pass
        return 1e6 * (time.perf_counter() - t0) / k

    off = per_call(n)
    log_dir = tempfile.mkdtemp(prefix="chipbench_cost_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        on = per_call(n // 10)
    finally:
        jax.profiler.stop_trace()
    return {"off_us": off, "on_us": on}


def measure(workload: str, seed: int, seconds: float, trace: bool,
            **run_kw) -> dict:
    """One ``cell.run`` (``run_kw`` passed on) and the readings above."""
    import cell
    import devtrace
    with program_spans(cell, devtrace) as made:
        result = cell.run(workload, seed, seconds, trace, **run_kw)
        counters, hook = made["counters"], made["hook"]
    w0, w1 = cell.WARMUP_S, cell.WARMUP_S + seconds   # traffic.py's window
    rows = [r for r in counters.rows if w0 <= r.t_end < w1]
    out = {"steps": len(rows),
           "frontdoor.queue_wait_ms": queue_wait(rows),
           "engine.host_ms_per_step": host_ms_per_step(rows),
           "runner.syncs_per_step": syncs_per_step(rows),
           "runner.sample_ms_per_step": sample_ms_per_step(rows),
           "phase_ms": phase_ms(rows)}
    if trace and "breakdown" in result:
        a, b = hook.traced_rows
        traced = counters.rows[a:b]
        window = result["device"]["window_s"]
        idle = idle_by_span(result["breakdown"]["idle_gaps"])
        out.update({
            "idle_by_span": idle,
            "idle_program_share": program_share(idle),
            "traced_steps": len(traced),
            "traced_host_ms_per_step": host_ms_per_step(traced),
            "traced_phase_ms": phase_ms(traced),
            "traced_host_pct": (100.0 * sum(r.times.host for r in traced)
                                / window if window > 0 else None)})
    result.pop("extra", None)
    return {"result": result, "spans": out}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--annotation-cost", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(HERE.parents[1] / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = measure(args.workload, args.seed, args.seconds, bool(args.trace))
    out.update(workload=args.workload, seed=args.seed, trace=args.trace)
    if args.annotation_cost:
        out["annotation_cost"] = annotation_cost()
    line = json.dumps(out, default=float)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

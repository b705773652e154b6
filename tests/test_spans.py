"""The program's own spans and counters (``repro.obs.spans``): a reduced
model served through the front door under the JAX profiler. The runner's
spans nest inside the step's on the profiler's clock, the per-step counters
fit inside the step, syncs are counted where they happen, the request
stamps are ordered, and a wall-clock ``Tracer`` file keeps one clock."""
import asyncio
import glob
import os
from collections import defaultdict

import jax
import pytest

from repro.configs import get_config
from repro.core import ECHO, SLO
from repro.core.engine import EngineListener, StepTimes
from repro.launch.serve import build_engine
from repro.obs import MetricsRegistry, Tracer, span
from repro.obs.trace import RT_PID, TID_REQ_BASE
from repro.rt import AsyncEchoEngine

PHASES = ("schedule", "swap", "prep", "launch", "wait", "fetch", "argmax",
          "commit", "emit", "threshold")


class _Steps(EngineListener):
    """Each step's times, prefill chunks and whether it decoded."""

    def __init__(self):
        self.steps = []

    def on_iteration(self, rec, detail):
        self.steps.append((detail.times, len(detail.prefill_spans),
                           bool(detail.decodes)))


def _host_events(log_dir):
    """``echo.*`` host events of the one xplane under ``log_dir``, by
    host line: (name, start_ns, end_ns, stats)."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    lines = defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("echo."):
                    s = int(e.start_ns)
                    stats = {k: v for k, v in e.stats}
                    lines[(plane.name, line.name)].append(
                        (e.name, s, s + int(e.duration_ns), stats))
    return list(lines.values())


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("profile"))
    outside = StepTimes()
    with span("echo.test.outside", outside, "wait", rid=1):
        pass
    engine = build_engine(get_config("qwen3-4b").reduced(), ECHO,
                          num_blocks=64, clock="wall", chunk_size=16,
                          max_pages_per_seq=8, max_running=4)
    steps = _Steps()
    engine.listeners.append(steps)
    tracer = Tracer()
    doc = list(range(3, 35))

    async def main():
        rt = AsyncEchoEngine(engine)
        rt.instrument(MetricsRegistry(), tracer)
        async with rt:
            hs = [await rt.submit(doc + [40 + i, 41 + i], task_type="offline",
                                  max_new_tokens=4) for i in range(3)]
            hs += [await rt.submit(list(range(7, 27 + 3 * i)),
                                   task_type="online", max_new_tokens=5,
                                   slo=SLO(5.0, 5.0)) for i in range(3)]
            for h in hs:
                await h.result()
        return [h.request for h in hs]

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        reqs = asyncio.run(main())
    finally:
        jax.profiler.stop_trace()
    return dict(steps=steps.steps, reqs=reqs, tracer=tracer,
                lines=_host_events(log_dir), outside=outside)


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_runner_spans_nest_inside_the_step(served):
    found = defaultdict(int)
    for events in served["lines"]:
        steps = [e for e in events if e[0] == "echo.step"]
        runner = [e for e in events
                  if e[0] in ("echo.runner.decode", "echo.runner.prefill")]
        for e in runner:
            assert any(_inside(e, s) for s in steps), e
            key = "rows" if e[0] == "echo.runner.decode" else "rid"
            assert e[3][key] >= (1 if key == "rows" else 0), e
            found[e[0]] += 1
        for e in events:
            if e[0] == "echo.runner.wait":
                assert any(_inside(e, r) for r in runner), e
                found[e[0]] += 1
        for s in steps:
            assert "step_num" in s[3]
    assert found["echo.runner.decode"] and found["echo.runner.prefill"]
    assert found["echo.runner.wait"] == (found["echo.runner.decode"]
                                         + found["echo.runner.prefill"])


def test_decode_span_counts_live_pages(served):
    """``pages`` on a decode span is the live pages its rows read: at
    least one a row."""
    spans = [e for events in served["lines"] for e in events
             if e[0] == "echo.runner.decode"]
    assert spans
    for e in spans:
        assert e[3]["pages"] >= e[3]["rows"] >= 1, e


def test_every_boundary_has_its_span(served):
    names = {e[0] for events in served["lines"] for e in events}
    assert {"echo.rt.intake", "echo.rt.dispatch", "echo.sched",
            "echo.runner.prep", "echo.runner.launch", "echo.runner.fetch",
            "echo.kv.commit", "echo.argmax", "echo.emit",
            "echo.kv.threshold", "echo.observe"} <= names


def test_step_counters_fit_inside_the_step(served):
    assert served["steps"]
    for times, _, _ in served["steps"]:
        total = sum(getattr(times, k) for k in PHASES)
        assert all(getattr(times, k) >= 0 for k in PHASES)
        assert total <= times.wall + 1e-9
        assert times.t_start <= times.t_exec <= times.t_end
        assert 0 <= times.host <= times.wall
        assert times.observe > 0       # filled in after the listeners


def test_syncs_are_prefill_chunks_plus_one_decode_batch(served):
    for times, n_prefill, decoded in served["steps"]:
        assert times.n_syncs == n_prefill + int(decoded)
        assert times.n_launches == times.n_syncs
    assert any(n > 1 for _, n, _ in served["steps"]) or \
        any(n and d for _, n, d in served["steps"])


def test_request_stamps_are_ordered(served):
    for req in served["reqs"]:
        assert req.wall_submit <= req.wall_intake <= req.wall_admit


def test_front_door_span_encloses_the_engine_spans(served):
    """One clock in the file: each connection's span holds its request's
    queued, prefill and decode spans on the engine's tracks."""
    conn, engine = {}, defaultdict(list)
    for ph, name, t, dur, pid, tid, _, _ in served["tracer"]._events:
        if ph != "X":
            continue
        if pid == RT_PID:
            conn[tid - TID_REQ_BASE] = (t, t + dur)
        elif pid == 0 and tid >= TID_REQ_BASE:
            engine[tid - TID_REQ_BASE].append((name, t, t + dur))
    for req in served["reqs"]:
        lo, hi = conn[req.rid]
        kinds = {name.split(" ")[0] for name, _, _ in engine[req.rid]}
        assert {"queued", "prefill", "decode"} <= kinds, kinds
        for name, s, e in engine[req.rid]:
            assert lo <= s and e <= hi, (req.rid, name)


def test_span_records_nothing_outside_a_session(served):
    names = {e[0] for events in served["lines"] for e in events}
    assert "echo.test.outside" not in names
    assert served["outside"].wait >= 0
    with span("echo.test.bare"):
        pass

"""One run of one cell: build the engine, warm up, drive the cell's traffic
through Echo's front door for the measured window, read the metrics, and
check what the timed path served against the plain reference.

The served path is the program's own: ``repro.launch.serve.build_engine``
(policy ECHO, wall clock) wrapped in ``repro.rt.AsyncEchoEngine``; the
benchmark hands it weights of its own (``weights.py``). Online requests
are open-loop arrivals timed from their due time; the offline backlog is
submitted at time 0. Token times are the front door's delivery stamps, on
the benchmark's clock.
"""
from __future__ import annotations

import asyncio
import gc
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax

import common
import reference
import devtrace as tracing
import traffic as traffic_gen
import weights as weight_gen

from repro.configs.base import ModelConfig
from repro.core import ECHO, SLO
from repro.core.engine import EngineListener, StepTimes
from repro.launch.serve import build_engine
from repro.rt import AsyncEchoEngine
from repro.serving.handle import HandleStatus

WARMUP_S = 5.0            # cell traffic run inside set-up
GRACE_S = 20.0            # longest wait for the window's online requests
TRACE_S = 4.0             # device-traced span, in the window's middle
SAMPLE_MIN_TOKENS = 384   # served tokens compared with the reference
SAMPLE_MAX_REQUESTS = 8
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Clock:
    """Seconds since the start of the traffic (the front door's clock)."""

    def __init__(self):
        self.origin = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def reset(self) -> None:
        self.origin = time.perf_counter()


def model_config(cfg: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for a configuration file, from its
    family (``families/<family>.py``)."""
    return common.family(cfg).program_config(cfg)


# ------------------------------------------------------------- counters
@dataclass
class IterRow:
    t_end: float                        # benchmark clock at step end
    schedule_wall: float
    compute_time: float
    predicted_time: float
    prefill: List[Tuple[int, int]]      # (start position, tokens)
    decode_ctx: List[int]               # context length of each decode row
    recomputed: int                     # tokens re-prefilled after preemption
    offline_first: List[Tuple[int, int]]  # (cached, prompt) tokens of each
    #                                       offline request's first chunk
    times: Optional[StepTimes] = None   # the step's ``IterationDetail.times``
    online_admits: List[Tuple[float, float, float]] = field(
        default_factory=list)           # (wall_submit, wall_intake,
    #   wall_admit) perf_counter stamps of the online requests this step
    #   admits for the first time


class Counters(EngineListener):
    """Per-step host counters from the engine's iteration hook."""

    def __init__(self, clock: Clock):
        self.clock = clock
        self.rows: List[IterRow] = []
        self._recomputed: Dict[int, int] = {}   # every request prefilled

    def on_iteration(self, rec, detail) -> None:
        rec_tokens, first = 0, []
        for r, s, _ in detail.prefill_spans:
            if r.rid not in self._recomputed and not r.is_online:
                # a first admission: its chunk starts past the cached prefix
                first.append((s, r.prompt_len))
            rec_tokens += r.recomputed_tokens - self._recomputed.get(r.rid, 0)
            self._recomputed[r.rid] = r.recomputed_tokens
        t0 = detail.times.t_start
        admits = [(r.wall_submit, r.wall_intake, r.wall_admit)
                  for r in detail.admitted
                  if r.is_online and r.wall_admit == t0
                  and r.wall_submit is not None]
        self.rows.append(IterRow(
            self.clock.now(), detail.schedule_wall, detail.compute_time,
            detail.predicted_time,
            [(s, e - s) for _, s, e in detail.prefill_spans],
            [r.total_len for r in detail.decodes], rec_tokens, first,
            detail.times, admits))


class StepHook:
    """Wraps ``engine.step``: in a traced run, starts and stops the
    profiler between steps, when the device is idle."""

    def __init__(self, engine, clock: Clock,
                 trace_span: Optional[Tuple[float, float]],
                 trace_dir: Optional[str]):
        self.clock = clock
        self.trace_span, self.trace_dir = trace_span, trace_dir
        self.traced_rows: Tuple[int, int] = (0, 0)
        self.tracing = False
        self.trace_done = False
        self._step = engine.step
        self.counters: Optional[Counters] = None

    def __call__(self):
        t = self.clock.now()
        if self.trace_span is not None:
            if not self.tracing and not self.trace_done \
                    and t >= self.trace_span[0]:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self.tracing = True
                self.traced_rows = (len(self.counters.rows), 0)
            elif self.tracing and t >= self.trace_span[1]:
                self.stop_trace()
        return self._step()

    def stop_trace(self) -> None:
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing, self.trace_done = False, True
            self.traced_rows = (self.traced_rows[0], len(self.counters.rows))


def annotate(obj, attr: str, name: str) -> None:
    """Wrap ``obj.attr`` in a profiler span named ``name``."""
    fn = getattr(obj, attr)

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)

    setattr(obj, attr, wrapped)


# ------------------------------------------------------------- client
@dataclass
class Client:
    online: bool
    due: float
    prompt: List[int]
    max_new: int
    handle: object = None
    sent: float = math.nan
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    shed: bool = False        # ended unfinished before the run stopped it

    @property
    def finished(self) -> bool:
        return len(self.tokens) == self.max_new

    @property
    def failed(self) -> bool:
        """Shed or aborted by the system, or never answered."""
        return self.shed or not self.times

    def tpot(self) -> float:
        """Mean time per output token after the first, so far."""
        n = len(self.times)
        return (self.times[-1] - self.times[0]) / (n - 1) if n > 1 else 0.0


def mark_shed(clients: List[Client]) -> None:
    """Before the run stops the engine (which sheds what is live): note
    the requests the system itself ended unfinished."""
    for c in clients:
        c.shed = c.handle.done and c.handle.status != HandleStatus.FINISHED


async def _consume(c: Client) -> None:
    async for ev in c.handle.tokens():
        c.times.append(ev.t_wall)
        c.tokens.append(ev.token)


async def drive(rt, tr: traffic_gen.Traffic, clock: Clock, slo: SLO,
                grace: float, window_opens) -> List[Client]:
    """Submit the backlog at time 0 and the online schedule on time; call
    ``window_opens`` as the window opens; after it, wait (at most
    ``grace``) for every online request due in it, then shed what is
    left."""
    await rt.start()
    clock.reset()
    clients: List[Client] = []
    tasks = []
    for r in tr.offline:
        c = Client(False, 0.0, r.prompt, r.max_new)
        c.handle = await rt.submit(r.prompt, task_type="offline",
                                   max_new_tokens=r.max_new)
        c.sent = clock.now()
        clients.append(c)
        tasks.append(asyncio.create_task(_consume(c)))
    w0, w1 = tr.window
    due_in_window: List[Client] = []

    async def arrivals():
        for r in tr.online:
            delay = r.due - clock.now()
            if delay > 0:
                await asyncio.sleep(delay)
            c = Client(True, r.due, r.prompt, r.max_new)
            c.handle = await rt.submit(r.prompt, task_type="online",
                                       max_new_tokens=r.max_new, slo=slo)
            c.sent = clock.now()
            clients.append(c)
            if w0 <= r.due < w1:
                due_in_window.append(c)
            tasks.append(asyncio.create_task(_consume(c)))

    gen = asyncio.create_task(arrivals())
    await asyncio.sleep(max(w0 - clock.now(), 0.0))
    window_opens()
    await asyncio.sleep(max(w1 - clock.now(), 0.0))
    deadline = w1 + grace
    while clock.now() < deadline and not all(c.handle.done
                                             for c in due_in_window):
        await asyncio.sleep(0.05)
    mark_shed(clients)
    gen.cancel()
    try:
        await gen
    except asyncio.CancelledError:
        pass
    await rt.stop()
    await asyncio.gather(*tasks)
    return clients


# ------------------------------------------------------------- metrics
def p95(values: List[float]) -> float:
    """Nearest-rank 95th percentile (``inf`` entries allowed)."""
    v = sorted(values)
    return v[max(math.ceil(0.95 * len(v)) - 1, 0)]


def itl_gaps(clients: List[Client], window: Tuple[float, float]
             ) -> Tuple[List[float], int]:
    """Inter-token gaps of online requests in the window, failures
    counted: every gap between consecutive tokens whose later token lands
    in the window; for a request still waiting for its next token at the
    window's close, the gap open then; for one due before the close that
    failed (shed or aborted by the system, or never answered), an infinite
    gap for every token it did not deliver. Returns the gaps and how many
    were open at the close."""
    w0, w1 = window
    gaps, n_open = [], 0
    for c in clients:
        if not c.online or c.due >= w1:
            continue
        gaps += [b - a for a, b in zip(c.times, c.times[1:]) if w0 <= b < w1]
        if c.failed:
            gaps += [math.inf] * (c.max_new - max(len(c.times), 1))
            continue
        sent = [t for t in c.times if t < w1]
        if sent and len(sent) < c.max_new:
            gaps.append(w1 - sent[-1])
            n_open += 1
    return gaps, n_open


def end_to_end(clients: List[Client], window: Tuple[float, float],
               slo: SLO) -> Tuple[Dict[str, float], dict]:
    """The end-to-end readings of one run, and what the run did.

    Online requests due in the window: TTFT from the due time (a failed
    request's is infinite); the SLO share meets both limits and did not
    fail (one still streaming when the run stops is judged by the tokens
    it had). Offline tokens credited in the window: a request's prompt
    tokens, cached ones included, when its first token arrives, and each
    token after (Echo's offline throughput); ``offline_out_tok_s`` counts
    only the tokens delivered."""
    w0, w1 = window
    online = [c for c in clients if c.online and w0 <= c.due < w1]
    ttft, ok = [], 0
    for c in online:
        t = math.inf if c.failed else c.times[0] - c.due
        ttft.append(t)
        ok += t <= slo.ttft and c.tpot() <= slo.tpot
    gaps, n_open = itl_gaps(clients, window)
    credit = out = 0
    for c in clients:
        if c.online:
            continue
        for i, t in enumerate(c.times):
            if w0 <= t < w1:
                credit += len(c.prompt) + 1 if i == 0 else 1
                out += 1
    late = sorted(c.sent - c.due for c in clients if c.online)
    metrics = {
        "offline_tok_s": credit / (w1 - w0),
        "offline_out_tok_s": out / (w1 - w0),
        "online_ttft_p95_ms": p95(ttft) * 1e3 if ttft else math.nan,
        "online_itl_p95_ms": p95(gaps) * 1e3 if gaps else math.nan,
        "online_slo_pct": 100.0 * ok / len(online) if online else math.nan,
    }
    info = {"online_due": len(online),
            "online_failed": sum(c.failed for c in online),
            "online_unfinished": sum(not c.finished and not c.failed
                                     for c in online),
            "itl_samples": len(gaps),
            "itl_open_at_close": n_open,
            "generator_late_p99_ms": (late[int(0.99 * (len(late) - 1))] * 1e3
                                      if late else math.nan),
            "offline_untouched": sum(1 for c in clients
                                     if not c.online and not c.times)}
    return metrics, info


@dataclass
class ReadContext:
    """What a per-layer reader reads: the configuration file, the chip's
    peaks, the window's host counters, (traced runs) the device trace
    with the host counters of the traced steps, and the run's own
    end-to-end readings (``end_to_end``), for a cell that reports one of
    them per layer."""
    dims: dict
    peak: dict
    rows: List[IterRow]
    trace: Optional[tracing.TraceSummary] = None
    traced_rows: List[IterRow] = field(default_factory=list)
    e2e: Dict[str, float] = field(default_factory=dict)


# ------------------------------------------------------------- run
def warm_up(runner, max_running: int) -> None:
    """Compile the prefill step and every decode bucket by calling them
    with rows that write nothing (no tokens; position -1), and the logits
    slice of every batch size."""
    jax.block_until_ready(runner.prefill_chunk([], 0, []))
    for b in range(1, max_running + 1):
        runner.decode([0] * b, [[0]] * b, [-1] * b)


def devices(require_tpu: bool, chips: int, name: str):
    """The devices JAX finds; exits when there is no TPU or too few."""
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0] is {dev.platform} "
                         f"({dev.device_kind}), {len(devs)} device(s)")
    if len(devs) < chips:
        raise SystemExit(f"{name} needs {chips} chips, found {len(devs)}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    return devs


def serve_engine(cfg: dict, seed: int, fault=None):
    """Echo's engine for ``cfg`` with the benchmark's weights from
    ``seed``, every step shape warmed. Returns the engine and the
    weights."""
    eng_cfg = cfg["engine"]
    engine = build_engine(model_config(cfg), ECHO,
                          num_blocks=eng_cfg["num_pages"], seed=seed,
                          block_size=eng_cfg["page_size"],
                          chunk_size=eng_cfg["chunk_size"],
                          max_pages_per_seq=eng_cfg["max_pages_per_seq"],
                          max_running=eng_cfg["max_running"],
                          max_batch_tokens=eng_cfg["max_batch_tokens"],
                          clock="wall")
    runner = engine.runner
    specs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         runner.params)
    runner.params = None
    gc.collect()
    weights = weight_gen.make_weights(specs, seed, common.family(cfg))
    runner.params = weights
    if fault is not None:
        fault(engine)
    warm_up(runner, eng_cfg["max_running"])
    return engine, weights


def slo_of(cfg: dict) -> SLO:
    return SLO(ttft=cfg["slo"]["ttft_s"], tpot=cfg["slo"]["tpot_s"])


def verdict(gaps: List[float], n_tok: int, backlog_left: int,
            limit: float) -> Tuple[bool, dict]:
    """``correct`` and the numbers it compared, each beside its limit: the
    widest logit gap under the limit, some tokens compared, and the
    offline backlog not run dry."""
    gap = max(gaps) if gaps else math.inf
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "compared_tokens": {"value": n_tok, "limit": 1},
              "backlog_left": {"value": backlog_left, "limit": 1}}
    return bool(gap <= limit and n_tok >= 1 and backlog_left >= 1), checks


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        cfg: Optional[dict] = None, mix: Optional[dict] = None,
        t_process: Optional[float] = None, require_tpu: bool = True,
        control: bool = False, compare: bool = True, fault=None) -> dict:
    """One run. Returns the result line's dict (``correct`` false when the
    comparison fails); raises when the run cannot be made. With
    ``control``, the fp8 control's own argmax is read at the same
    positions and judged by the same ``verdict`` in the program's place
    (``extra.control_correct``)."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = (common.find_cell(cell_name) if cfg is None or mix is None
            else {"config": cfg["name"], "traffic": "given", "chips": 1})
    devs = devices(require_tpu, cell["chips"], f"cell {cell_name}")
    dev = devs[0]
    cfg = cfg or common.load_config(cell["config"])
    mix = mix or common.load_mix(cell["traffic"])
    peak = common.peaks(dev.device_kind) if require_tpu else None
    slo = slo_of(cfg)

    engine, weights = serve_engine(cfg, seed, fault)
    runner = engine.runner
    log(f"engine built and warmed: {time.perf_counter() - t_process:.1f}s "
        f"after start")

    tr = traffic_gen.build(mix, cfg["knee_rps"], cfg["vocab_size"], seed,
                           warmup=WARMUP_S, seconds=seconds, tail=GRACE_S)
    clock = Clock()
    counters = Counters(clock)
    engine.listeners.append(counters)
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    mid = WARMUP_S + seconds / 2
    span = (mid - TRACE_S / 2, mid + TRACE_S / 2) if trace else None
    rt = AsyncEchoEngine(engine, clock=clock, token_queue_cap=0)
    if trace:
        annotate(engine, "step", "bench.step")
        annotate(engine.scheduler, "schedule", "bench.schedule")
        annotate(runner, "prefill_chunk", "bench.prefill")
        annotate(runner, "decode", "bench.decode")
        annotate(engine.bm, "commit", "bench.commit")
        annotate(rt, "_step_hop", "bench.hop")
    # the profiler starts and stops outside the traced step's span
    hook = StepHook(engine, clock, span, trace_dir)
    hook.counters = counters
    engine.step = hook

    compiles: List[Tuple[float, float]] = []

    def on_compile(event, secs, **kw):
        if event == COMPILE_EVENT:
            compiles.append((clock.now(), secs))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    setup_box = {}

    def window_opens():
        setup_box["setup_s"] = time.perf_counter() - t_process

    try:
        clients = asyncio.run(drive(rt, tr, clock, slo, GRACE_S,
                                    window_opens))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        hook.stop_trace()
    w0, w1 = tr.window
    in_window = [s for t, s in compiles if w0 <= t < w1]
    log(f"compiles: {len(compiles)} after the traffic started, "
        f"{len(in_window)} inside the window ({sum(in_window):.2f}s)")
    metrics, info = end_to_end(clients, tr.window, slo)
    metrics["setup_s"] = setup_box["setup_s"]
    for k, v in {**metrics, **info}.items():
        log(f"{k}: {v}")
    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    log(f"peak_bytes_in_use {peak_bytes} of {stats.get('bytes_limit')}")

    # ---- per-layer reads (host counters of the window, device trace)
    rows = [r for r in counters.rows if w0 <= r.t_end < w1]
    summary = None
    traced_rows = counters.rows[hook.traced_rows[0]:hook.traced_rows[1]]
    if trace:
        compact = tracing.load(tracing.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        summary = tracing.reduce(compact)
        del compact
    ctx = ReadContext(cfg, peak, rows, summary, traced_rows, metrics)

    # ---- free the program's state, then compare with the reference
    finished = [(i, c.prompt, c.tokens) for i, c in enumerate(clients)
                if c.finished]
    backlog_left = info["offline_untouched"]
    for c in clients:
        c.handle = None                 # a handle holds the engine
    del rt, engine, runner, hook, counters
    gc.collect()
    t_ref = time.perf_counter()
    view = reference.view(weights, cfg)
    pick = (reference.sample(finished, seed, min_tokens=SAMPLE_MIN_TOKENS,
                             max_requests=SAMPLE_MAX_REQUESTS)
            if compare else [])
    gaps, ctrl_gaps, n_tok = [], [], 0
    for i in pick:
        _, prompt, served = finished[i]
        g, cg = reference.request_gaps(view, cfg, prompt, served,
                                       control=control)
        gaps.append(float(g.max()))
        n_tok += len(served)
        if control:
            ctrl_gaps.append(float(cg.max()))
    log(f"reference: {len(pick)} requests, {n_tok} served tokens, "
        f"{time.perf_counter() - t_ref:.1f}s")
    limit = cfg["correct"]["logit_gap_limit"]
    correct, checks = verdict(gaps, n_tok, backlog_left, limit)
    result = {"correct": correct,
              "attempted": info["online_due"],
              "failed": info["online_failed"],
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs),
                         "memory_peak_bytes": peak_bytes}}
    units = {m["name"]: m["unit"] for kind in ("end_to_end", "per_layer")
             for m in common.benchmark_spec()[kind]}
    if not trace:
        for name in common.metrics_of(cell_name, "end_to_end"):
            result["metrics"][name] = {"value": metrics[name],
                                       "unit": units[name]}
    else:
        for name in common.metrics_of(cell_name, "per_layer"):
            v = common.metric_reader(name)(ctx)
            if v is not None:
                result["metrics"][name] = {"value": v, "unit": units[name]}
        if summary is not None:
            result["device"]["busy_s"] = summary.busy_s
            result["device"]["window_s"] = summary.window_s
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in summary.top_ops],
                "idle_gaps": [[n, s] for n, s in summary.idle_by_host]}
    result["extra"] = {"gaps": gaps, "control_gaps": ctrl_gaps,
                       "e2e": metrics, "info": info}
    if control:
        result["extra"]["control_correct"], result["extra"][
            "control_checks"] = verdict(ctrl_gaps, n_tok, backlog_left, limit)
    result["checks"] = checks
    return result

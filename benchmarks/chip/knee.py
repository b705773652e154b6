#!/usr/bin/env python3
"""Sweep of online-only load for one configuration, to find its knee.

    python benchmarks/chip/knee.py --config qwen3-4b --mix docqa-chat \\
        --rates 0.15,0.2,0.25 --start 0.2 --requests 100 --settle 20

One process builds the engine once and offers online-only traffic (steady
Poisson, the mix's chat lengths, no offline work) at one rate after
another. At each rate the ``--requests`` requests that arrive after its
first ``--settle`` seconds are judged: a request meets the limits when
its first token comes within the TTFT limit of its due time and its mean
time per output token is within the TPOT limit; one shed or never
answered misses. A rate holds when at least 90% meet both, and misses as
soon as more than a tenth of its judged requests have missed. The knee is
the highest rate that holds.

The sweep starts at ``--start``. After a rate has offered all its
requests it goes on to the next higher rate at once, while the last ones
are still judged; once some rate misses, the requests in flight above the
lowest rate that missed are aborted, and the next lower rate not yet run,
if any, is offered on the emptied engine. It stops when a rate that holds
lies next to one that misses, or at the end of the list. One JSON line per
rate on stdout, then one with the knee.
"""
import asyncio
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

POLL_S = 0.25
JUDGE_CAP_S = 240.0     # longest wait for a rate's last judged requests


@dataclass
class Phase:
    rate: float
    opened: float
    clients: list = field(default_factory=list)
    judged: list = field(default_factory=list)
    offered: bool = False
    verdict: Optional[bool] = None      # None: not decided (yet)
    aborted: bool = False


def missed(c, now: float, slo) -> bool:
    """Whether judged request ``c`` has missed, as far as can be told."""
    from repro.serving.handle import HandleStatus
    if c.handle.done and c.handle.status != HandleStatus.FINISHED:
        return True
    if not c.times:
        return now - c.due > slo.ttft
    return c.times[0] - c.due > slo.ttft or (c.finished
                                             and c.tpot() > slo.tpot)


def judge(phases: Dict[int, Phase], now: float, slo, n: int) -> None:
    for ph in phases.values():
        if ph.verdict is not None or ph.aborted:
            continue
        if sum(missed(c, now, slo) for c in ph.judged) > 0.1 * n:
            ph.verdict = False
        elif ph.offered and all(c.handle.done for c in ph.judged):
            ph.verdict = True


def report(config: str, ph: Phase, slo) -> dict:
    import cell
    ttft = [c.times[0] - c.due if c.times else math.inf for c in ph.judged]
    tpot = [c.tpot() for c in ph.judged if len(c.times) > 1]
    met = sum(not missed(c, math.inf, slo) and c.finished
              for c in ph.judged)
    return {"config": config, "rate_rps": ph.rate,
            "verdict": ("aborted" if ph.aborted else
                        {None: "undecided", True: "holds",
                         False: "misses"}[ph.verdict]),
            "judged": len(ph.judged), "met": met,
            "share_pct": 100.0 * met / len(ph.judged) if ph.judged else None,
            "ttft_p95_ms": cell.p95(ttft) * 1e3 if ttft else None,
            "tpot_p95_ms": cell.p95(tpot) * 1e3 if tpot else None,
            "opened_s": ph.opened}


async def sweep(rt, clock, slo, mix, vocab, seed, rates, start, n, settle):
    import cell
    from cell import Client, _consume
    await rt.start()
    clock.reset()
    phases: Dict[int, Phase] = {}
    tasks = []

    def failed() -> List[int]:
        judge(phases, clock.now(), slo, n)
        return [j for j, ph in phases.items() if ph.verdict is False]

    async def offer(i: int) -> bool:
        """Offer rate ``i``'s requests on time; False once a rate misses
        that had not missed before."""
        known = set(failed())
        t0 = clock.now()
        ph = phases[i] = Phase(rates[i], t0)
        reqs = cell.traffic_gen.steady_online(
            mix, rates[i], t0, t0 + settle + n / rates[i], vocab, seed, i)
        for r in reqs:
            while r.due - clock.now() > 0:
                await asyncio.sleep(min(r.due - clock.now(), POLL_S))
                if set(failed()) - known:
                    return False
            c = Client(True, r.due, r.prompt, r.max_new)
            c.handle = await rt.submit(r.prompt, task_type="online",
                                       max_new_tokens=r.max_new, slo=slo)
            c.sent = clock.now()
            ph.clients.append(c)
            if r.due >= t0 + settle:
                ph.judged.append(c)
            tasks.append(asyncio.create_task(_consume(c)))
        ph.offered = True
        return not set(failed()) - known

    async def settle_verdicts() -> None:
        t_end = clock.now() + JUDGE_CAP_S
        while clock.now() < t_end and any(
                ph.verdict is None and not ph.aborted
                for ph in phases.values()):
            await asyncio.sleep(POLL_S)
            judge(phases, clock.now(), slo, n)

    i: Optional[int] = rates.index(start)
    while i is not None:
        ok = await offer(i)
        higher = i + 1
        if ok and higher < len(rates) and higher not in phases:
            i = higher
            continue
        if ok:
            await settle_verdicts()
        bad = failed()
        if not bad:
            i = None
            continue
        low = min(bad)
        for j, ph in phases.items():
            if j > low and ph.verdict is None:
                ph.aborted = True
            if j >= low:
                for c in ph.clients:
                    if not c.handle.done:
                        await c.handle.abort()
        if low - 1 >= 0 and low - 1 not in phases:
            i = low - 1
        else:
            await settle_verdicts()
            i = None
    await rt.stop()
    await asyncio.gather(*tasks)
    return phases


def main(argv=None) -> int:
    import argparse
    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--rates", required=True,
                    help="ascending requests/s, comma-separated")
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--settle", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(HERE.parents[1] / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import common
    import cell
    from repro.rt import AsyncEchoEngine
    rates = [float(r) for r in args.rates.split(",")]
    assert rates == sorted(rates) and args.start in rates
    cell.devices(True, 1, f"config {args.config}")
    cfg = common.load_config(args.config)
    slo = cell.slo_of(cfg)
    engine, _ = cell.serve_engine(cfg, args.seed)
    cell.log(f"engine built and warmed: {time.perf_counter() - t_process:.1f}s")
    clock = cell.Clock()
    rt = AsyncEchoEngine(engine, clock=clock, token_queue_cap=0)
    phases = asyncio.run(sweep(rt, clock, slo, common.load_mix(args.mix),
                               cfg["vocab_size"], args.seed, rates,
                               args.start, args.requests, args.settle))
    holds = []
    for i in sorted(phases):
        line = report(args.config, phases[i], slo)
        print(json.dumps(line), flush=True)
        if line["verdict"] == "holds":
            holds.append(rates[i])
    print(json.dumps({"config": args.config,
                      "knee_rps": max(holds) if holds else None,
                      "seconds": time.perf_counter() - t_process}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

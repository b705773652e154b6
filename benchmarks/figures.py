"""Paper-figure benchmarks (Fig. 6-11), driven by the §5.4 simulator over
the shared scenario. Each returns a list of CSV rows
(name, us_per_call, derived)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.scenario import build_engine
from repro.core import ALL_POLICIES, BS, ECHO
from repro.core.engine import EngineListener
from repro.core.estimator import RatePredictor
from repro.data import BurstyTrace


def _run(policy, seed=0, **kw):
    eng, online, offline, p = build_engine(policy, seed=seed, **kw)
    t0 = time.perf_counter()
    stats = eng.run(max_iters=200_000, until_time=p["duration"])
    wall = time.perf_counter() - t0
    return eng, stats, wall, p


# workload variants mirroring the paper's Fig.6 bars
FIG6_VARIANTS = {
    # CPU-scale LooGLE QA-Short-like (fast; shares the Fig.7-10 scenario)
    "loogle_short": dict(),
    # ShareGPT-like offline: no prefix sharing (questions_per_doc=1)
    "sharegpt": dict(n_docs=240, questions=1, doc_len=96, question_len=32,
                     offline_new=24),
    # paper-scale LooGLE: 8k-token docs, A100-40G-sized cache (9.5k blocks),
    # A100-magnitude coefficients
    "loogle_paper": dict(
        n_docs=18, questions=22, doc_len=8192, question_len=128,
        offline_new=32, num_blocks=9500, block_size=16, chunk_size=512,
        duration=120.0, online_rate=1.0, burst_rate=6.0, online_prompt=308,
        online_new=64, max_running=64,
        tm_kw=dict(alpha=1e-8, beta=2e-5, gamma=3e-6, delta=3e-6)),
}


def fig6_throughput_speedup():
    """Offline task throughput speedup over BS (paper Fig. 6; up to 3.3x)."""
    rows = []
    for variant, kw in FIG6_VARIANTS.items():
        tput = {}
        for pol in ALL_POLICIES:
            eng, stats, wall, _ = _run(pol, **kw)
            tput[pol.name] = stats.offline_throughput()
            rows.append((f"fig6.{variant}.tput.{pol.name}",
                         wall * 1e6 / max(len(stats.iterations), 1),
                         f"{tput[pol.name]:.1f}tok/s"))
        base = max(tput["BS"], 1e-9)
        for pol in ALL_POLICIES:
            rows.append((f"fig6.{variant}.speedup.{pol.name}", 0.0,
                         f"{tput[pol.name] / base:.3f}x"))
    return rows


def fig7_slo():
    """TTFT / TPOT attainment per policy (paper Fig. 7)."""
    rows = []
    for pol in ALL_POLICIES:
        eng, stats, wall, _ = _run(pol)
        on = [r for r in stats.finished if r.is_online and r.ttft() is not None]
        ttfts = sorted(r.ttft() for r in on)
        p99 = ttfts[int(0.99 * (len(ttfts) - 1))] if ttfts else float("nan")
        rows.append((f"fig7.{pol.name}.ttft_attain", 0.0,
                     f"{stats.slo_attainment('ttft'):.3f}"))
        rows.append((f"fig7.{pol.name}.tpot_attain", 0.0,
                     f"{stats.slo_attainment('tpot'):.3f}"))
        rows.append((f"fig7.{pol.name}.ttft_p99", 0.0, f"{p99:.3f}s"))
    return rows


def fig8_interplay():
    """Active online vs offline requests move in opposition (paper Fig. 8)."""
    eng, stats, wall, _ = _run(ECHO)
    on = np.array([r.n_online for r in stats.iterations], float)
    off = np.array([r.n_offline for r in stats.iterations], float)
    if len(on) > 4 and on.std() > 0 and off.std() > 0:
        corr = float(np.corrcoef(on, off)[0, 1])
    else:
        corr = float("nan")
    return [("fig8.online_offline_corr", 0.0, f"{corr:.3f}"),
            ("fig8.mean_active_online", 0.0, f"{on.mean():.2f}"),
            ("fig8.mean_active_offline", 0.0, f"{off.mean():.2f}")]


def fig9_hit_rate():
    """Offline prefix-cache hit ratio under online bursts (paper Fig. 9:
    Echo keeps it high & stable; LRU flushes it)."""
    rows = []
    for pol in ALL_POLICIES:
        eng, stats, wall, _ = _run(pol)
        rows.append((f"fig9.{pol.name}.offline_hit", 0.0,
                     f"{eng.bm.metrics.offline_hit_rate:.3f}"))
        rows.append((f"fig9.{pol.name}.punished_tokens", 0.0,
                     str(eng.bm.metrics.punished_tokens)))
    return rows


class _UsageRecorder(EngineListener):
    """The block pool's occupancy breakdown after every step."""

    def __init__(self, bm):
        self.bm = bm
        self.usages = []

    def on_iteration(self, rec, detail):
        self.usages.append(self.bm.usage_breakdown())


def fig10_memory():
    """Memory occupancy breakdown (paper Fig. 10)."""
    eng, _, _, p = build_engine(ECHO, seed=0)
    rec = _UsageRecorder(eng.bm)
    eng.listeners.append(rec)
    eng.run(max_iters=200_000, until_time=p["duration"])
    usages = rec.usages
    keys = ("running_online", "running_offline", "free_online",
            "free_offline", "unused")
    total = eng.bm.num_blocks
    rows = []
    for k in keys:
        frac = np.mean([u[k] for u in usages]) / total
        rows.append((f"fig10.mean_frac.{k}", 0.0, f"{frac:.3f}"))
    occupied = np.mean([u["running_online"] + u["running_offline"]
                        for u in usages]) / total
    rows.append(("fig10.mean_occupied", 0.0, f"{occupied:.3f}"))
    return rows


def fig11_trace_prediction():
    """mu+sigma sliding-window arrival-rate prediction vs actual (Fig. 11)."""
    trace = BurstyTrace(base_rate=4.0, tidal_period=1200.0, burst_rate=6.0,
                        burst_len=10.0, burst_prob=0.03, seed=7)
    arrivals = trace.sample(0, 1200)
    rp = RatePredictor(window=300.0)
    errs, preds = [], []
    ai = 0
    for t in np.arange(60, 1200, 30.0):
        while ai < len(arrivals) and arrivals[ai] <= t:
            rp.observe(arrivals[ai])
            ai += 1
        pred = rp.predict_rate(t)
        actual = sum(1 for a in arrivals if t <= a < t + 30.0) / 30.0
        preds.append(pred)
        errs.append(pred - actual)
    cover = np.mean([e >= 0 for e in errs])     # prediction should over-cover
    mae = float(np.mean(np.abs(errs)))
    return [("fig11.pred_mae_req_s", 0.0, f"{mae:.3f}"),
            ("fig11.over_coverage", 0.0, f"{cover:.3f}"),
            ("fig11.mean_pred", 0.0, f"{np.mean(preds):.3f}")]

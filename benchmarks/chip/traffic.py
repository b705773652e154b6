"""The one traffic generator: reads a mix file's parameters, returns the
online arrival schedule and the offline backlog of one run.

Open loop. Time 0 is the start of the warm-up traffic; the measured
window is ``[warmup, warmup + seconds)`` and the tail after it keeps the
load on while the window's last requests finish. Online arrivals follow a
piecewise-constant rate: ``rate_share`` times the configuration's knee,
raised to ``bursts.rate_share`` times the knee for ``bursts.seconds``
every ``bursts.every`` seconds, the first burst opening the window.

Every seed gets the same work: each segment (warm-up, window, tail) holds
a fixed number of arrivals, the integral of the rate over it; their gaps
are the quantiles of an exponential and their lengths the quantiles of
the mix's distributions, in one shuffled order; the offline backlog is
submitted in one shuffled order. The seed draws the token ids (and, in
the harness, the weights). A window holds some 14-28 online requests and
a few dozen offline ones, so an order drawn from the seed moved the
metrics far more than the run-to-run noise of one seed did (offline
tokens/s 274-657 over six seeds, within 12% on each seed): the order is
part of the workload, not of the seed.

Offline kinds:

- ``shared_docs``: documents asked several questions each, every prompt
  the whole document followed by its question (document QA);
- ``unique``: distinct prompts that share nothing (data generation).

The backlog is submitted at time 0, shuffled, as a batch API submits it.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional, Tuple

import numpy as np

# independent random streams of one seed
_ORDER, _GAPS, _ONLINE_LEN, _ONLINE_TOK, _OFFLINE = range(5)
_SHAPE = 0        # the seed of the streams that shape the work, on every seed


@dataclass
class OnlineRequest:
    due: float                    # seconds after the start of the traffic
    prompt: List[int]
    max_new: int


@dataclass
class OfflineRequest:
    prompt: List[int]
    max_new: int
    doc: Optional[int] = None     # shared document, for ``shared_docs``


@dataclass
class Traffic:
    online: List[OnlineRequest]
    offline: List[OfflineRequest]
    window: Tuple[float, float]   # measured span, traffic time


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths at the quantiles ``(i + 0.5) / n`` of the
    distribution ``spec``, clipped to ``[min, max]``, in an order drawn
    from ``rng``."""
    if n <= 0:
        return np.zeros(0, np.int64)
    q = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = lo + np.floor(q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    v = np.clip(np.rint(v), lo, hi).astype(np.int64)
    return rng.permutation(v)


def _breaks(online: dict, knee: float, t0: float, t1: float, warmup: float):
    """Breakpoints of the rate on ``[t0, t1)`` and the rate after each."""
    base = online["rate_share"] * knee
    bursts = online.get("bursts")
    pts = {t0, t1}
    if bursts:
        every, dur = bursts["every"], bursts["seconds"]
        k0 = int(np.floor((t0 - warmup) / every)) - 1
        k1 = int(np.ceil((t1 - warmup) / every)) + 1
        for k in range(k0, k1 + 1):
            s = warmup + k * every
            for t in (s, s + dur):
                if t0 < t < t1:
                    pts.add(t)
    ts = sorted(pts)

    def rate(t):
        if bursts:
            x = (t - warmup) % bursts["every"]
            if x < bursts["seconds"]:
                return bursts["rate_share"] * knee
        return base

    return np.array(ts), np.array([rate((a + b) / 2)
                                   for a, b in zip(ts, ts[1:])])


def _arrivals(online: dict, knee: float, t0: float, t1: float, warmup: float,
              rng: np.random.Generator) -> np.ndarray:
    ts, rates = _breaks(online, knee, t0, t1, warmup)
    cum = np.concatenate([[0.0], np.cumsum(rates * np.diff(ts))])
    total = cum[-1]
    n = int(np.floor(round(total, 6) + 0.5))     # no float noise at .5
    if n == 0:
        return np.zeros(0)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    pts = (np.cumsum(gaps) - gaps / 2) / gaps.sum() * total
    return np.interp(pts, cum, ts)


def online_schedule(mix: dict, knee: float, vocab: int, seed: int,
                    warmup: float, seconds: float,
                    tail: float) -> List[OnlineRequest]:
    online = mix["online"]
    segs = [(0.0, warmup), (warmup, warmup + seconds),
            (warmup + seconds, warmup + seconds + tail)]
    gap_rng, order_rng = _rng(_SHAPE, _GAPS), _rng(_SHAPE, _ORDER)
    tok_rng, pair_rng = _rng(seed, _ONLINE_TOK), _rng(_SHAPE, _ONLINE_LEN)
    out = []
    for t0, t1 in segs:
        due = _arrivals(online, knee, t0, t1, warmup, gap_rng)
        plen = lengths(online["prompt"], len(due), pair_rng)
        olen = lengths(online["output"], len(due), pair_rng)
        order = order_rng.permutation(len(due))
        for d, p, o in zip(due, plen[order], olen[order]):
            out.append(OnlineRequest(
                float(d), tok_rng.integers(0, vocab, int(p)).tolist(), int(o)))
    out.sort(key=lambda r: r.due)
    return out


def offline_backlog(mix: dict, vocab: int, seed: int) -> List[OfflineRequest]:
    off = mix.get("offline")
    rng, tok_rng = _rng(_SHAPE, _OFFLINE), _rng(seed, _OFFLINE)
    reqs = []
    if off is None:
        return reqs
    if off["kind"] == "shared_docs":
        doc_len = lengths(off["doc_len"], off["docs"], rng)
        n_q = lengths(off["questions_per_doc"], off["docs"], rng)
        q_len = lengths(off["question_len"], int(n_q.sum()), rng)
        a_len = lengths(off["answer_len"], int(n_q.sum()), rng)
        i = 0
        for d, (dl, nq) in enumerate(zip(doc_len, n_q)):
            doc = tok_rng.integers(0, vocab, int(dl)).tolist()
            for _ in range(int(nq)):
                q = tok_rng.integers(0, vocab, int(q_len[i])).tolist()
                reqs.append(OfflineRequest(doc + q, int(a_len[i]), d))
                i += 1
    elif off["kind"] == "unique":
        p_len = lengths(off["prompt"], off["count"], rng)
        o_len = lengths(off["output"], off["count"], rng)
        for p, o in zip(p_len, o_len):
            reqs.append(OfflineRequest(
                tok_rng.integers(0, vocab, int(p)).tolist(), int(o)))
    else:
        raise ValueError(f"unknown offline kind {off['kind']!r}")
    order = _rng(_SHAPE, _ORDER).permutation(len(reqs))
    return [reqs[i] for i in order]


def steady_online(mix: dict, rate: float, t0: float, t1: float, vocab: int,
                  seed: int, part: int) -> List[OnlineRequest]:
    """Online-only traffic at a steady ``rate`` on ``[t0, t1)`` with the
    mix's chat lengths, drawn as the segments of ``online_schedule`` are
    (the knee sweep's); ``part`` keeps the streams of each span apart."""
    online = mix["online"]
    streams = 100 * (part + 1)
    due = _arrivals({"rate_share": 1.0}, rate, t0, t1, t0,
                    _rng(_SHAPE, streams + _GAPS))
    len_rng = _rng(_SHAPE, streams + _ONLINE_LEN)
    plen = lengths(online["prompt"], len(due), len_rng)
    olen = lengths(online["output"], len(due), len_rng)
    tok_rng = _rng(seed, streams + _ONLINE_TOK)
    return [OnlineRequest(float(d), tok_rng.integers(0, vocab, int(p)).tolist(),
                          int(o)) for d, p, o in zip(due, plen, olen)]


def build(mix: dict, knee: float, vocab: int, seed: int, *, warmup: float,
          seconds: float, tail: float) -> Traffic:
    return Traffic(online_schedule(mix, knee, vocab, seed, warmup, seconds,
                                   tail),
                   offline_backlog(mix, vocab, seed),
                   (warmup, warmup + seconds))

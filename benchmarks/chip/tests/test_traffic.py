"""The traffic generator: seeds, counts and length distributions."""
import math
from statistics import NormalDist

import numpy as np
import pytest

import common
import traffic

SEED = 3_000_000_017          # larger than 32 bits hold


def _build(mix_name, seed, knee=5.0, vocab=1000):
    return traffic.build(common.load_mix(mix_name), knee, vocab, seed,
                         warmup=5.0, seconds=50.0, tail=45.0)


@pytest.mark.parametrize("mix", ["docqa-chat", "gen-chat"])
def test_same_seed_same_schedule(mix):
    a, b = _build(mix, SEED), _build(mix, SEED)
    assert [(r.due, r.prompt, r.max_new) for r in a.online] == \
        [(r.due, r.prompt, r.max_new) for r in b.online]
    assert [(r.prompt, r.max_new) for r in a.offline] == \
        [(r.prompt, r.max_new) for r in b.offline]


@pytest.mark.parametrize("mix", ["docqa-chat", "gen-chat"])
def test_seeds_change_tokens_not_work(mix):
    """Another seed: the same arrivals, lengths and order; other token
    ids."""
    a, b = _build(mix, SEED), _build(mix, SEED + 1)
    assert [(r.due, len(r.prompt), r.max_new) for r in a.online] == \
        [(r.due, len(r.prompt), r.max_new) for r in b.online]
    assert [(len(r.prompt), r.max_new, r.doc) for r in a.offline] == \
        [(len(r.prompt), r.max_new, r.doc) for r in b.offline]
    assert [r.prompt for r in a.online] != [r.prompt for r in b.online]
    assert [r.prompt for r in a.offline] != [r.prompt for r in b.offline]


@pytest.mark.parametrize("seed", [SEED, SEED + 1, 7])
def test_window_rate_matches_mix(seed):
    """docqa-chat: ``rate_share`` x knee, ``bursts.rate_share`` x knee for
    3 s of every 10 s, so a 50 s window holds 35 s of the one and 15 s of
    the other."""
    on = common.load_mix("docqa-chat")["online"]
    t = _build("docqa-chat", seed, knee=5.0)
    w0, w1 = t.window
    n = sum(w0 <= r.due < w1 for r in t.online)
    expect = (on["rate_share"] * 35 + on["bursts"]["rate_share"] * 15) * 5.0
    assert n == math.floor(expect + 0.5)
    dues = [r.due for r in t.online]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 100.0


def test_lengths_follow_their_distribution():
    rng = np.random.default_rng(0)
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.9,
            "min": 32, "max": 2048}
    v = traffic.lengths(spec, 1001, rng)
    assert v.min() >= 32 and v.max() <= 2048
    assert np.median(v) == 256
    above = 1 - NormalDist().cdf(math.log(2048 / 256) / 0.9)
    assert abs(np.mean(v == 2048) - above) < 2e-3
    spec = {"dist": "uniform", "min": 256, "max": 1024}
    v = traffic.lengths(spec, 769, rng)
    assert sorted(v.tolist()) == list(range(256, 1025))


def test_docqa_questions_share_their_document():
    t = _build("docqa-chat", SEED)
    mix = common.load_mix("docqa-chat")["offline"]
    by_doc = {}
    for r in t.offline:
        by_doc.setdefault(r.doc, []).append(r)
    assert len(by_doc) == mix["docs"]
    for reqs in by_doc.values():
        assert 4 <= len(reqs) <= 8
        lens = {len(r.prompt) - q for r in reqs for q in range(16, 65)}
        doc_len = min(len(r.prompt) for r in reqs) - 64
        prefix = reqs[0].prompt[:max(doc_len, 0)]
        assert all(r.prompt[:len(prefix)] == prefix for r in reqs)
        assert lens
    assert max(len(r.prompt) + r.max_new for r in t.offline) <= 4096

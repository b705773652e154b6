"""The knee sweep, driven on the CPU at the tiny size: a light rate holds,
a flood misses, and the sweep walks down from a rate that misses."""
import asyncio

import pytest

import cell
import common
import knee
import traffic
from repro.rt import AsyncEchoEngine

CFG = common.load_json(common.HERE / "tests" / "tiny.json")
MIX = common.load_json(common.HERE / "tests" / "tiny-mix.json")
SEED = 3_000_000_023
RATES = [1.0, 500.0]


@pytest.fixture(scope="module")
def engine():
    return cell.serve_engine(CFG, SEED)[0]


@pytest.mark.parametrize("start", RATES, ids=["from_below", "from_above"])
def test_sweep_finds_the_rate_that_holds(engine, start):
    clock = cell.Clock()
    rt = AsyncEchoEngine(engine, clock=clock, token_queue_cap=0)
    slo = cell.slo_of(CFG)
    phases = asyncio.run(knee.sweep(rt, clock, slo, MIX, CFG["vocab_size"],
                                    SEED, RATES, start, 4, 0.5))
    lines = [knee.report("tiny", phases[i], slo) for i in sorted(phases)]
    assert [ln["verdict"] for ln in lines] == ["holds", "misses"]
    assert lines[0]["judged"] >= 3 and lines[0]["share_pct"] >= 90.0


def test_steady_online_is_fixed_by_the_seed():
    a = traffic.steady_online(MIX, 2.0, 5.0, 25.0, 512, SEED, 1)
    b = traffic.steady_online(MIX, 2.0, 5.0, 25.0, 512, SEED, 1)
    c = traffic.steady_online(MIX, 2.0, 5.0, 25.0, 512, SEED + 1, 1)
    assert len(a) == 40 and all(5.0 <= r.due < 25.0 for r in a)
    assert [(r.due, r.prompt, r.max_new) for r in a] == \
        [(r.due, r.prompt, r.max_new) for r in b]
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in c]

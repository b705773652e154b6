"""The end-to-end readings from the clients' token times, and the prefix
hit share from first admissions."""
import math

import pytest

import cell
import common

SLO = cell.SLO(ttft=1.0, tpot=0.18)
WINDOW = (10.0, 20.0)


def _client(due, times, max_new, online=True, shed=False, prompt=4):
    c = cell.Client(online, due, [1] * prompt, max_new)
    c.times = list(times)
    c.tokens = [0] * len(times)
    c.shed = shed
    return c


def test_itl_counts_gaps_that_end_in_the_window():
    c = _client(9.0, [9.5, 9.9, 10.1, 10.3, 19.9], 5)
    gaps, n_open = cell.itl_gaps([c], WINDOW)
    assert gaps == pytest.approx([0.2, 0.2, 9.6])
    assert n_open == 0


def test_itl_counts_the_gap_open_at_the_close():
    """A request starved at the window's close shows its open gap."""
    c = _client(11.0, [11.5, 11.6, 13.0], 10)
    gaps, n_open = cell.itl_gaps([c], WINDOW)
    assert gaps == pytest.approx([0.1, 1.4, 7.0])
    assert n_open == 1


@pytest.mark.parametrize("times,shed,missing", [
    ([11.5, 11.6], True, 8),     # shed after two of ten tokens
    ([], True, 9),               # shed before its first token
    ([], False, 9),              # never answered
], ids=["shed_midway", "shed_at_once", "never_answered"])
def test_itl_counts_a_failed_request_as_infinite_gaps(times, shed, missing):
    c = _client(11.0, times, 10, shed=shed)
    gaps, _ = cell.itl_gaps([c], WINDOW)
    assert sum(math.isinf(g) for g in gaps) == missing
    ok = [_client(12.0, [12.1 + 0.1 * i for i in range(40)], 40)]
    m, info = cell.end_to_end(ok + [c], WINDOW, SLO)
    assert math.isinf(m["online_itl_p95_ms"]) == (missing > 2)
    assert info["online_failed"] == 1
    assert m["online_slo_pct"] == 50.0


def test_shedding_cannot_lower_the_itl_tail():
    """Dropping the slow request instead of serving it reads worse."""
    fast = [_client(11.0 + i, [11.1 + i + 0.05 * k for k in range(20)], 20)
            for i in range(5)]
    slow = _client(12.0, [12.1 + 0.5 * k for k in range(10)], 10)
    served, _ = cell.end_to_end(fast + [slow], WINDOW, SLO)
    dropped = _client(12.0, [12.1], 10, shed=True)
    shed, _ = cell.end_to_end(fast + [dropped], WINDOW, SLO)
    assert shed["online_itl_p95_ms"] > served["online_itl_p95_ms"]


def test_offline_credit():
    """Echo's credit: prompt tokens with the first token, then each token;
    the output rate counts only the tokens delivered in the window."""
    a = _client(0.0, [9.0, 10.5, 11.0], 3, online=False, prompt=100)
    b = _client(0.0, [12.0, 21.0], 2, online=False, prompt=50)
    m, info = cell.end_to_end([a, b], WINDOW, SLO)
    assert m["offline_tok_s"] == pytest.approx((1 + 1 + 51) / 10)
    assert m["offline_out_tok_s"] == pytest.approx(3 / 10)
    assert info["offline_untouched"] == 0


def _row(first):
    return cell.IterRow(0.0, 0.0, 0.01, 0.01, [], [], 0, first)


def test_prefix_hits_count_first_admissions_only():
    read = common.metric_reader("kv.prefix_hit_pct")
    ctx = cell.ReadContext({}, {}, [_row([(2048, 2100)]), _row([(0, 300)]),
                                    _row([])])
    assert read(ctx) == pytest.approx(100.0 * 2048 / 2400)
    assert read(cell.ReadContext({}, {}, [_row([])])) is None


def test_counters_note_only_an_offline_first_admission():
    """A re-admitted request, which re-finds its own blocks, and online
    requests add nothing to the prefix hits."""
    from types import SimpleNamespace as NS
    from repro.core.engine import StepTimes
    from repro.core.request import Request, TaskType
    off = Request(tuple(range(300)), 4, TaskType.OFFLINE)
    on = Request(tuple(range(50)), 4, TaskType.ONLINE)
    counters = cell.Counters(cell.Clock())

    def step(spans):
        counters.on_iteration(None, NS(
            prefill_spans=spans, schedule_wall=0.0, compute_time=0.01,
            predicted_time=0.01, decodes=[], admitted=[],
            times=StepTimes()))

    step([(off, 256, 300), (on, 0, 50)])
    off.recomputed_tokens = 44
    step([(off, 256, 300)])
    assert [r.offline_first for r in counters.rows] == [[(256, 300)], []]
    assert [r.recomputed for r in counters.rows] == [0, 44]


def test_rows_carry_the_step_times_and_first_admissions():
    """``IterRow.times`` is the step's own ``StepTimes``; the front-door
    stamps are those of the online requests the step admits first."""
    from types import SimpleNamespace as NS
    from repro.core.engine import StepTimes
    from repro.core.request import Request, TaskType
    on = [Request(tuple(range(50)), 4, TaskType.ONLINE) for _ in range(3)]
    off = Request(tuple(range(80)), 4, TaskType.OFFLINE)
    for i, r in enumerate(on + [off]):
        r.wall_submit, r.wall_intake = 0.5 + i, 0.7 + i
        r.wall_admit = 2.0
    on[2].wall_admit = 1.0                 # re-admitted after a preemption
    times = StepTimes(t_start=2.0, t_end=2.1, wait=0.05, n_syncs=1)
    counters = cell.Counters(cell.Clock())
    counters.on_iteration(None, NS(
        prefill_spans=[], schedule_wall=0.0, compute_time=0.01,
        predicted_time=0.01, decodes=[], admitted=on + [off], times=times))
    (row,) = counters.rows
    assert row.times is times
    assert row.online_admits == [(0.5, 0.7, 2.0), (1.5, 1.7, 2.0)]


def test_frontdoor_itl_reads_the_runs_own_p95():
    """The per-layer ITL p95 is the end-to-end reading of the same run,
    and nothing where no online gap landed in the window."""
    reader = common.metric_reader("frontdoor.itl_p95_ms")
    clients = [_client(11.0 + i, [11.1 + i + 0.05 * k for k in range(20)],
                       20) for i in range(5)]
    m, _ = cell.end_to_end(clients, WINDOW, SLO)
    ctx = cell.ReadContext({}, {}, [], e2e=m)
    assert reader(ctx) == m["online_itl_p95_ms"] == pytest.approx(50.0)
    empty, _ = cell.end_to_end([], WINDOW, SLO)
    assert reader(cell.ReadContext({}, {}, [], e2e=empty)) is None

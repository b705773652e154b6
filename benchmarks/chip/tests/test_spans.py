"""``spans.py``: the device trace with the program's ``echo.*`` spans
kept puts idle time down to the innermost span and leaves the ten
per-layer readings as they were; the four new readings on hand-made
steps; and one short run on the CPU."""
import copy

import pytest

import cell
import common
import devtrace
import spans
from repro.core.engine import StepTimes

MS = 1_000_000
TEN = ("sched.host_ms_per_step", "est.abs_err_pct", "kv.prefix_hit_pct",
       "kv.recompute_pct", "runner.decode_step_ms",
       "runner.prefill_chunk_ms", "decode_attn_roofline",
       "prefill_attn_roofline", "step.mfu_pct", "device.idle_pct")


def _hand_made():
    """One step: the scheduler, a decode call whose program runs 3-6 ms,
    its fetch, and the step's own tail; the program's spans inside the
    benchmark's."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 3 * MS, 2 * MS, False],
                    ["custom-call.2", 5 * MS, 1 * MS, True]],
            "modules": [["jit__decode_impl", 3 * MS, 3 * MS]]}},
        "host": [["bench.step", 0, 10 * MS],
                 ["echo.step", 0, 9 * MS],
                 ["echo.sched", 0, 1 * MS],
                 ["bench.decode", 1 * MS, 7 * MS],
                 ["echo.runner.decode#rows=8#", 1 * MS, 7 * MS],
                 ["echo.runner.prep", 1 * MS, 1 * MS],
                 ["echo.runner.launch", 2 * MS, 1 * MS],
                 ["echo.runner.wait", 3 * MS, 3 * MS],
                 ["echo.runner.fetch#rows=8#", 6 * MS, 2 * MS]],
    }


def test_idle_goes_to_the_innermost_program_span():
    got = devtrace.reduce(_hand_made())
    idle = spans.idle_by_span(got.idle_by_host)
    # 0-1 ms scheduling, 1-2 prep, 2-3 launch, 6-8 fetch (two names, one
    # span), 8-9 the step's own tail, 9-10 the benchmark's step
    assert idle == pytest.approx({"echo.sched": 0.001,
                                  "echo.runner.prep": 0.001,
                                  "echo.runner.launch": 0.001,
                                  "echo.runner.fetch": 0.002,
                                  "echo.step": 0.001, "bench.step": 0.001})
    assert spans.program_share(idle) == pytest.approx(5 / 7)
    assert got.calls == {"decode": 1, "prefill": 0}
    assert got.program_s["decode"] == pytest.approx(0.003)


def _with_program_spans(compact):
    """The recorded trace with a program span inside each of the
    benchmark's: ``echo.step`` in ``bench.step``, the runner's call in
    ``bench.decode``/``bench.prefill`` with its wait inside."""
    out = copy.deepcopy(compact)
    inner = {"bench.step": "echo.step", "bench.schedule": "echo.sched",
             "bench.decode": "echo.runner.decode",
             "bench.prefill": "echo.runner.prefill",
             "bench.commit": "echo.kv.commit"}
    for name, s, d in compact["host"]:
        if name in inner:
            out["host"].append([inner[name], s + 1000, d - 2000])
        if name in ("bench.decode", "bench.prefill"):
            out["host"].append(["echo.runner.wait", s + d // 4, d // 2])
    return out


def _rows(n):
    row = cell.IterRow(0.0, 0.002, 0.1, 0.12, [(0, 256), (2048, 100)],
                       [300, 1200, 3000], 16, [(2048, 2148)])
    return [row] * n


def test_the_ten_readings_are_unchanged_by_program_spans(recorded_trace):
    sample = recorded_trace
    dims = common.load_config("qwen3-4b")
    peak = common.peaks("TPU v5 lite")
    values = []
    for compact in (sample, _with_program_spans(sample)):
        summary = devtrace.reduce(compact)
        ctx = cell.ReadContext(dims, peak, _rows(8), summary, _rows(4))
        values.append({m: common.metric_reader(m)(ctx) for m in TEN})
    assert values[0] == values[1]
    assert all(v is not None for v in values[0].values())
    idle = spans.idle_by_span(devtrace.reduce(
        _with_program_spans(sample)).idle_by_host)
    assert any(n.startswith("echo.runner") for n in idle)


def _step(host_ms, wait_ms, syncs, fetch_ms, argmax_ms, admits=()):
    t = StepTimes(t_start=1.0, t_end=1.0 + (host_ms + wait_ms) / 1e3,
                  wait=wait_ms / 1e3, fetch=fetch_ms / 1e3,
                  argmax=argmax_ms / 1e3, n_syncs=syncs, n_launches=syncs)
    return cell.IterRow(0.0, t.schedule, 0.0, 0.0, [], [], 0, [], t,
                        list(admits))


def test_the_four_readings_on_hand_made_steps():
    rows = [_step(4.0, 100.0, 1, 1.0, 0.5, [(0.0, 0.2, 1.0)]),
            _step(6.0, 50.0, 3, 2.0, 1.5,
                  [(0.0, 0.1, 0.3), (0.0, 0.1, 2.0)])]
    assert spans.host_ms_per_step(rows) == pytest.approx(5.0)
    assert spans.syncs_per_step(rows) == 2.0
    assert spans.sample_ms_per_step(rows) == pytest.approx(2.5)
    wait = spans.queue_wait(rows)
    assert wait["n"] == 3
    assert wait["median_ms"] == pytest.approx(1000.0)
    assert wait["p95_ms"] == pytest.approx(2000.0)
    assert wait["intake_median_ms"] == pytest.approx(100.0)
    assert wait["sched_median_ms"] == pytest.approx(800.0)
    phases = spans.phase_ms(rows)
    assert phases["wall"] == pytest.approx(80.0)
    assert phases["other"] == pytest.approx(80.0 - 75.0 - 1.5 - 1.0)
    for read in (spans.host_ms_per_step, spans.syncs_per_step,
                 spans.sample_ms_per_step, spans.queue_wait):
        assert read([]) is None


def test_program_spans_restores_the_harness():
    saved = (cell.Counters, cell.StepHook, devtrace.HOST_PREFIX,
             devtrace.reduce)
    with spans.program_spans(cell, devtrace):
        assert devtrace.HOST_PREFIX == ("bench.", "echo.")
        assert issubclass(cell.Counters, saved[0])
    assert (cell.Counters, cell.StepHook, devtrace.HOST_PREFIX,
            devtrace.reduce) == saved


def test_a_short_run_reads_the_four_on_the_cpu():
    cfg = common.load_json(common.HERE / "tests" / "tiny.json")
    mix = common.load_json(common.HERE / "tests" / "tiny-mix.json")
    out = spans.measure("tiny.test", 3_000_000_021, 2.0, False, cfg=cfg,
                        mix=mix, require_tpu=False)["spans"]
    assert out["steps"] > 0
    assert out["engine.host_ms_per_step"] > 0
    assert out["runner.syncs_per_step"] >= 1
    assert out["runner.sample_ms_per_step"] > 0
    assert out["frontdoor.queue_wait_ms"]["n"] > 0
    assert out["phase_ms"]["wait"] > 0

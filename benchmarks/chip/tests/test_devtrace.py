"""The trace reducer against a brute-force count: on a small trace
recorded on a TPU v5e (``trace_sample.json``, a slice of a traced run of
``qwen3-4b.docqa-chat``) and on a hand-made one."""
import json

import numpy as np
import pytest

import common
import devtrace

US = 1000    # the brute force works on a 1 us grid


def _hand_made():
    """Two steps: a decode call (one module, a kernel op and a matmul op)
    and a prefill call, with host work between them."""
    ms = 1_000_000
    return {
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 1 * ms, 2 * ms, False],
                    ["custom-call.2", 3 * ms, 1 * ms, True],
                    ["fusion.3", 10 * ms, 3 * ms, False],
                    ["custom-call.4", 13 * ms, 2 * ms, True],
                    ["fusion.5", 14 * ms, 2 * ms, False]],
            "modules": [["jit__decode_impl", 1 * ms, 3 * ms],
                        ["jit__prefill_impl", 10 * ms, 6 * ms]]}},
        "host": [["bench.step", 0, 8 * ms],
                 ["bench.schedule", 0, 1 * ms],
                 ["bench.decode", 1 * ms, 4 * ms],
                 ["bench.step", 9 * ms, 9 * ms],
                 ["bench.prefill", 10 * ms, 7 * ms],
                 ["bench.hop", 0, 20 * ms]],
    }


def _recorded():
    return common.load_json(common.HERE / "tests" / "trace_sample.json")


def _brute(compact):
    host = compact["host"]
    steps = [(s, s + d) for n, s, d in host if n == "bench.step"]
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    n = (hi - lo) // US + 1
    busy_sum, kern, prog = 0.0, {}, {}
    spans = {k: np.zeros(n, bool) for k in ("decode", "prefill")}
    for name, s, d in host:
        k = {"bench.decode": "decode", "bench.prefill": "prefill"}.get(name)
        if k:
            spans[k][max((s - lo) // US, 0):max((s + d - lo) // US, 0)] = True
    for dev in compact["devices"].values():
        busy = np.zeros(n, bool)
        for _, s, d, c in dev["ops"]:
            a, b = max((s - lo) // US, 0), min((s + d - lo) // US, n - 1)
            busy[a:b] = True
            mid = min(max((s + d // 2 - lo) // US, 0), n - 1)
            for k, sp in spans.items():
                if c and sp[mid]:
                    kern[k] = kern.get(k, 0.0) + (b - a) * US / 1e9
        for _, s, d in dev["modules"]:
            mid = min(max((s + d // 2 - lo) // US, 0), n - 1)
            for k, sp in spans.items():
                if sp[mid] and lo <= s < hi:
                    prog[k] = prog.get(k, 0.0) + min(d, hi - s) / 1e9
        busy_sum += busy.sum() * US / 1e9
    return ((hi - lo) / 1e9, busy_sum / len(compact["devices"]), kern, prog)


@pytest.mark.parametrize("make", [_hand_made, _recorded],
                         ids=["hand_made", "recorded"])
def test_reduce_matches_brute_force(make):
    compact = make()
    got = devtrace.reduce(compact)
    window, busy, kern, prog = _brute(compact)
    assert got.window_s == pytest.approx(window)
    assert got.busy_s == pytest.approx(busy, rel=1e-3, abs=2e-5)
    for k in ("decode", "prefill"):
        assert got.kernel_s[k] == pytest.approx(kern.get(k, 0.0), rel=1e-3,
                                                abs=2e-5)
        assert got.program_s[k] == pytest.approx(prog.get(k, 0.0))
    assert 0 < got.busy_s <= got.window_s
    idle = sum(s for _, s in got.idle_by_host)
    assert idle == pytest.approx(got.window_s - got.busy_s, rel=1e-6)


def test_hand_made_numbers():
    got = devtrace.reduce(_hand_made())
    assert got.window_s == pytest.approx(0.018)
    # busy: 1-4 ms, 10-16 ms
    assert got.busy_s == pytest.approx(0.009)
    assert got.calls == {"decode": 1, "prefill": 1}
    assert got.kernel_s == pytest.approx({"decode": 0.001, "prefill": 0.002})
    assert got.program_s == pytest.approx({"decode": 0.003,
                                           "prefill": 0.006})
    idle = dict(got.idle_by_host)
    # 0-1 ms under the scheduler; 4-5 under decode, 5-8 under the step,
    # 8-9 under the hop, 9-10 under the step, 16-17 under prefill, 17-18
    # under the step
    assert idle == pytest.approx({"bench.schedule": 0.001,
                                  "bench.decode": 0.001,
                                  "bench.step": 0.005, "bench.hop": 0.001,
                                  "bench.prefill": 0.001})


def test_recorded_sample_is_a_tpu_trace():
    compact = _recorded()
    assert all(p.startswith("/device:TPU") for p in compact["devices"])
    assert any(c for d in compact["devices"].values()
               for *_, c in d["ops"])
    json.dumps(compact)

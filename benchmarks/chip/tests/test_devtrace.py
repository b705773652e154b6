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
        for op, s, d, c in dev["ops"]:
            a, b = max((s - lo) // US, 0), min((s + d - lo) // US, n - 1)
            busy[a:b] = True
            mid = min(max((s + d // 2 - lo) // US, 0), n - 1)
            for k, sp in spans.items():
                if c and sp[mid]:
                    name = devtrace.kernel_name(op)
                    kern.setdefault(k, {})
                    kern[k][name] = kern[k].get(name, 0.0) + (b - a) * US / 1e9
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
        assert set(got.kernels[k]) == set(kern.get(k, {}))
        for name, secs in kern.get(k, {}).items():
            assert got.kernels[k][name] == pytest.approx(secs, rel=1e-3,
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
    assert got.kernels == {"decode": {"custom-call": pytest.approx(0.001)},
                           "prefill": {"custom-call": pytest.approx(0.002)}}
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


def test_kernel_names():
    assert devtrace.kernel_name("paged_decode_attention.9") == \
        "paged_decode_attention"
    assert devtrace.kernel_name("chunked_prefill_attention") == \
        "chunked_prefill_attention"
    assert devtrace.kernel_name("fusion.1.2") == "fusion.1"


# ---- the attention rooflines read their own kernel, by name
ATTN = ("decode_attn_roofline", "prefill_attn_roofline")


def _context(compact, dims, peak):
    import cell
    row = cell.IterRow(0.0, 0.002, 0.1, 0.12, [(0, 256), (2048, 100)],
                       [300, 1200, 3000], 16, [(2048, 2148)])
    return cell.ReadContext(dims, peak, [row] * 8, devtrace.reduce(compact),
                            [row] * 4)


def _read_before(name, ctx, custom_s):
    """The readers as they were before kernel time was kept by name: over
    every custom call inside the kind's calls (``custom_s``)."""
    import roofline
    least = 0.0
    for r in ctx.traced_rows:
        if name == "decode_attn_roofline" and r.decode_ctx:
            least += roofline.least_time(
                *roofline.decode_attn(ctx.dims, r.decode_ctx), ctx.peak)
        if name == "prefill_attn_roofline":
            for start, n in r.prefill:
                least += roofline.least_time(
                    *roofline.prefill_attn(ctx.dims, start, n), ctx.peak)
    return 100.0 * least / custom_s


def _plant(compact, kind, name):
    """A custom call named ``name`` of 2 ms, in the middle of the first
    ``bench.<kind>`` span, on every device."""
    import copy
    out = copy.deepcopy(compact)
    (s, d) = next((s, d) for n, s, d in out["host"] if n == f"bench.{kind}")
    for dev in out["devices"].values():
        dev["ops"].append([f"{name}.77", s + d // 2 - 1_000_000, 2_000_000,
                           True])
    return out


def test_attention_rooflines_read_as_before(recorded_trace):
    """On the recorded trace, whose only custom calls are the two
    attention kernels, each reader reads what it read over all custom
    calls of its kind."""
    dims, peak = common.load_config("qwen3-4b"), common.peaks("TPU v5 lite")
    ctx = _context(recorded_trace, dims, peak)
    for name, kind in zip(ATTN, ("decode", "prefill")):
        custom_s = sum(ctx.trace.kernels[kind].values())
        assert len(ctx.trace.kernels[kind]) == 1
        got = common.metric_reader(name)(ctx)
        assert got is not None
        assert got == _read_before(name, ctx, custom_s)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_another_kernel_enters_no_attention_roofline(recorded_trace, kind):
    """A custom call of another name inside a runner call (a grouped
    expert matmul, say) leaves both attention readers as they were."""
    dims, peak = common.load_config("qwen3-4b"), common.peaks("TPU v5 lite")
    base = _context(recorded_trace, dims, peak)
    planted = _context(_plant(recorded_trace, kind, "grouped_expert_matmul"),
                       dims, peak)
    assert planted.trace.kernels[kind]["grouped_expert_matmul"] > 0
    for name in ATTN:
        assert common.metric_reader(name)(planted) == \
            common.metric_reader(name)(base)

"""Real-time serving layer: async lifecycle (stream/abort/drain),
backpressure at both ends, wall-vs-drive equivalence on a paused clock,
EventBus thread safety under a two-thread hammer, engine step-lock
reentrancy, link-calibration fitting, and the TCP front door."""
import asyncio
import threading

import numpy as np
import pytest

from repro.cluster import ClusterSimulator
from repro.core import ECHO, SLO, EchoEngine, TimeModel
from repro.core.request import Request, TaskType
from repro.core.simulator import clone_requests
from repro.data import make_offline_corpus, make_online_requests
from repro.serving import AdmissionConfig, EchoService, HandleStatus
from repro.serving.events import EventBus
from repro.serving.handle import TokenEvent
from repro.rt import (AsyncEchoEngine, EchoServer, ManualClock,
                      RTState, SubmitQueueFull, request_once)
from repro.rt.calibrate import calibrate_link
import repro.rt.calibrate as calibrate_mod

from tests.test_serving import (assert_no_block_leaks,
                                assert_no_owner_pin_leaks)


def _tm():
    return TimeModel.a100()


def _engine(num_blocks=128, **kw):
    return EchoEngine(None, None, ECHO, num_blocks=num_blocks, block_size=16,
                      chunk_size=32, time_model=_tm(), **kw)


def _workload(seed=0, duration=6.0, rate=2.0):
    rng = np.random.default_rng(seed)
    arrivals = list(np.cumsum(rng.exponential(1.0 / rate,
                                              int(rate * duration))))
    online = make_online_requests(arrivals, prompt_mean=48, prompt_std=12,
                                  max_new_mean=8, slo=SLO(1.0, 0.1),
                                  seed=seed + 1)
    offline = make_offline_corpus(3, 8, doc_len=96, question_len=16,
                                  max_new=6, seed=seed + 2)
    return online, offline


def _leakcheck(rt):
    leaks = rt.kv_leaks()
    assert not any(leaks.values()), f"leaked after drain: {leaks}"
    for eng in rt.service.backend.engines():
        assert_no_block_leaks(eng)
        assert_no_owner_pin_leaks(eng)


# ------------------------------------------------------------- lifecycle
def test_stream_and_result():
    async def main():
        rt = AsyncEchoEngine(_engine(), clock=ManualClock())
        async with rt:
            h = await rt.submit([1, 2, 3], max_new_tokens=8)
            got = []
            async for ev in h.tokens():
                got.append(ev.token)
                assert ev.index == len(got) - 1
            assert got[0] is not None and len(got) == 8
            res = await h.result()
            assert res.status is HandleStatus.FINISHED
            assert res.tokens == got
            assert h.wall_ttft() is not None
        assert rt.state is RTState.STOPPED
        _leakcheck(rt)
    asyncio.run(main())


def test_graceful_drain_with_inflight_decode():
    """drain() must let requests that are mid-decode finish — not shed
    them — and leave zero KV residue."""
    async def main():
        rt = AsyncEchoEngine(_engine(), clock=ManualClock())
        await rt.start()
        hs = [await rt.submit([1 + i, 2, 3], max_new_tokens=24)
              for i in range(6)]
        # wait until at least one token streamed (decode is in flight)
        first = await hs[0].tokens().__anext__()
        assert first.index == 0
        await rt.drain()
        for h in hs:
            res = await h.result()
            assert res.status is HandleStatus.FINISHED, res.status
            assert len(res.tokens) == 24
        assert rt.stats.drain_sheds == 0
        _leakcheck(rt)
        # the front door is closed: late submits are shed, not queued
        late = await rt.submit([9, 9], max_new_tokens=4)
        assert late.status is HandleStatus.SHED
        assert rt.stats.shed_closed == 1
    asyncio.run(main())


def test_drain_flushes_swap_stager():
    """Graceful drain on a host-tiered engine lands every in-flight
    staging transfer (flush hook through the backend)."""
    async def main():
        rt = AsyncEchoEngine(_engine(num_blocks=48, host_kv_blocks=64),
                             clock=ManualClock())
        async with rt:
            online, offline = _workload(seed=3, duration=3.0)
            hs = [await rt.submit_request(r)
                  for r in clone_requests(online + offline)]
            for h in hs:
                await h.result()
        assert rt.service.engine._stager is None or \
            rt.service.engine._stager.inflight_blocks() == 0
        _leakcheck(rt)
    asyncio.run(main())


def test_mid_stream_abort_releases_kv():
    """await handle.abort() mid-decode frees blocks/pins immediately and
    terminates the token stream."""
    async def main():
        rt = AsyncEchoEngine(_engine(num_blocks=64, host_kv_blocks=32),
                             clock=ManualClock())
        async with rt:
            victim = await rt.submit([1] * 40, max_new_tokens=200)
            others = [await rt.submit([7 + i] * 8, max_new_tokens=8)
                      for i in range(3)]
            stream = victim.tokens()
            seen = 0
            async for _ev in stream:
                seen += 1
                if seen == 3:
                    assert await victim.abort() is True
            assert 3 <= seen < 200          # stream ended early
            assert victim.status is HandleStatus.ABORTED
            assert await victim.abort() is False     # already terminal
            res = await victim.result()
            assert res.status is HandleStatus.ABORTED
            for h in others:                # survivors unaffected
                assert (await h.result()).status is HandleStatus.FINISHED
        assert rt.stats.aborted == 1
        _leakcheck(rt)
    asyncio.run(main())


def test_abort_while_still_in_intake_queue():
    """Aborting before the loop ever drains the submit queue must settle
    the handle without touching the backend."""
    async def main():
        rt = AsyncEchoEngine(_engine(), clock=ManualClock())
        # not started: the request sits in intake
        h = await rt.submit([1, 2], max_new_tokens=4)
        assert h.status is HandleStatus.QUEUED
        assert await h.abort() is True
        assert h.status is HandleStatus.ABORTED
        await rt.start()
        await rt.drain()
        assert len(rt.service.engine.stats.iterations) == 0
        _leakcheck(rt)
    asyncio.run(main())


# ------------------------------------------------------------- backpressure
def test_submit_queue_sheds_when_saturated():
    async def main():
        rt = AsyncEchoEngine(_engine(), clock=ManualClock(),
                             max_submit_queue=4)
        # loop not started: nothing drains the queue, so 4 fit, rest shed
        hs = [await rt.submit([1, i], max_new_tokens=2, wait=False)
              for i in range(10)]
        shed = [h for h in hs if h.status is HandleStatus.SHED]
        assert len(shed) == 6
        assert rt.stats.shed_submit_queue == 6
        for h in shed:                      # shed handles settle instantly
            res = await h.result()
            assert res.status is HandleStatus.SHED
            assert res.tokens == []
        with pytest.raises(SubmitQueueFull):
            rt.try_submit_nowait(Request(prompt=(1,), max_new_tokens=2,
                                         task_type=TaskType.ONLINE,
                                         arrival_time=0.0))
        await rt.start()
        await rt.drain()                    # the 4 queued ones complete
        assert rt.stats.finished == 4
        _leakcheck(rt)
    asyncio.run(main())


def test_slow_consumer_hits_token_queue_cap():
    """A consumer that never reads must be aborted at the queue cap, not
    buffer the whole generation."""
    async def main():
        rt = AsyncEchoEngine(_engine(), clock=ManualClock(),
                             token_queue_cap=4)
        async with rt:
            h = await rt.submit([1, 2, 3], max_new_tokens=64)
            res = await h.result()          # never consumes the stream
        assert res.status is HandleStatus.ABORTED
        assert h.overflowed
        assert rt.stats.slow_consumer_aborts == 1
        assert len(res.tokens) < 64
        # the stream still terminates (EOS forced in) for a late reader
        tokens = [ev async for ev in h.tokens()]
        assert len(tokens) <= 4
        _leakcheck(rt)
    asyncio.run(main())


def test_admission_shed_propagates_to_async_handle():
    async def main():
        rt = AsyncEchoEngine(_engine(num_blocks=32),
                             admission=AdmissionConfig(max_online_queue=1),
                             clock=ManualClock())
        async with rt:
            hs = [await rt.submit([1 + i] * 24, max_new_tokens=16)
                  for i in range(30)]
            res = await asyncio.gather(*[h.result() for h in hs])
        statuses = {r.status for r in res}
        assert HandleStatus.SHED in statuses      # queue cap bit
        assert HandleStatus.FINISHED in statuses  # but service kept going
        assert rt.stats.shed == sum(
            r.status is HandleStatus.SHED for r in res)
        _leakcheck(rt)
    asyncio.run(main())


# ------------------------------------------------------------- equivalence
def test_wall_loop_matches_drive_on_paused_clock():
    """The async loop is plumbing, not policy: replaying a trace through
    it (paused serving clock, explicit arrival stamps) must reproduce the
    synchronous drive() path bit-identically."""
    online, offline = _workload(seed=11, duration=5.0, rate=3.0)
    ref_service = EchoService(_engine())
    want = ref_service.drive(clone_requests(online + offline,
                                            preserve_rid=True),
                             max_iters=20_000, until_time=60.0)

    async def main():
        rt = AsyncEchoEngine(_engine(), clock=ManualClock())
        async with rt:
            hs = [await rt.submit_request(r)
                  for r in clone_requests(online + offline,
                                          preserve_rid=True)]
            results = [await h.result() for h in hs]
        _leakcheck(rt)
        return results

    results = asyncio.run(main())
    # engine-domain outcomes must match request by request
    want_by_rid = {r.rid: r for r in want.finished}
    assert len(results) == len(online) + len(offline)
    finished = [r for r in results if r.status is HandleStatus.FINISHED]
    assert len(finished) == len(want.finished)
    for req, res in zip(clone_requests(online + offline, preserve_rid=True),
                        results):
        ref = want_by_rid.get(req.rid)
        if ref is None:
            continue
        assert res.tokens == list(ref.output_tokens), req.rid
        assert res.finish_time == ref.finish_time, req.rid
        assert res.ttft == ref.ttft(), req.rid


def test_wall_loop_matches_drive_on_cluster():
    online, offline = _workload(seed=5, duration=4.0, rate=2.0)

    def sim():
        return ClusterSimulator(2, ECHO, num_blocks=96, time_model=_tm(),
                                seed=0)

    want = EchoService(sim()).drive(
        clone_requests(online + offline, preserve_rid=True),
        until_time=60.0)

    async def main():
        rt = AsyncEchoEngine(sim(), clock=ManualClock())
        async with rt:
            hs = [await rt.submit_request(r)
                  for r in clone_requests(online + offline,
                                          preserve_rid=True)]
            return [await h.result() for h in hs]

    results = asyncio.run(main())
    merged = want.merged()
    finished = [r for r in results if r.status is HandleStatus.FINISHED]
    assert len(finished) == len(merged.finished)
    # same scheduling decisions -> same engine-domain finish times
    want_by_rid = {r.rid: r for r in merged.finished}
    for req, res in zip(clone_requests(online + offline, preserve_rid=True),
                        results):
        if req.rid in want_by_rid:
            assert res.finish_time == want_by_rid[req.rid].finish_time


# ------------------------------------------------------------- wall clock
def test_wall_stamps_use_serving_clock():
    async def main():
        clock = ManualClock()
        rt = AsyncEchoEngine(_engine(), clock=clock)
        h = await rt.submit([1, 2], max_new_tokens=4)
        assert h.t_submit_wall == 0.0
        clock.advance(1.5)
        async with rt:
            res = await h.result()
        assert res.status is HandleStatus.FINISHED
        assert h.t_first_token_wall == 1.5
        assert h.wall_ttft() == 1.5
        assert h.wall_latency() == 1.5
        _leakcheck(rt)
    asyncio.run(main())


# ------------------------------------------------------------- thread safety
def test_event_bus_concurrent_emit_two_thread_hammer():
    """Regression for the off-thread step loop: two threads emitting into
    one bus must never lose a count (the emit path is serialized)."""
    bus = EventBus()
    seen = [0]
    bus.on_finish(lambda h: seen.__setitem__(0, seen[0] + 1))
    N = 5_000

    def hammer():
        for _ in range(N):
            bus.emit("finish", None)

    threads = [threading.Thread(target=hammer) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert seen[0] == 2 * N
    assert bus.dropped_callbacks == 0


def test_live_metrics_concurrent_token_counts_exact():
    from repro.serving.events import LiveMetrics

    class _Req:
        is_online = True

    class _H:
        request = _Req()

    bus = EventBus()
    live = LiveMetrics(bus)
    N = 4_000
    ev = TokenEvent(handle=_H(), token=1, t=0.0, index=1)

    def hammer():
        for _ in range(N):
            bus.emit("token", ev)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert live.online_tokens == 4 * N


def test_engine_step_rejects_reentry():
    """The step lock must fail loudly on a second concurrent driver rather
    than corrupt scheduler/KV state."""
    eng = _engine()
    eng.submit(Request(prompt=(1, 2, 3), max_new_tokens=4,
                       task_type=TaskType.ONLINE, arrival_time=0.0))
    entered = threading.Event()
    release = threading.Event()
    errors = []

    orig = eng._step_impl

    def slow_step():
        entered.set()
        release.wait(5.0)
        return orig()

    eng._step_impl = slow_step
    t = threading.Thread(target=eng.step)
    t.start()
    assert entered.wait(5.0)
    with pytest.raises(RuntimeError, match="re-entered"):
        eng.step()
    release.set()
    t.join(5.0)
    eng._step_impl = orig
    eng.run(100)                            # engine still healthy


# ------------------------------------------------------------- calibration
def test_fit_swap_recovers_synthetic_link():
    tm = TimeModel.a100()
    byte_s, floor = 2e-10, 5e-5             # 5 GB/s + 50us floor
    samples = [(n, byte_s * n + floor)
               for n in (1 << 16, 1 << 18, 1 << 20, 1 << 22)]
    tm.fit_swap(samples)
    assert tm.swap_byte == pytest.approx(byte_s, rel=1e-6)
    assert tm.swap_floor == pytest.approx(floor, rel=1e-6)


def test_calibrate_link_degenerate_fit_restores_presets(monkeypatch):
    tm = TimeModel.a100()
    before = (tm.swap_byte, tm.swap_floor, tm.swap_launch)
    # all-equal timings -> zero fitted byte rate -> keep presets
    monkeypatch.setattr(calibrate_mod, "measure_link",
                        lambda sizes, repeats: [(1 << 18, 1e-4),
                                                (1 << 22, 1e-4)])
    cal = calibrate_link(tm, overlap=False)
    assert not cal.applied and "degenerate" in cal.error
    assert (tm.swap_byte, tm.swap_floor, tm.swap_launch) == before


def test_calibrate_link_degenerate_fit_raises_off_cpu(monkeypatch):
    """Only the CPU backend, whose device buffers alias host memory, may
    keep the presets; an accelerator link that measures nothing is an
    error, not a silent skip."""
    tm = TimeModel.a100()
    before = (tm.swap_byte, tm.swap_floor, tm.swap_launch)
    monkeypatch.setattr(calibrate_mod, "measure_link",
                        lambda sizes, repeats: [(1 << 18, 1e-4),
                                                (1 << 22, 1e-4)])
    monkeypatch.setattr(calibrate_mod.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="degenerate"):
        calibrate_link(tm, overlap=False)
    assert (tm.swap_byte, tm.swap_floor, tm.swap_launch) == before


def test_calibrate_link_measurement_error_propagates(monkeypatch):
    def broken(sizes, repeats):
        raise OSError("transfer failed")
    monkeypatch.setattr(calibrate_mod, "measure_link", broken)
    with pytest.raises(OSError, match="transfer failed"):
        calibrate_link(TimeModel.a100())


def test_calibrate_link_real_backend_smoke():
    """On the CPU backend the calibration must either apply a positive
    byte rate or explain why it kept the presets — and never raise."""
    tm = TimeModel.a100()
    cal = calibrate_link(tm, sizes=(1 << 16, 1 << 18), repeats=1)
    if cal.applied:
        assert tm.swap_byte > 0.0
        assert cal.bandwidth_gbs > 0.0
    else:
        assert cal.error


# ------------------------------------------------------------- TCP server
def test_tcp_server_roundtrip_and_drain():
    async def main():
        rt = AsyncEchoEngine(_engine())
        await rt.start()
        srv = await EchoServer(rt, port=0).start()
        host, port = srv.address
        outs = await asyncio.gather(*[
            request_once(host, port, [1, 2, 3 + i], max_new_tokens=4)
            for i in range(8)])
        assert all(o["status"] == "finished" for o in outs)
        assert all(len(o["tokens"]) == 4 for o in outs)
        await srv.close()
        assert srv.requests_served == 8
        _leakcheck(rt)
    asyncio.run(main())


def test_tcp_server_disconnect_aborts_inflight():
    async def main():
        rt = AsyncEchoEngine(_engine())
        await rt.start()
        srv = await EchoServer(rt, port=0).start()
        host, port = srv.address
        reader, writer = await asyncio.open_connection(host, port)
        import json as _json
        writer.write(_json.dumps({"prompt": [1] * 30,
                                  "max_new_tokens": 500}).encode() + b"\n")
        await writer.drain()
        await reader.readline()             # one token arrived
        writer.close()                      # hang up mid-stream
        try:
            await writer.wait_closed()
        except ConnectionResetError:
            pass
        # the server aborts the orphaned request; drain must not hang
        await asyncio.wait_for(srv.close(), timeout=30.0)
        assert rt.stats.aborted >= 1
        _leakcheck(rt)
    asyncio.run(main())


def test_tcp_server_rejects_malformed_request():
    async def main():
        rt = AsyncEchoEngine(_engine())
        await rt.start()
        srv = await EchoServer(rt, port=0).start()
        host, port = srv.address
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b'{"nope": 1}\n')
        await writer.drain()
        import json as _json
        err = _json.loads(await reader.readline())
        assert "error" in err
        # connection survives: a valid request still works
        writer.write(_json.dumps({"prompt": [1, 2],
                                  "max_new_tokens": 2}).encode() + b"\n")
        await writer.drain()
        lines = [await reader.readline() for _ in range(3)]
        assert _json.loads(lines[-1])["done"]
        writer.close()
        await srv.close()
        _leakcheck(rt)
    asyncio.run(main())


# ------------------------------------------------------------- observability
def test_rt_probe_wall_histograms_and_spans():
    from repro.obs import MetricsRegistry, Tracer
    from repro.obs.trace import RT_PID

    async def main():
        clock = ManualClock()
        rt = AsyncEchoEngine(_engine(), clock=clock)
        tracer = Tracer()
        reg = rt.instrument(MetricsRegistry(), tracer)
        async with rt:
            hs = [await rt.submit([1, 2, 3 + i], max_new_tokens=4)
                  for i in range(3)]
            for h in hs:
                await h.result()
        assert reg.get("rt_requests_total").labels("finished").value == 3
        assert reg.get("rt_ttft_wall_seconds").percentile(0.5) is not None
        rt_events = [e for e in tracer._events if e[4] == RT_PID]
        assert len(rt_events) >= 3          # one span per connection
        _leakcheck(rt)
    asyncio.run(main())

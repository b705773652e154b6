#!/usr/bin/env python3
"""One-chip smoke run of Echo's serving path on a TPU.

    python chip_smoke.py

One process, three phases:

1. kernels — paged decode (ragged contexts) and chunked prefill
   (prefix + chunk) at Qwen3-4B widths in bf16, each against its ``ref.py``
   oracle computed in float32;
2. reference — full-width Qwen3-4B (36 layers, d_model 2560, bf16, random
   weights from ``--seed``) built by ``repro.launch.serve.build_engine``:
   every step shape is compiled ahead of serving, the decode step must hold
   a Pallas kernel (``tpu_custom_call``), and one prompt's logits through
   the paged prefill must agree with the dense ``Model.prefill``;
3. serve — that engine behind ``repro.rt.AsyncEchoEngine`` on the wall
   clock: online requests with an SLO plus an offline backlog sharing one
   document prefix, every token streamed, one request aborted
   mid-stream, then a graceful drain with leak checks.

Progress goes to earlier lines; the last line of stdout is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a TPU,
or when any check fails, the script exits non-zero and prints no result.
The scheduler's estimate is the A100 ``TimeModel`` preset (no v5e preset
is fitted yet), so no estimator number here describes the chip.

The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import ECHO, SLO  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.serve import build_engine  # noqa: E402
from repro.rt import AsyncEchoEngine  # noqa: E402
from repro.serving import HandleStatus  # noqa: E402

ARCH = "qwen3-4b"
# Pool sized from the compiled steps' memory_analysis: the jitted steps
# do not donate the pool, so the peak is params (8.04 GB, tied
# embeddings) plus twice the pool (2 x 2.42 GB at 1024 pages of 16).
NUM_PAGES = 1024
PAGE_SIZE = 16
MAX_PAGES_PER_SEQ = 64         # 1024-token contexts
CHUNK = 256
MAX_RUNNING = 8                # decode buckets 1, 2, 4, 8
BF16_TOL = 3e-2                # kernel vs float32 oracle, abs + rel
LOGITS_TOL = 0.1               # paged vs dense prefill, relative L2
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

ONLINE_PROMPTS = (48, 96, 160, 200)
ONLINE_NEW = 16
ABORT_PROMPT, ABORT_NEW, ABORT_AFTER = 64, 64, 4
DOC_LEN, QUESTION_LEN, N_QUESTIONS, OFFLINE_NEW = 512, 32, 6, 8


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else one fixed in-checkout
    path (the path is part of the cache key, so it never moves)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")


# ------------------------------------------------------------- kernels
def kernel_check(cfg, seed: int) -> None:
    """Pallas paged decode and chunked prefill at ``cfg``'s widths in
    bf16 against the float32 ``ref.py`` oracles."""
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf = jnp.bfloat16

    def f32_ref(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*(a.astype(jnp.float32)
                        if a.dtype == bf else a for a in args))

    def max_err(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        check(got.shape == want.shape and np.isfinite(got).all(),
              f"kernel output shape {got.shape} finite")
        err = np.abs(got - want)
        check(bool((err <= BF16_TOL + BF16_TOL * np.abs(want)).all()),
              f"kernel within bf16 tolerance (max err {err.max():.3g})")
        return float(err.max())

    b, pages = 8, 4 * MAX_PAGES_PER_SEQ
    q = jax.random.normal(ks[0], (b, hq, hd), bf)
    kp = jax.random.normal(ks[1], (pages, PAGE_SIZE, hkv, hd), bf)
    vp = jax.random.normal(ks[2], (pages, PAGE_SIZE, hkv, hd), bf)
    bt = jax.random.randint(ks[3], (b, MAX_PAGES_PER_SEQ), 0, pages)
    ctx = jnp.asarray([1, 15, 16, 17, 300, 511, 777, 1024], jnp.int32)
    got = ops.paged_attention(q, kp, vp, bt, ctx,
                              impl="paged_decode_attention")
    err = max_err(got, f32_ref(ref.ref_paged_attention, q, kp, vp, bt, ctx))
    log(f"kernel paged decode         B={b} Hq={hq} Hkv={hkv} hd={hd} "
        f"ctx={ctx.tolist()}: max abs err {err:.3g} vs float32 ref")

    t, prefix = MAX_PAGES_PER_SEQ * PAGE_SIZE, 700
    q = jax.random.normal(ks[4], (CHUNK, hq, hd), bf)
    k = jax.random.normal(ks[5], (t, hkv, hd), bf)
    v = jax.random.normal(ks[6], (t, hkv, hd), bf)
    got = ops.chunked_prefill_attention(q, k, v, prefix, impl="pallas")
    err = max_err(got, f32_ref(ref.ref_chunked_prefill_attention,
                               q, k, v, jnp.int32(prefix)))
    log(f"kernel chunked prefill      Sc={CHUNK} T={t} prefix={prefix}: "
        f"max abs err {err:.3g} vs float32 ref")


# ------------------------------------------------------------- reference
def compile_steps(runner, batch_sizes):
    """Compile the prefill step and every decode bucket ahead of serving
    (the runner's later calls reuse these executables). Returns compile
    seconds per shape, and whether every decode step holds a Pallas
    kernel (``tpu_custom_call``)."""
    i32 = np.int32
    secs, kernel = {}, True
    t0 = time.perf_counter()
    runner._prefill_jit.lower(
        runner.params, jnp.asarray(np.zeros(runner.chunk_size, i32)),
        jnp.int32(0), jnp.int32(0),
        jnp.asarray(np.zeros(runner.max_pages, i32)), runner.pages).compile()
    secs[f"prefill[{runner.chunk_size}]"] = time.perf_counter() - t0
    for b in batch_sizes:
        t0 = time.perf_counter()
        exe = runner._decode_jit.lower(
            runner.params, jnp.asarray(np.zeros(b, i32)),
            jnp.asarray(np.zeros((b, runner.max_pages), i32)),
            jnp.asarray(np.full(b, -1, i32)), runner.pages).compile()
        secs[f"decode[{b}]"] = time.perf_counter() - t0
        kernel &= "tpu_custom_call" in exe.as_text()
    return secs, kernel


def reference_check(engine, prompt) -> None:
    """First-token logits of ``prompt`` through the paged runner (its
    pages are written before any request owns them) against the dense
    ``Model.prefill`` on the same weights."""
    runner, model = engine.runner, engine.model
    n_pages = -(-len(prompt) // PAGE_SIZE)
    paged = runner.prefill_chunk(prompt, 0, list(range(n_pages)))
    t0 = time.perf_counter()
    dense, _ = jax.jit(model.prefill)(runner.params,
                                      jnp.asarray([prompt], jnp.int32))
    dense = np.asarray(dense[0], np.float32)
    log(f"compile+run dense prefill[{len(prompt)}]: "
        f"{time.perf_counter() - t0:.1f}s")
    paged = np.asarray(paged, np.float32)
    check(paged.shape == dense.shape == (model.cfg.vocab_size,)
          and np.isfinite(paged).all(), "paged prefill logits finite")
    rel = float(np.linalg.norm(paged - dense) / np.linalg.norm(dense))
    log(f"reference: paged vs dense prefill logits, relative L2 "
        f"{rel:.4g}, top-1 {int(paged.argmax())} vs {int(dense.argmax())}")
    check(rel < LOGITS_TOL, f"paged prefill agrees with dense ({rel:.3g})")


# ------------------------------------------------------------- serve
async def serve(engine, vocab: int, seed: int) -> dict:
    """Online + shared-prefix offline traffic through AsyncEchoEngine;
    streams every token, aborts one request mid-stream, drains."""
    rng = np.random.default_rng(seed)

    def toks(n):
        return [int(t) for t in rng.integers(0, vocab, n)]

    rt = AsyncEchoEngine(engine)
    await rt.start()
    slo = SLO(ttft=1.0, tpot=0.1)
    plan = [(await rt.submit(toks(n), task_type="online",
                             max_new_tokens=ONLINE_NEW, slo=slo), ONLINE_NEW)
            for n in ONLINE_PROMPTS]
    victim = await rt.submit(toks(ABORT_PROMPT), task_type="online",
                             max_new_tokens=ABORT_NEW, slo=slo)
    doc = toks(DOC_LEN)
    plan += [(await rt.submit(doc + toks(QUESTION_LEN), task_type="offline",
                              max_new_tokens=OFFLINE_NEW), OFFLINE_NEW)
             for _ in range(N_QUESTIONS)]

    async def stream(handle, abort_after=None):
        n = 0
        async for _ in handle.tokens():
            n += 1
            if n == abort_after:
                await handle.abort()
        return n

    t0 = time.perf_counter()
    counts = await asyncio.gather(*(stream(h) for h, _ in plan),
                                  stream(victim, ABORT_AFTER))
    await rt.drain()
    wall = time.perf_counter() - t0
    for (h, want), n in zip(plan, counts):
        check(h.status is HandleStatus.FINISHED and n == want,
              f"request {h.rid} finished with {want} tokens "
              f"(status {h.status.value}, streamed {n})")
    n_victim = counts[-1]
    check(victim.status is HandleStatus.ABORTED
          and ABORT_AFTER <= n_victim < ABORT_NEW,
          f"request {victim.rid} aborted mid-stream "
          f"(status {victim.status.value}, streamed {n_victim})")
    leaks = rt.kv_leaks()
    hit_tokens = engine.bm.metrics.hit_blocks * engine.bm.block_size
    return {"finished": len(plan), "aborted": 1, "tokens_out": sum(counts),
            "prefix_hit_tokens": hit_tokens, "wall_s": wall,
            "steps": rt.stats.steps, "leaks": leaks,
            "dropped_callbacks": rt.events.dropped_callbacks}


def serve_phase(cfg, seed: int, **engine_kw) -> dict:
    """Build the engine through ``build_engine``, compile its steps,
    check it against the dense model, then serve."""
    t0 = time.perf_counter()
    engine = build_engine(cfg, ECHO, num_blocks=NUM_PAGES, seed=seed,
                          block_size=PAGE_SIZE, chunk_size=CHUNK,
                          max_pages_per_seq=MAX_PAGES_PER_SEQ,
                          max_running=MAX_RUNNING, clock="wall", **engine_kw)
    jax.block_until_ready(engine.runner.params)
    log(f"model {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.dtype}, {cfg.param_count / 1e9:.2f}e9 params (random, seed "
        f"{seed}); pool {NUM_PAGES} pages x {PAGE_SIZE} tokens; "
        f"attention {engine.runner.attn_impl}, tiles "
        f"{engine.runner.tuning}; built in {time.perf_counter() - t0:.1f}s")
    log("scheduler estimate: TimeModel.a100 preset (no v5e preset fitted); "
        "engine clock: wall")
    buckets = [1 << i for i in range(MAX_RUNNING.bit_length())]
    secs, kernel = compile_steps(engine.runner, buckets)
    for shape, s in secs.items():
        log(f"compile {shape}: {s:.1f}s")
    log(f"decode steps hold a Pallas kernel (tpu_custom_call): {kernel}")
    prompt = np.random.default_rng(seed + 1).integers(
        0, cfg.vocab_size, 64).tolist()
    reference_check(engine, prompt)
    compiles = []

    def on_compile(event, secs, **kw):
        if event == BACKEND_COMPILE_EVENT:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        out = asyncio.run(serve(engine, cfg.vocab_size, seed))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    out["decode_kernel"] = kernel
    log(f"compiles while serving: {len(compiles)} "
        f"({sum(compiles):.1f}s)")
    log(f"served: {out['finished']} finished + {out['aborted']} aborted "
        f"mid-stream, {out['tokens_out']} tokens streamed, "
        f"{out['prefix_hit_tokens']} prefix-hit tokens, {out['steps']} "
        f"steps in {out['wall_s']:.2f}s")
    leaks = out["leaks"]
    log("kv leaks: " + ("none" if not any(leaks.values()) else str(leaks)))
    log(f"event bus dropped callbacks: {out['dropped_callbacks']}")
    check(not any(leaks.values()), "no KV leaks after drain")
    check(out["dropped_callbacks"] == 0, "no subscriber raised")
    check(out["prefix_hit_tokens"] > 0, "offline backlog hit the prefix cache")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        log(f"no TPU found: jax.devices()[0] is {dev.platform} "
            f"({dev.device_kind}); nothing was run")
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"compile cache {compile_cache_dir()}")
    cfg = get_config(ARCH)
    kernel_check(cfg, args.seed)
    out = serve_phase(cfg, args.seed)
    check(out["decode_kernel"], "decode steps run the Pallas kernel")
    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    log(f"peak_bytes_in_use {peak} of bytes_limit {limit}")
    check(peak is not None and limit is not None and peak < limit,
          "peak device memory under the chip's HBM")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

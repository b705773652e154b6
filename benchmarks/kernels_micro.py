"""Kernel micro-benchmarks: CPU-dispatch wall time + hard numerics gate.

``us_per_call`` times the jitted path the engine actually runs on this
backend (the jnp ref oracles on CPU — what ``impl="auto"`` dispatches to);
on TPU the Pallas kernels compile natively and the same harness times
them. Every row also validates the Pallas kernel(s) for that shape in
interpret mode against ``kernels/ref.py`` — a mismatch is an error, not a
footnote: ``rows()`` raises ``KernelNumericsError`` and the CLI exits
nonzero, so CI cannot go green on silently-wrong kernels.

CLI:
    PYTHONPATH=src:. python benchmarks/kernels_micro.py --json out.json

``benchmarks/check_kernels.py`` gates the JSON against the committed
baseline (``benchmarks/baselines/kernels_micro.json``): per-kernel
``us_per_call`` ceilings plus the ``pallas_matches`` booleans.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.paged_attention import (paged_attention,
                                           paged_decode_attention)
from repro.kernels.ssd_scan import ssd_scan

RTOL = ATOL = 2e-4


class KernelNumericsError(AssertionError):
    """A Pallas kernel disagreed with its jnp oracle."""


def _time(fn, reps=10):
    fn()                                    # warm / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e6


def _one_layer(pool):
    """A (P, bs, Hkv, hd) pool as the one layer of a lane-layout stack, the
    form ``paged_decode_attention`` reads."""
    return pool.reshape(1, *pool.shape[:2], -1)


def _matches(got, want):
    return bool(np.allclose(np.asarray(got, np.float32),
                            np.asarray(want, np.float32),
                            rtol=RTOL, atol=ATOL))


def rows(strict: bool = True):
    """Returns [(name, us_per_call, "pallas_matches=..."), ...]. With
    ``strict`` (the default — including under ``benchmarks/run.py``), any
    pallas/oracle mismatch raises ``KernelNumericsError`` after all rows
    are measured, naming every offender."""
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 8)
    tune = ops.kernel_tuning()
    out = []

    # ---- paged decode: short-context online regime ----------------------
    b, hq, hkv, hd, p, bs, nblk = 8, 8, 2, 64, 64, 16, 16
    q = jax.random.normal(ks[0], (b, hq, hd))
    kp = jax.random.normal(ks[1], (p, bs, hkv, hd))
    vp = jax.random.normal(ks[2], (p, bs, hkv, hd))
    bt = jax.random.randint(ks[3], (b, nblk), 0, p)
    cl = jnp.full((b,), nblk * bs, jnp.int32)
    jit_ref = jax.jit(ref.ref_paged_attention)
    want = jit_ref(q, kp, vp, bt, cl)
    us = _time(lambda: jit_ref(q, kp, vp, bt, cl))
    ok = _matches(paged_attention(q, kp, vp, bt, cl, interpret=True), want)
    out.append(("kernel.paged_attention", us, f"pallas_matches={ok}"))
    ok = _matches(
        paged_decode_attention(q, _one_layer(kp), _one_layer(vp), bt, cl, 0,
                               pages_per_block=tune.pages_per_block,
                               interpret=True), want)
    out.append(("kernel.paged_decode_attention", us, f"pallas_matches={ok}"))

    # ---- paged decode: long ragged contexts -----------------------------
    b2, nblk2, p2 = 4, 64, 96
    q2 = jax.random.normal(ks[4], (b2, hq, hd))
    kp2 = jax.random.normal(ks[5], (p2, bs, hkv, hd))
    vp2 = jax.random.normal(ks[6], (p2, bs, hkv, hd))
    bt2 = jax.random.randint(ks[7], (b2, nblk2), 0, p2)
    cl2 = jnp.asarray([nblk2 * bs, 40, 520, 7], jnp.int32)   # ragged batch
    want2 = jit_ref(q2, kp2, vp2, bt2, cl2)
    us = _time(lambda: jit_ref(q2, kp2, vp2, bt2, cl2))
    ok = _matches(
        paged_decode_attention(q2, _one_layer(kp2), _one_layer(vp2), bt2,
                               cl2, 0, pages_per_block=tune.pages_per_block,
                               interpret=True), want2)
    out.append(("kernel.paged_decode_attention_long", us,
                f"pallas_matches={ok}"))

    # ---- chunked prefill: fused epilogue, tuned tiles -------------------
    sc, t = 128, 512
    qc = jax.random.normal(ks[4], (sc, hq, hd))
    kc = jax.random.normal(ks[5], (t, hkv, hd))
    vc = jax.random.normal(ks[6], (t, hkv, hd))
    jit_ref2 = jax.jit(ref.ref_chunked_prefill_attention)
    want = jit_ref2(qc, kc, vc, 256)
    us = _time(lambda: jit_ref2(qc, kc, vc, 256))
    ok = _matches(
        chunked_prefill_attention(qc, kc, vc, 256, blk_q=tune.blk_q,
                                  blk_k=tune.blk_k, interpret=True), want)
    out.append(("kernel.chunked_prefill", us, f"pallas_matches={ok}"))

    # ---- chunked prefill: non-divisible chunk/block shapes --------------
    sc3, t3, ctx3 = 100, 420, 250
    q3 = jax.random.normal(ks[0], (sc3, hq, hd))
    k3 = jax.random.normal(ks[1], (t3, hkv, hd))
    v3 = jax.random.normal(ks[2], (t3, hkv, hd))
    want = jit_ref2(q3, k3, v3, ctx3)
    us = _time(lambda: jit_ref2(q3, k3, v3, ctx3))
    ok = _matches(
        chunked_prefill_attention(q3, k3, v3, ctx3, blk_q=tune.blk_q,
                                  blk_k=tune.blk_k, interpret=True), want)
    out.append(("kernel.chunked_prefill_ragged", us, f"pallas_matches={ok}"))

    # ---- SSD chunk scan -------------------------------------------------
    bz, s, h, pd, n = 2, 256, 4, 32, 16
    x = jax.random.normal(ks[7], (bz, s, h, pd))
    dta = -jax.nn.softplus(jax.random.normal(ks[0], (bz, s, h)))
    bm = jax.random.normal(ks[1], (bz, s, n))
    cm = jax.random.normal(ks[2], (bz, s, n))
    jit_ref3 = jax.jit(ref.ref_ssd_sequential)
    yr, fr = jit_ref3(x, dta, bm, cm)
    us = _time(lambda: jit_ref3(x, dta, bm, cm))
    y, fs = ssd_scan(x, dta, bm, cm, chunk=64, interpret=True)
    ok = _matches(y, yr) and _matches(fs, fr)
    out.append(("kernel.ssd_scan", us, f"pallas_matches={ok}"))

    # ---- RG-LRU scan ----------------------------------------------------
    from repro.kernels.rglru_scan import rglru_scan
    a = jax.nn.sigmoid(jax.random.normal(ks[3], (2, 256, 128)))
    bv = jax.random.normal(ks[4], (2, 256, 128))
    jit_ref4 = jax.jit(ref.ref_rglru_scan)
    want = jit_ref4(a, bv)
    us = _time(lambda: jit_ref4(a, bv))
    ok = _matches(rglru_scan(a, bv, chunk=64, interpret=True), want)
    out.append(("kernel.rglru_scan", us, f"pallas_matches={ok}"))

    bad = [name for name, _, d in out if d != "pallas_matches=True"]
    if strict and bad:
        raise KernelNumericsError(
            f"pallas kernels disagree with kernels/ref.py: {', '.join(bad)}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write {name: {us_per_call, pallas_matches}} here "
                         "(written even on a numerics failure, for triage)")
    args = ap.parse_args()
    out = rows(strict=False)
    if args.json:
        payload = {name: {"us_per_call": round(us, 1),
                          "pallas_matches": d == "pallas_matches=True"}
                   for name, us, d in out}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
    print("name,us_per_call,derived")
    for name, us, d in out:
        print(f"{name},{us:.1f},{d}")
    bad = [name for name, _, d in out if d != "pallas_matches=True"]
    if bad:
        raise SystemExit(
            f"kernel numerics FAILED: {', '.join(bad)} "
            "(pallas kernel disagrees with kernels/ref.py)")


if __name__ == "__main__":
    main()

"""Pallas TPU chunked-prefill flash-attention kernel.

Computes one prefill chunk's queries against the resident prefix + the
chunk itself (Sarathi-style chunked prefill — the batching substrate Echo
schedules over). Causal block-skipping: K blocks entirely above the
diagonal are never brought into VMEM, and blocks entirely *below* the
causal frontier take a mask-free fast path (only diagonal-straddling
blocks pay the iota/where).

The epilogue is fused: the final grid step normalizes by the running
softmax denominator, zeroes padded query rows, and casts to the output
dtype inside the kernel — no separate normalization/cleanup pass over the
output. Non-divisible shapes are handled by the wrapper padding q/k/v up
to the block grid (padded K rows sit past ctx+Sc, so causality masks
them; padded Q rows are zeroed by the epilogue and sliced off).

Grid: (q_head, q_blocks, k_blocks); running-softmax scratch in VMEM.
q/k/v are read through the lane-sliced ``(S, H*hd)`` view of
``paged_attention.lane_view``: one ``(blk, hd)`` block per head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import lane_view

NEG_INF = -1e30


def _kernel(ctx_ref,                                  # scalar prefetch
            q_ref, k_ref, v_ref, out_ref,
            m_ref, l_ref, acc_ref,
            *, blk_q: int, blk_k: int, scale: float, group: int,
            sc_valid: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    ctx = ctx_ref[0]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute pos of q row r: ctx + iq*blk_q + r ; K col c: ik*blk_k + c
    # block is live unless its first col exceeds the last row's position;
    # it is mask-free when its last col can't exceed the first row's
    first_q_pos = ctx + iq * blk_q
    last_q_pos = first_q_pos + blk_q - 1
    live = ik * blk_k <= last_q_pos
    full = (ik + 1) * blk_k - 1 <= first_q_pos

    def _accumulate(s):
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def _scores():
        q = q_ref[...].astype(jnp.float32)            # (blk_q, hd)
        k = k_ref[...].astype(jnp.float32)            # (blk_k, hd)
        return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32) * scale

    @pl.when(jnp.logical_and(live, full))
    def _compute_unmasked():                          # below the diagonal
        _accumulate(_scores())

    @pl.when(jnp.logical_and(live, jnp.logical_not(full)))
    def _compute_masked():                            # straddles the diagonal
        s = _scores()
        rows = ctx + iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _accumulate(jnp.where(cols <= rows, s, NEG_INF))

    # fused epilogue: normalize + zero padded q rows + cast, in one write
    @pl.when(ik == nk - 1)
    def _write():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
        rows = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        out = jnp.where(rows < sc_valid, out, 0.0)
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("blk_q", "blk_k", "interpret"))
def chunked_prefill_attention(q, k, v, ctx_len, *, blk_q: int = 128,
                              blk_k: int = 128, interpret: bool = False):
    """q (Sc,Hq,hd); k/v (T,Hkv,hd); ctx_len scalar int32 -> (Sc,Hq,hd).

    Rows of k/v beyond ctx_len + Sc are padding (masked by causality).
    Sc and T need not divide the block sizes: inputs are zero-padded up to
    the (blk_q, blk_k) grid and the fused epilogue zeroes the padded rows
    before the wrapper slices them off.
    """
    sc, hq, hd = q.shape
    t, hkv, _ = k.shape
    g = hq // hkv
    scale = 1.0 / (hd ** 0.5)
    ctx = jnp.asarray(ctx_len, jnp.int32).reshape(1)

    blk_q = min(blk_q, max(sc, 1))
    blk_k = min(blk_k, max(t, 1))
    sc_p = pl.cdiv(sc, blk_q) * blk_q
    t_p = pl.cdiv(t, blk_k) * blk_k
    if sc_p != sc:
        q = jnp.pad(q, ((0, sc_p - sc), (0, 0), (0, 0)))
    if t_p != t:
        # padded K rows land at positions >= T >= ctx + Sc, above every
        # query's causal frontier — masked like any other future token
        k = jnp.pad(k, ((0, t_p - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, t_p - t), (0, 0), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hq, sc_p // blk_q, t_p // blk_k),
        in_specs=[
            pl.BlockSpec((blk_q, hd), lambda h, iq, ik, c: (iq, h)),
            pl.BlockSpec((blk_k, hd), lambda h, iq, ik, c: (ik, h // g)),
            pl.BlockSpec((blk_k, hd), lambda h, iq, ik, c: (ik, h // g)),
        ],
        out_specs=pl.BlockSpec((blk_q, hd), lambda h, iq, ik, c: (iq, h)),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, blk_q=blk_q, blk_k=blk_k, scale=scale,
                          group=g, sc_valid=sc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((sc_p, hq * hd), q.dtype),
        interpret=interpret,
    )(ctx, lane_view(q, interpret), lane_view(k, interpret),
      lane_view(v, interpret))
    out = out.reshape(sc_p, hq, hd)
    return out[:sc] if sc_p != sc else out

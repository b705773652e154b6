"""Pallas TPU paged-attention decode kernels.

TPU adaptation of vLLM's PagedAttention: the page indirection lives in the
grid's scalar-prefetched block table — each grid step DMAs one whole KV page
HBM->VMEM via BlockSpec index_map — so the MXU inner loop is dense flash
attention over VMEM tiles (no per-element gather).

Two schedules over the page dimension:

* ``paged_attention`` (legacy): grid (batch, kv_head, num_pages) — one
  running-softmax state walks every page of the max context serially, so
  a single long sequence bounds the whole launch.
* ``paged_attention_splitk`` (flash-decoding): grid (batch, kv_head,
  num_splits, pages_per_split) — the page dimension is partitioned across
  a dedicated grid axis. Each partition carries its own (m, l, acc)
  running-softmax state over at most ``pages_per_split`` pages and writes
  an *unnormalized* partial (acc, m, l); a lightweight cross-partition
  log-sum-exp merge (fused into the same jit) produces the final output.
  Partitions are independent, so on hardware the split axis can fill idle
  cores/lanes for the long-context offline regime, and partitions whose
  pages lie entirely past ``ctx_len`` skip compute (ragged batches stop
  paying for the max context).

Both read the pool through a lane-sliced view: the wrapper reshapes the
``(P, bs, Hkv, hd)`` pool to ``(P, bs, Hkv*hd)`` (free for a contiguous
array) and each grid step takes the ``(1, bs, hd)`` block at lane-block
``h``. Mosaic tiles the last two block dims by (8, 128) unless they span
the whole array dim, so a ``(.., 1, hd)`` head block is refused; the
view only needs ``hd % 128 == 0`` (or a single KV head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def lane_view(x, interpret: bool):
    """(..., H, hd) -> (..., H*hd), the layout whose ``(.., hd)`` head
    blocks the TPU compiler accepts. Raises on a compiled (non-interpret)
    call whose head_dim the view cannot tile — never a silent fallback."""
    *lead, h, hd = x.shape
    if not interpret and h > 1 and hd % LANES:
        raise ValueError(
            f"TPU attention kernel cannot tile array of shape {x.shape}: "
            f"a ({hd},)-lane head block of a {h * hd}-lane row needs "
            f"head_dim % {LANES} == 0 (or one head)")
    return x.reshape(*lead, h * hd)


def _kernel(block_tables_ref, ctx_lens_ref,          # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref,                     # VMEM blocks
            out_ref,
            m_ref, l_ref, acc_ref,                   # VMEM scratch
            *, page_size: int, scale: float):
    b = pl.program_id(0)
    i = pl.program_id(2)
    npages = pl.num_programs(2)
    ctx = ctx_lens_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * page_size < ctx)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, hd)
        k = k_ref[0].astype(jnp.float32)             # (bs, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tok = i * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tok < ctx, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == npages - 1)
    def _write():
        out_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                         ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                    *, interpret: bool = False):
    """q (B,Hq,hd); k/v_pages (P,bs,Hkv,hd); block_tables (B,nblk) int32;
    ctx_lens (B,) int32 -> (B,Hq,hd)."""
    b, hq, hd = q.shape
    _, page_size, hkv, _ = k_pages.shape
    g = hq // hkv
    nblk = block_tables.shape[1]
    qg = q.reshape(b, hkv, g, hd)
    scale = 1.0 / (hd ** 0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bb, h, i, bt, cl: (bb, h, 0, 0)),
            pl.BlockSpec((1, page_size, hd),
                         lambda bb, h, i, bt, cl: (bt[bb, i], 0, h)),
            pl.BlockSpec((1, page_size, hd),
                         lambda bb, h, i, bt, cl: (bt[bb, i], 0, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda bb, h, i, bt, cl: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        interpret=interpret,
    )(block_tables, ctx_lens, qg, lane_view(k_pages, interpret),
      lane_view(v_pages, interpret))
    return out.reshape(b, hq, hd)


def _splitk_kernel(block_tables_ref, ctx_lens_ref,    # scalar prefetch (SMEM)
                   q_ref, k_ref, v_ref,               # VMEM blocks
                   o_ref, m_out_ref, l_out_ref,       # partial outputs
                   m_ref, l_ref, acc_ref,             # VMEM scratch
                   *, page_size: int, scale: float, pages_per_split: int,
                   nblk: int):
    b = pl.program_id(0)
    s_idx = pl.program_id(2)
    j = pl.program_id(3)
    i = s_idx * pages_per_split + j                   # absolute page index
    ctx = ctx_lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # early exit: pages past the ragged ctx (or past the table on the
    # final, possibly short, split) never touch the MXU
    @pl.when(jnp.logical_and(i < nblk, i * page_size < ctx))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (G, hd)
        k = k_ref[0].astype(jnp.float32)              # (bs, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tok = i * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tok < ctx, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # partition epilogue: write the *unnormalized* partial — the
    # cross-partition LSE merge divides exactly once, after combining
    @pl.when(j == pages_per_split - 1)
    def _write():
        o_ref[0, 0, 0] = acc_ref[...]
        m_out_ref[0, 0, 0] = m_ref[...]
        l_out_ref[0, 0, 0] = l_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("pages_per_split", "interpret"))
def paged_attention_splitk(q, k_pages, v_pages, block_tables, ctx_lens,
                           *, pages_per_split: int = 4,
                           interpret: bool = False):
    """Split-K / flash-decoding schedule. Same contract as
    ``paged_attention``: q (B,Hq,hd); k/v_pages (P,bs,Hkv,hd);
    block_tables (B,nblk) int32; ctx_lens (B,) int32 -> (B,Hq,hd).

    The page dimension is tiled into ``ceil(nblk / pages_per_split)``
    independent partitions, each producing an unnormalized (acc, m, l)
    triple; the final output is their log-sum-exp merge. A partition whose
    pages all lie past ``ctx_len`` contributes (0, -inf, 0) — exactly the
    identity of the merge — so ragged batches cost only their live pages.
    """
    b, hq, hd = q.shape
    _, page_size, hkv, _ = k_pages.shape
    g = hq // hkv
    nblk = block_tables.shape[1]
    pps = max(1, min(pages_per_split, nblk))
    nsplit = pl.cdiv(nblk, pps)
    qg = q.reshape(b, hkv, g, hd)
    scale = 1.0 / (hd ** 0.5)

    def _page(bb, h, s, j, bt, cl):
        # clamp the tail split's overhang onto a valid table entry; the
        # kernel's i < nblk guard skips its compute anyway
        return bt[bb, jnp.minimum(s * pps + j, nblk - 1)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nsplit, pps),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda bb, h, s, j, bt, cl: (bb, h, 0, 0)),
            pl.BlockSpec((1, page_size, hd),
                         lambda bb, h, s, j, bt, cl:
                         (_page(bb, h, s, j, bt, cl), 0, h)),
            pl.BlockSpec((1, page_size, hd),
                         lambda bb, h, s, j, bt, cl:
                         (_page(bb, h, s, j, bt, cl), 0, h)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, g, hd),
                         lambda bb, h, s, j, bt, cl: (bb, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda bb, h, s, j, bt, cl: (bb, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda bb, h, s, j, bt, cl: (bb, h, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        functools.partial(_splitk_kernel, page_size=page_size, scale=scale,
                          pages_per_split=pps, nblk=nblk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, nsplit, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, nsplit, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, nsplit, g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_tables, ctx_lens, qg, lane_view(k_pages, interpret),
      lane_view(v_pages, interpret))

    # cross-partition combine: one exp re-base per partition, one divide
    # total. Empty partitions (m=-inf, l=0, acc=0) drop out of both sums.
    m_max = jnp.max(m_part, axis=2, keepdims=True)            # (B,K,1,G,1)
    w = jnp.exp(m_part - jnp.maximum(m_max, NEG_INF))         # (B,K,S,G,1)
    l_tot = jnp.sum(w * l_part, axis=2)                       # (B,K,G,1)
    o_tot = jnp.sum(w * o_part, axis=2)                       # (B,K,G,hd)
    out = (o_tot / jnp.maximum(l_tot, 1e-20)).astype(q.dtype)
    return out.reshape(b, hq, hd)

"""Pallas TPU paged-attention decode kernels.

TPU adaptation of vLLM's PagedAttention: the page indirection lives in a
scalar-prefetched block table, so the MXU inner loop is dense flash
attention over VMEM tiles (no per-element gather).

Two schedules over the page dimension:

* ``paged_attention`` (legacy): grid (batch, kv_head, num_pages) — each
  grid step takes one page of one KV head through a BlockSpec index_map,
  and every row walks every table slot, live or not.
* ``paged_decode_attention``: grid (batch,) — a grid step takes one row
  and every KV head, and loops over the row's live pages only, a block of
  ``pages_per_block`` pages at a time. Each page is one contiguous
  ``(bs, Hkv*hd)`` slab copied by one DMA into a double buffer, the next
  block's copies (or the next row's first block) in flight while the
  current one is computed. A running softmax per KV head is kept in f32
  VMEM across the row's blocks and normalised after its last block.

Both read pages in the lane layout ``(.., bs, Hkv*hd)``, a head's keys the
lanes ``[h*hd, (h+1)*hd)``. Mosaic tiles the last two dims by (8, 128)
unless they span the whole array dim, so a head's lanes only tile when
``hd % 128 == 0`` (or there is one KV head). The TPU stores an array
tiled over its last two dims, so ``(bs, Hkv, hd)`` -> ``(bs, Hkv*hd)`` is a
relayout, a copy of every byte, not a free reshape. ``paged_decode_attention``
therefore takes the serving pool as it is kept, already in the lane layout
and stacked over layers, ``(L, P, bs, Hkv*hd)``, with the layer to read as a
scalar; the legacy kernel lane-views a ``(P, bs, Hkv, hd)`` pool itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _check_lanes(shape, h: int, hd: int, interpret: bool) -> None:
    """Raise on a compiled (non-interpret) call whose ``(.., hd)`` head
    blocks of an ``h * hd``-lane row the TPU cannot tile — never a silent
    fallback. ``shape`` names the array in the message."""
    if not interpret and h > 1 and hd % LANES:
        raise ValueError(
            f"TPU attention kernel cannot tile array of shape {tuple(shape)}: "
            f"a ({hd},)-lane head block of a {h * hd}-lane row needs "
            f"head_dim % {LANES} == 0 (or one head)")


def lane_view(x, interpret: bool):
    """(..., H, hd) -> (..., H*hd), the layout whose ``(.., hd)`` head
    blocks the TPU compiler accepts. On the TPU this is a relayout copy of
    ``x`` (see the module docstring), so it suits operands made per call,
    not the page pool."""
    *lead, h, hd = x.shape
    _check_lanes(x.shape, h, hd, interpret)
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


def _decode_kernel(block_tables_ref, ctx_lens_ref,   # scalar prefetch (SMEM)
                   layer_ref,
                   q_ref, k_hbm, v_hbm,               # q block; pools in HBM
                   o_ref,
                   k_buf, v_buf, sems, slot_ref,      # page buffers, DMA state
                   m_ref, l_ref, acc_ref,             # running softmax (f32)
                   *, page_size: int, pages_per_block: int, nblk: int,
                   scale: float):
    """Grid step ``b``: row ``b``'s live pages, every KV head, a block of
    ``pages_per_block`` pages per loop iteration. Each page of the lane
    view is one contiguous ``(bs, Hkv*hd)`` slab, copied HBM->VMEM by one
    DMA into a double buffer. The next block's copies start before the
    current block is computed, the next row's first block during this
    row's last, so a row's first copy is hidden behind the row before it.
    ``slot_ref`` carries the buffer slot of the next row's first block
    across grid steps (the grid runs in order on one TensorCore). The
    pools hold every layer; pages are copied from layer ``layer_ref[0]``."""
    b = pl.program_id(0)
    nrows = pl.num_programs(0)
    ppb, bs = pages_per_block, page_size
    blk_tok = ppb * bs
    hkv, g, hd = q_ref.shape[1:]
    layer = layer_ref[0]

    def live_pages(r):
        return jnp.minimum(pl.cdiv(ctx_lens_ref[r], bs), nblk)

    def copy_block(r, j, slot, start: bool):
        """Start (or wait for) the copies of row ``r``'s block ``j``: its
        live pages only, K and V of every head in one DMA a page each."""

        def page(i, carry):
            pid = block_tables_ref[r * nblk + j * ppb + i]
            for n, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                c = pltpu.make_async_copy(src.at[layer, pid], dst.at[slot, i],
                                          sems.at[n, slot])
                if start:
                    c.start()
                else:
                    c.wait()
            return carry

        n = jnp.minimum(live_pages(r) - j * ppb, ppb)
        jax.lax.fori_loop(0, n, page, 0)

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        copy_block(0, 0, 0, start=True)

    ctx = ctx_lens_ref[b]
    nb = pl.cdiv(live_pages(b), ppb)                  # this row's live blocks
    s0 = slot_ref[0]

    def prefetch_after(j):
        """Start what follows row b's block j: its block j+1, else the
        next row's first block."""
        more = j + 1 < nb

        @pl.when(more | (b + 1 < nrows))
        def _():
            copy_block(jnp.where(more, b, b + 1), jnp.where(more, j + 1, 0),
                       (s0 + j + 1) % 2, start=True)

    def block(j, carry):
        slot = (s0 + j) % 2
        prefetch_after(j)
        copy_block(b, j, slot, start=False)
        # tokens past ctx: a page's tail, buffer slots this row left stale
        iota = jax.lax.broadcasted_iota
        live_s = j * blk_tok + iota(jnp.int32, (g, blk_tok), 1) < ctx
        live_v = j * blk_tok + iota(jnp.int32, (blk_tok, hd), 0) < ctx
        for h in range(hkv):
            lanes = pl.ds(h * hd, hd)
            k = k_buf[slot, :, :, lanes].reshape(blk_tok, hd)
            v = v_buf[slot, :, :, lanes].reshape(blk_tok, hd)
            s = jax.lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(live_s, s, NEG_INF)
            v = jnp.where(live_v, v, jnp.zeros_like(v))
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + _pv(p, v)
            m_ref[h] = m_new
        return carry

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(nb == 0)
    def _empty():                 # a padded row copies nothing, writes 0
        prefetch_after(-1)

    jax.lax.fori_loop(0, nb, block, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                ).astype(o_ref.dtype)
    slot_ref[0] = (s0 + nb) % 2


def _pv(p, v):
    """p (G, T) f32 times v (T, hd) with f32 accumulation, p kept at f32.
    A bf16 v enters the MXU as stored: p is split into three bf16 terms
    whose sum is p exactly, so each product is exact in f32."""
    if v.dtype == jnp.float32:
        return jax.lax.dot(p, v, preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype)
    r = p - hi.astype(jnp.float32)
    mid = r.astype(v.dtype)
    lo = (r - mid.astype(jnp.float32)).astype(v.dtype)
    return sum(jax.lax.dot(t, v, preferred_element_type=jnp.float32)
               for t in (hi, mid, lo))


@functools.partial(jax.jit,
                   static_argnames=("pages_per_block", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, ctx_lens, layer,
                           *, pages_per_block: int = 4,
                           interpret: bool = False):
    """Decode attention over a row's live pages of one layer. q (B,Hq,hd);
    k/v_pages (L,P,bs,Hkv*hd), the pool of every layer in the lane layout;
    block_tables (B,nblk) int32; ctx_lens (B,) int32; layer, the layer to
    read (int32 scalar) -> (B,Hq,hd).

    Grid ``(B,)``; row ``b`` copies and computes ``cdiv(ctx, ppb * bs)``
    blocks of ``ppb = pages_per_block`` pages and no page past its last
    live one; a row with ``ctx == 0`` copies nothing and writes zeros.
    The pools stay in HBM and are only read: page ``pid`` of the layer is
    one DMA of ``k_pages[layer, pid]``.
    """
    b, hq, hd = q.shape
    _, _, page_size, lanes = k_pages.shape
    hkv = lanes // hd
    _check_lanes(k_pages.shape, hkv, hd, interpret)
    g = hq // hkv
    nblk = block_tables.shape[1]
    ppb = max(1, min(pages_per_block, nblk))
    qg = q.reshape(b, hkv, g, hd)
    row = pl.BlockSpec((1, hkv, g, hd), lambda bb, bt, cl, ly: (bb, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, lanes), k_pages.dtype),
            pltpu.VMEM((2, ppb, page_size, lanes), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size,
                          pages_per_block=ppb, nblk=nblk,
                          scale=1.0 / (hd ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.reshape(-1), ctx_lens,
      jnp.asarray(layer, jnp.int32).reshape(1), qg, k_pages, v_pages)
    return out.reshape(b, hq, hd)

"""Paged-KV execution path for the serving engine (attention families).

KV lives in a global page pool; requests reference pages through block
tables (the BlockManager owns the indirection). On TPU the attention
inner loops are the Pallas kernels in repro.kernels; on CPU the jnp ref
oracles execute the same layout. Prefill is chunked (Sarathi-style) and
decode is batched — the two batch shapes Echo's scheduler composes.

The pool is updated in place, never copied whole:

* Each segment keeps one K and one V array per unit kind, stacked over its
  layers in the decode kernel's lane layout, ``(n_layers, P, bs, Hkv*hd)``.
  On the TPU a ``(bs, Hkv, hd)`` -> ``(bs, Hkv*hd)`` reshape is a relayout
  copy, so no call reshapes the pool; the host KV tier's payloads stay
  ``(n_layers, bs, Hkv, hd)``, reshaped one page at a time.
* The prefill and decode programs donate the pool: ``(params, ..., pages)
  -> (out, pages)`` with the output pool aliasing the input.
* The layer scan carries the stacked pool (``(h, pages)``) beside the
  layer index instead of scanning it as xs/ys, which would slice each
  layer out and write it back. A layer scatters its new K/V rows into the
  carried stack at ``[layer, page, slot]``; decode attention reads the
  stack at that layer and prefill gathers ``stack[layer, pages]``.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_io import io_spec_for_model
from repro.kernels import ops as kops
from repro.models import transformer as tfm
from repro.models.common import rms_norm, rope_angles, swiglu
from repro.models.model import Model
from repro.models.moe import moe_apply
from repro.obs.spans import span


def _write_pages(stack, layer, flat_idx, rows):
    """stack (L,P,bs,Hkv*hd); rows (N,Hkv,hd) land at ``[layer, flat_idx]``
    with flat_idx (N,) into P*bs; entries >= P*bs are dropped. NOTE: the
    drop sentinel must be positive-OOB — JAX scatter *wraps* negative
    indices instead of dropping them."""
    _, _, bs, lanes = stack.shape
    rows = rows.reshape(rows.shape[0], lanes).astype(stack.dtype)
    return stack.at[layer, flat_idx // bs, flat_idx % bs].set(rows,
                                                              mode="drop")


def _gather_pages(stack, layer, block_table, hd):
    """stack (L,P,bs,Hkv*hd); block_table (nblk,) -> the layer's pages
    (nblk*bs, Hkv, hd)."""
    _, _, bs, lanes = stack.shape
    pages = stack[layer, block_table]                   # (nblk, bs, lanes)
    return pages.reshape(block_table.shape[0] * bs, lanes // hd, hd)


def _attn_prefill_paged(p, cfg, x, cos, sin, k_pages, v_pages, layer,
                        block_table, ctx_len, chunk_len, impl="auto",
                        preset=None):
    """x (1,Sc,d). Writes chunk KV into the layer's pages, attends vs
    prefix+chunk."""
    from repro.models.attention import _qkv
    sc = x.shape[1]
    q, k, v = _qkv(p, cfg, x, cos, sin)              # (1,Sc,H*,hd)
    ar = jnp.arange(sc)
    pos = ctx_len + ar
    bs = k_pages.shape[2]
    oob = k_pages.shape[1] * bs                  # positive-OOB drop sentinel
    idx = block_table[pos // bs] * bs + pos % bs
    idx = jnp.where(ar < chunk_len, idx, oob)
    k_pages = _write_pages(k_pages, layer, idx, k[0])
    v_pages = _write_pages(v_pages, layer, idx, v[0])
    hd = q.shape[-1]
    kk = _gather_pages(k_pages, layer, block_table, hd)
    vv = _gather_pages(v_pages, layer, block_table, hd)
    out = kops.chunked_prefill_attention(q[0], kk, vv, ctx_len, impl=impl,
                                         preset=preset)
    out = jnp.einsum("shk,hkd->sd", out, p["wo"])[None]
    return out, k_pages, v_pages


def _attn_decode_paged(p, cfg, x, cos, sin, k_pages, v_pages, layer,
                       block_tables, pos, impl="auto", preset=None):
    """x (B,1,d); block_tables (B,nblk); pos (B,). ctx = pos + 1."""
    from repro.models.attention import _qkv
    b = x.shape[0]
    q, k, v = _qkv(p, cfg, x, cos, sin)
    bs = k_pages.shape[2]
    oob = k_pages.shape[1] * bs                  # positive-OOB drop sentinel
    bidx = jnp.arange(b)
    safe_pos = jnp.maximum(pos, 0)
    flat_idx = block_tables[bidx, safe_pos // bs] * bs + safe_pos % bs
    flat_idx = jnp.where(pos >= 0, flat_idx, oob)     # padded rows: drop
    k_pages = _write_pages(k_pages, layer, flat_idx, k[:, 0])
    v_pages = _write_pages(v_pages, layer, flat_idx, v[:, 0])
    out = kops.paged_attention(q[:, 0], k_pages, v_pages, block_tables,
                               pos + 1, layer, impl=impl, preset=preset)
    out = jnp.einsum("bhk,hkd->bd", out, p["wo"])[:, None]
    return out, k_pages, v_pages


def _block_paged(kind, p, cfg, x, rope, pages, layer, attn_fn):
    cos, sin = rope
    h, kp, vp = attn_fn(p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps),
                        cos, sin, pages["k"], pages["v"], layer)
    x = x + h
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + (swiglu(p["mlp"], h2) if kind == "attn"
             else moe_apply(p["moe"], cfg, h2))
    return x, {"k": kp, "v": vp}


class PagedRunner:
    """Owns the page pool and the jitted paged prefill/decode callables."""

    def __init__(self, model: Model, params, num_pages: int, page_size: int,
                 max_pages_per_seq: int, chunk_size: int,
                 attn_impl: str = "auto", kernel_profile: Optional[str] = None):
        cfg = model.cfg
        kinds = set(cfg.attn_layers)
        if not kinds <= {"attn", "moe"}:
            raise NotImplementedError(
                f"paged engine supports attention families, got {kinds}; "
                "SSM/hybrid use state-snapshot caching (see DESIGN.md)")
        self.model = model
        self.params = params
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_pages = max_pages_per_seq
        self.chunk_size = chunk_size
        # attention kernel dispatch: "auto" runs the jnp oracles on CPU and
        # the Pallas paged_decode_attention path on accelerators;
        # "ref"/"pallas"/"paged_decode_attention" force one. kernel_profile picks the block-size tuning table
        # (None resolves by device kind — see repro.kernels.ops).
        self.attn_impl = attn_impl
        self.kernel_profile = kernel_profile
        self.tuning = kops.kernel_tuning(kernel_profile)
        self.io = io_spec_for_model(model)   # paged: per-token KV payload
        dt = model.dtype
        # the decode kernel's lane layout, stacked over a segment's layers
        shp = (num_pages, page_size, cfg.num_kv_heads * cfg.head_dim)
        self.pages = []
        for stype, unit, n in tfm.segments(cfg):
            seg = tuple({"k": jnp.zeros((n,) + shp, dt),
                         "v": jnp.zeros((n,) + shp, dt)} for _ in unit)
            self.pages.append(seg)
        # every program donates the pool, so XLA updates it in place: a
        # model call writes its new rows, a restore its page, and none
        # copies the pool
        self._prefill_jit = jax.jit(self._prefill_impl,
                                    donate_argnames="pages")
        self._decode_jit = jax.jit(self._decode_impl, donate_argnames="pages")
        self._write_block_jit = jax.jit(self._write_block_impl,
                                        donate_argnums=0)

    # ------------------------------------------------------------- impls
    def _rope_for(self, positions):
        cfg = self.model.cfg
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return rope_angles(positions, cfg.head_dim, cfg.rope_theta,
                           cfg.mrope_sections)

    def _run_stack(self, params, h, rope, pages, attn_fn):
        """Every layer over ``h``. A scanned segment carries its stacked
        pool through the scan beside ``h`` and takes the layer index with
        the layer's params, so each layer updates the stack in place."""
        cfg = self.model.cfg
        new_pages = []
        for (stype, unit, n), seg_p, seg_pg in zip(
                tfm.segments(cfg), params["layers"], pages):
            def layer_step(x, seg_pg, p_slice, layer, unit=unit):
                outs = []
                for kind, p_k, pg_k in zip(unit, p_slice, seg_pg):
                    x, pg = _block_paged(kind, p_k, cfg, x, rope, pg_k,
                                         layer, attn_fn)
                    outs.append(pg)
                return x, tuple(outs)

            if stype == "scan":
                def body(carry, xs, layer_step=layer_step):
                    return layer_step(*carry, *xs), None
                (h, seg_pg), _ = jax.lax.scan(body, (h, seg_pg),
                                              (seg_p, jnp.arange(n)))
            else:
                h, seg_pg = layer_step(h, seg_pg, seg_p, 0)
            new_pages.append(seg_pg)
        return h, new_pages

    def _prefill_impl(self, params, tokens, ctx_len, chunk_len, block_table,
                      pages):
        cfg = self.model.cfg
        sc = tokens.shape[0]
        positions = (ctx_len + jnp.arange(sc))[None]                  # (1,Sc)
        rope = self._rope_for(positions)
        h = jnp.take(params["embed"], tokens[None], axis=0)
        attn_fn = (lambda p, c, x, cos, sin, kp, vp, layer: _attn_prefill_paged(
            p, c, x, cos, sin, kp, vp, layer, block_table, ctx_len, chunk_len,
            impl=self.attn_impl, preset=self.kernel_profile))
        h, pages = self._run_stack(params, h, rope, pages, attn_fn)
        idx = jnp.maximum(chunk_len - 1, 0)
        h_last = jax.lax.dynamic_index_in_dim(h[0], idx, 0, keepdims=False)
        logits = self._final_logits(params, h_last[None])
        return logits[0], pages

    def _decode_impl(self, params, tokens, block_tables, pos, pages):
        positions = jnp.maximum(pos, 0)[:, None]
        rope = self._rope_for(positions)
        h = jnp.take(params["embed"], tokens[:, None], axis=0)
        attn_fn = (lambda p, c, x, cos, sin, kp, vp, layer: _attn_decode_paged(
            p, c, x, cos, sin, kp, vp, layer, block_tables, pos,
            impl=self.attn_impl, preset=self.kernel_profile))
        h, pages = self._run_stack(params, h, rope, pages, attn_fn)
        logits = self._final_logits(params, h[:, 0])
        return logits, pages

    def _final_logits(self, params, h):
        cfg = self.model.cfg
        h = rms_norm(h, params["final_ln"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        return h @ w

    def release(self, rid: int) -> None:
        """No per-request device state beyond the pages (owned by the
        BlockManager); nothing to drop."""

    # ------------------------------------------------------- host KV swap
    def snapshot_block(self, bid: int):
        """Phase 1 of a device->host block read: dispatch the per-layer page
        slices, each ``(n_layers, bs, Hkv, hd)``, and return the (possibly
        still in-flight) device arrays. Must run on the thread that owns
        the pool — dispatch order sequences the slice before any later
        donating call overwrites the page, so the snapshot always sees the
        pre-overwrite content."""
        cfg = self.model.cfg
        heads = (cfg.num_kv_heads, cfg.head_dim)
        out = []
        for seg in self.pages:
            out.append(tuple(
                {name: pg[name][:, bid].reshape(-1, self.page_size, *heads)
                 for name in ("k", "v")} for pg in seg))
        return out

    @staticmethod
    def materialize(snapshot):
        """Phase 2: block until the snapshot's slices land and copy them to
        host numpy. Only *reads* self-contained device buffers, so it is
        safe on the async copy worker while the owner thread keeps
        dispatching compute."""
        return [tuple(
            {name: np.asarray(jax.device_get(blk[name]))
             for name in ("k", "v")} for blk in seg)
            for seg in snapshot]

    def read_block(self, bid: int):
        """Device->host staging of one KV page across every layer: the
        swap-out half of the tiered cache (synchronous snapshot +
        materialize). Returns a nested [segment][unit]{"k","v"} structure of
        host numpy arrays, shape (n_layers, page_size, H, hd) each."""
        return self.materialize(self.snapshot_block(bid))

    @staticmethod
    def stage_payload(payload):
        """Host->device upload of a block payload (the H2D half of swap-in)
        without touching the page pool — safe on the copy worker. The cheap
        donated scatter into the pool (``write_block``) stays with the pool
        owner. Idempotent on already-staged device arrays."""
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a)), payload)

    def _write_block_impl(self, pages, bid, payload):
        new_pages = []
        for seg, seg_payload in zip(pages, payload):
            new_seg = []
            for pg, blk in zip(seg, seg_payload):
                new_seg.append({
                    name: pg[name].at[:, bid].set(
                        blk[name].reshape(pg[name].shape[0], self.page_size,
                                          -1).astype(pg[name].dtype))
                    for name in ("k", "v")})
            new_pages.append(tuple(new_seg))
        return new_pages

    def write_block(self, bid: int, payload) -> None:
        """Host->device restore of one KV page (the swap-in half): stages
        the payload via ``jax.device_put`` (a no-op if the copy worker
        already uploaded it) and scatters it into the pool at ``bid`` inside
        a donated jit, so the update happens in place — the block table
        indirection makes the new bid transparent to attention."""
        staged = self.stage_payload(payload)
        self.pages = self._write_block_jit(self.pages, jnp.int32(bid),
                                           staged)

    def write_block_lazy(self, bid: int, payload) -> None:
        """Protocol completeness: paged KV has no lazy restore (attention
        reads every cached position, so every restored page must be device-
        resident) — a lazy write is a full write. The BlockManager never
        journals "in_lazy" for a paged io spec."""
        self.write_block(bid, payload)

    def bytes_per_block(self, n_tokens: int) -> int:
        """Link weight of one block holding ``n_tokens`` (per-token KV)."""
        return self.io.block_bytes(n_tokens)

    # ------------------------------------------------------------- API
    # Each call is one profiler span with the input copies, the jitted
    # call, the wait for the device and the logits copy to the host as
    # spans inside it; given the step's ``StepTimes`` (``times``) their
    # wall seconds, the launch and the sync are counted there too.
    def prefill_chunk(self, token_chunk: Sequence[int], ctx_len: int,
                      block_table: Sequence[int],
                      rid: Optional[int] = None, times=None) -> np.ndarray:
        with span("echo.runner.prefill", rid=-1 if rid is None else rid):
            with span("echo.runner.prep", times, "prep"):
                sc = self.chunk_size
                toks = np.zeros((sc,), np.int32)
                toks[: len(token_chunk)] = token_chunk
                bt = np.zeros((self.max_pages,), np.int32)
                bt[: len(block_table)] = block_table
                args = (jnp.asarray(toks), jnp.int32(ctx_len),
                        jnp.int32(len(token_chunk)), jnp.asarray(bt))
            with span("echo.runner.launch", times, "launch"):
                logits, self.pages = self._prefill_jit(self.params, *args,
                                                       self.pages)
            return self._fetch(logits, times)

    def decode(self, tokens: Sequence[int], block_tables: List[Sequence[int]],
               pos: Sequence[int],
               rids: Optional[Sequence[int]] = None,
               times=None) -> np.ndarray:
        b = len(tokens)
        bs = self.page_size     # live pages: the ones the decode kernel reads
        pages = sum((p + bs) // bs for p in pos)
        with span("echo.runner.decode", rows=b, pages=pages):
            with span("echo.runner.prep", times, "prep"):
                bpad = 1 << (b - 1).bit_length() if b > 1 else 1
                toks = np.zeros((bpad,), np.int32)
                toks[:b] = tokens
                bts = np.zeros((bpad, self.max_pages), np.int32)
                for i, bt in enumerate(block_tables):
                    bts[i, : len(bt)] = bt
                ps = np.full((bpad,), -1, np.int32)   # -1: padded, no write
                ps[:b] = pos
                args = (jnp.asarray(toks), jnp.asarray(bts), jnp.asarray(ps))
            with span("echo.runner.launch", times, "launch"):
                logits, self.pages = self._decode_jit(self.params, *args,
                                                      self.pages)
                logits = logits[:b]
            return self._fetch(logits, times)

    @staticmethod
    def _fetch(logits, times) -> np.ndarray:
        """Wait for the program that produced ``logits``, then copy them to
        the host."""
        with span("echo.runner.wait", times, "wait"):
            jax.block_until_ready(logits)
        with span("echo.runner.fetch", times, "fetch"):
            out = np.asarray(logits)
        if times is not None:
            times.n_launches += 1
            times.n_syncs += 1
        return out

"""State-snapshot serving path for attention-free (SSM) models.

Echo's prefix caching adapted per DESIGN.md §Arch-applicability: instead of
paged KV, the cache pool stores the recurrent state snapshot *after every
block_size tokens* (block_size == cfg.ssm_chunk, so SSD chunk boundaries
line up with BlockManager blocks). A prefix hit resumes from the snapshot
of the last cached block; eviction priorities / threshold / RC apply to
snapshot slots exactly as to KV blocks — the BlockManager is unchanged.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_io import io_spec_for_model
from repro.models import transformer as tfm
from repro.models.common import rms_norm
from repro.models.model import Model
from repro.models.ssm import ssm_context
from repro.obs.spans import spanned


class StateRunner:
    """Engine runner for recurrent-state configs: pure SSM (mamba2) and
    hybrid (recurrentgemma — RG-LRU states + *bounded* local-attention
    window rings; the full snapshot stays fixed-size, so block-boundary
    snapshotting works identically). Snapshot pool is a host dict
    bid -> state pytree (engine scale is tiny; slots are overwritten when
    the BlockManager reuses a block id, so stale entries are harmless).

    Pure-SSM chunks run through a jitted block-aligned span function (SSD
    chunk scan with boundary capture); hybrid configs step token-by-token
    through decode_step (correct; CPU-test scale)."""

    def __init__(self, model: Model, params, num_blocks: int, block_size: int,
                 max_pages_per_seq: int, chunk_size: int):
        cfg = model.cfg
        kinds = set(cfg.attn_layers)
        if not kinds <= {"ssm", "rglru", "attn"}:
            raise NotImplementedError("StateRunner: ssm/hybrid families only")
        if kinds == {"ssm"}:
            assert block_size == cfg.ssm_chunk, \
                "block_size must equal ssm_chunk so snapshots align with blocks"
        self._pure_ssm = kinds == {"ssm"}
        assert chunk_size % block_size == 0
        self.model = model
        self.params = params
        self.block_size = block_size
        # hybrid: the attention ring must cover the local window
        self._state_len = 1 if self._pure_ssm else max(cfg.window, 1)
        self.io = io_spec_for_model(model)   # state: fixed-size snapshots
        self.pool: Dict[int, object] = {}       # bid -> state pytree (numpy)
        self.live: Dict[int, object] = {}       # rid -> state pytree (jnp)
        # position the live state is valid for: a preempted request can be
        # re-admitted with a LONGER cached prefix than it had computed (the
        # pool gained boundaries meanwhile), making the surviving live
        # state stale for the new resume point — it must only short-circuit
        # the boundary-snapshot resume when the positions agree
        self._live_pos: Dict[int, int] = {}     # rid -> tokens consumed
        self._span_jit = {}
        self._decode_jit = jax.jit(model.decode_step)

    # ------------------------------------------------------------- states
    def _zeros_state(self):
        return self.model.make_cache(1, self._state_len)

    def _span_fn(self, n: int):
        """Jitted: consume n (block-aligned) tokens from a state. Returns
        (last_logits (V,), final_state, boundaries: tuple of states)."""
        if n in self._span_jit:
            return self._span_jit[n]
        model, cfg = self.model, self.model.cfg
        bs = self.block_size
        nc = n // bs

        def span(params, tokens, state):
            h = jnp.take(params["embed"], tokens[None], axis=0)   # (1,n,d)
            new_segs, bound_segs = [], []
            for (stype, unit, cnt), seg_p, seg_s in zip(
                    tfm.segments(cfg), params["layers"], state):

                def body(hh, xs):
                    p_k, st_k = xs
                    out, cache, bounds = ssm_context(
                        p_k["ssm"], cfg,
                        rms_norm(hh, p_k["ln"], cfg.norm_eps),
                        return_cache=True, initial=st_k,
                        boundary_states=True)
                    per_block = tuple(
                        {"conv": bounds["conv"][:, i].astype(cache["conv"].dtype),
                         "ssd": bounds["ssd"][:, i]}
                        for i in range(nc))
                    return hh + out, (cache, per_block)

                if stype == "scan":
                    h, (new_s, bounds) = tfm._scan(body, h,
                                                   (seg_p[0], seg_s[0]), cnt)
                    new_segs.append((new_s,))
                    bound_segs.append((bounds,))
                else:
                    outs, bnds = [], []
                    for p_k, st_k in zip(seg_p, seg_s):
                        h, (c, bd) = body(h, (p_k, st_k))
                        outs.append(c)
                        bnds.append(bd)
                    new_segs.append(tuple(outs))
                    bound_segs.append(tuple(bnds))
            logits = model._logits(params, h[:, -1][:, None])[:, 0]
            # restructure: boundaries[i] has the same pytree shape as state
            boundaries = tuple(
                [tuple(jax.tree.map(lambda t: t, kb[i]) for kb in seg)
                 for seg in bound_segs]
                for i in range(nc))
            return logits[0], new_segs, boundaries

        fn = jax.jit(span)
        self._span_jit[n] = fn
        return fn

    # ------------------------------------------------------------- API
    # ``times`` (the engine's per-step counters) is accepted and left at
    # zero: this runner's calls are one span each, not split by phase
    @spanned("echo.runner.prefill")
    def prefill_chunk(self, token_chunk: Sequence[int], ctx_len: int,
                      block_table: Sequence[int], rid: Optional[int] = None,
                      times=None):
        bs = self.block_size
        assert ctx_len % bs == 0, "resume points are block-aligned"
        if rid in self.live and self._live_pos.get(rid) == ctx_len:
            state = self.live[rid]
        elif ctx_len > 0 and block_table[ctx_len // bs - 1] in self.pool:
            state = jax.tree.map(jnp.asarray,
                                 self.pool[block_table[ctx_len // bs - 1]])
        else:
            assert ctx_len == 0, "resume snapshot missing"
            state = self._zeros_state()

        toks = list(token_chunk)
        full = (len(toks) // bs * bs) if self._pure_ssm else 0
        logits = None
        if full:
            fn = self._span_fn(full)
            logits, state, boundaries = fn(
                self.params, jnp.asarray(toks[:full], jnp.int32), state)
            first_block = ctx_len // bs
            for i, bstate in enumerate(boundaries):
                bid = block_table[first_block + i]
                self.pool[bid] = jax.tree.map(np.asarray, bstate)
        for j, t in enumerate(toks[full:]):
            p = ctx_len + full + j
            lg, state = self._decode_jit(self.params,
                                         jnp.asarray([t], jnp.int32),
                                         state, jnp.asarray([p], jnp.int32))
            logits = lg[0]
            if (p + 1) % bs == 0 and (p + 1) // bs - 1 < len(block_table):
                self.pool[block_table[(p + 1) // bs - 1]] = \
                    jax.tree.map(np.asarray, state)
        self.live[rid] = state
        self._live_pos[rid] = ctx_len + len(toks)
        return np.asarray(logits)

    @spanned("echo.runner.decode")
    def decode(self, tokens: Sequence[int], block_tables: List[Sequence[int]],
               pos: Sequence[int], rids: Optional[Sequence[int]] = None,
               times=None):
        bs = self.block_size
        out = np.zeros((len(tokens), self.model.cfg.vocab_size), np.float32)
        for i, (t, bt, p, rid) in enumerate(zip(tokens, block_tables, pos, rids)):
            state = self.live.get(rid)
            if state is None:
                state = self._zeros_state()
            lg, state = self._decode_jit(self.params,
                                         jnp.asarray([t], jnp.int32), state,
                                         jnp.asarray([p], jnp.int32))
            self.live[rid] = state
            self._live_pos[rid] = p + 1
            if (p + 1) % bs == 0 and (p + 1) // bs - 1 < len(bt):
                self.pool[bt[(p + 1) // bs - 1]] = jax.tree.map(np.asarray, state)
            out[i] = np.asarray(lg[0])
        return out

    def release(self, rid: int) -> None:
        self.live.pop(rid, None)
        self._live_pos.pop(rid, None)

    # --------------------------------------------------- host tier protocol
    # Same split-phase block I/O protocol as PagedRunner, over boundary
    # snapshots instead of KV pages. The pool already lives host-side
    # (entries are numpy pytrees, replaced wholesale and never mutated in
    # place), so snapshot/materialize are reference hand-offs, not copies —
    # the copy stream's worker can hold them race-free while the owner
    # thread keeps dispatching compute.
    def snapshot_block(self, bid: int):
        """Phase 1 of a device->host block read: hand out the boundary
        snapshot recorded for ``bid``. Every committed block has one — the
        span function and decode store a snapshot at each crossed boundary,
        and swap-in re-registers restored payloads."""
        snap = self.pool.get(bid)
        assert snap is not None, f"no boundary snapshot for block {bid}"
        return snap

    @staticmethod
    def materialize(snapshot):
        """Phase 2: ensure the snapshot is host numpy. Pool entries already
        are (a no-op tree pass); entries staged device-side by a recent
        ``write_block`` get pulled across here."""
        return jax.tree.map(np.asarray, snapshot)

    def read_block(self, bid: int):
        """Synchronous device->host staging of one boundary snapshot."""
        return self.materialize(self.snapshot_block(bid))

    @staticmethod
    def stage_payload(payload):
        """Host->device upload of one snapshot (the H2D half of swap-in) —
        safe on the copy worker; the pool insert stays with the owner."""
        return jax.tree.map(lambda a: jax.device_put(jnp.asarray(a)), payload)

    def write_block(self, bid: int, payload) -> None:
        """Restore one boundary snapshot device-side: upload (no-op if the
        copy worker already staged it) and re-register under ``bid``. The
        next ``prefill_chunk`` resume from this boundary pays no H2D copy."""
        self.pool[bid] = self.stage_payload(payload)

    def write_block_lazy(self, bid: int, payload) -> None:
        """Re-register a host payload under ``bid`` WITHOUT uploading — the
        ``"in_lazy"`` half of restore_last_only swap-in: earlier boundaries
        of a restored prefix only matter for future mid-prefix resumes, and
        resume lazily uploads (``jnp.asarray``) whatever the pool holds."""
        self.pool[bid] = payload

    def bytes_per_block(self, n_tokens: int) -> int:
        """Link weight of one block: the fixed-size snapshot, regardless of
        how deep the boundary sits in the prefix."""
        return self.io.block_bytes(n_tokens)

"""Jit'd dispatch wrappers + per-preset block-size tuning for the Pallas
kernels.

On CPU the Pallas kernels execute in interpret mode for
correctness validation; on TPU they compile natively. Callers can force a
path via ``impl``:

* ``"ref"``    — the pure-jnp oracle (the fast, XLA-compiled CPU path);
* ``"pallas"`` — the legacy serial-page / fixed-grid Pallas kernels;
* ``"paged_decode_attention"`` — the decode kernel that reads only each
  row's live pages, every KV head and a block of pages per step (decode
  only; prefill always uses the fused chunked kernel);
* ``"auto"``   — ``"ref"`` on CPU (interpret mode is a correctness tool,
  not a fast path), ``"paged_decode_attention"`` on accelerators.

Block sizes and pages per decode block come from per-hardware tuning tables
(``KernelTuning`` presets). ``kernel_tuning(profile)`` resolves a profile
name — or, when ``profile`` is None, the attached device: the CPU gets
the interpreter's table, a TPU the table keyed by its ``device_kind``
(``DEVICE_KIND_PROFILES``). An accelerator kind with no table raises; it
never borrows another chip's tiles.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax

from repro.kernels import ref as ref_mod
from repro.kernels.chunked_prefill import chunked_prefill_attention as _pallas_chunked
from repro.kernels.paged_attention import paged_attention as _pallas_paged
from repro.kernels.paged_attention import paged_decode_attention as _pallas_decode
from repro.kernels.ssd_scan import ssd_scan as _pallas_ssd


@dataclass(frozen=True)
class KernelTuning:
    """Per-hardware kernel launch parameters.

    blk_q/blk_k: chunked-prefill flash tile sizes (queries x keys);
    pages_per_block: pages a decode row copies and computes per loop
    iteration — larger blocks mean fewer, larger DMA waits and MXU calls,
    smaller ones copy less past a row's last live page.
    """
    blk_q: int = 128
    blk_k: int = 128
    pages_per_block: int = 4

    def override(self, **kw) -> "KernelTuning":
        return replace(self, **{k: v for k, v in kw.items() if v is not None})


TUNING_PRESETS = {
    # TPU v5e: prefill tiles the v5e compile tests accept, not tuned for
    # speed. pages_per_block from a sweep on the chip, ms a decode call (all
    # layers, 8 rows of 60-2,600 tokens): Qwen3-4B 9.62 / 5.46 / 3.65 / 2.79
    # and Yi-9B (24 layers) 3.96 / 2.53 / 1.91 / 1.67 at 4 / 8 / 16 / 32
    "v5e": KernelTuning(blk_q=128, blk_k=128, pages_per_block=32),
    # CPU / interpret: small tiles keep the (slow) interpreter tractable
    # and exercise multi-block grids at test shapes
    "cpu": KernelTuning(blk_q=64, blk_k=64, pages_per_block=4),
}


# jax ``device_kind`` -> tuning profile of the chips this code runs on
DEVICE_KIND_PROFILES = {"TPU v5 lite": "v5e"}


def kernel_tuning(profile: str | None = None) -> KernelTuning:
    """Resolve a tuning table: explicit profile name, else by device."""
    if profile is None:
        dev = jax.devices()[0]
        if dev.platform == "cpu":
            profile = "cpu"
        elif dev.device_kind in DEVICE_KIND_PROFILES:
            profile = DEVICE_KIND_PROFILES[dev.device_kind]
        else:
            raise ValueError(f"no kernel tuning table for device kind "
                             f"{dev.device_kind!r} ({dev.platform}); have "
                             f"{sorted(DEVICE_KIND_PROFILES)}")
    if profile not in TUNING_PRESETS:
        raise ValueError(f"unknown kernel tuning profile {profile!r}; "
                         f"have {sorted(TUNING_PRESETS)}")
    return TUNING_PRESETS[profile]


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return ("ref" if jax.default_backend() == "cpu"
                else "paged_decode_attention")
    return impl


def paged_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                    impl="pallas", preset=None, pages_per_block=None):
    """Decode attention dispatch. ``impl`` in {auto, ref, pallas,
    paged_decode_attention}; ``preset`` picks the tuning table for the
    pages per block, overridable via ``pages_per_block``."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref_mod.ref_paged_attention(q, k_pages, v_pages, block_tables,
                                           ctx_lens)
    if impl == "paged_decode_attention":
        tune = kernel_tuning(preset).override(pages_per_block=pages_per_block)
        return _pallas_decode(q, k_pages, v_pages, block_tables, ctx_lens,
                              pages_per_block=tune.pages_per_block,
                              interpret=_interpret())
    return _pallas_paged(q, k_pages, v_pages, block_tables, ctx_lens,
                         interpret=_interpret())


def chunked_prefill_attention(q, k, v, ctx_len, impl="pallas", preset=None,
                              blk_q=None, blk_k=None):
    """Chunked-prefill dispatch (fused-epilogue kernel on the Pallas
    paths). Tile sizes default to the preset's tuning table."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref_mod.ref_chunked_prefill_attention(q, k, v, ctx_len)
    tune = kernel_tuning(preset).override(blk_q=blk_q, blk_k=blk_k)
    return _pallas_chunked(q, k, v, ctx_len, blk_q=tune.blk_q,
                           blk_k=tune.blk_k, interpret=_interpret())


def ssd_scan(x, dt_a, b_mat, c_mat, chunk=64, impl="pallas"):
    if _resolve(impl) == "ref":
        y, fs = ref_mod.ref_ssd_sequential(x, dt_a, b_mat, c_mat)
        return y, fs
    return _pallas_ssd(x, dt_a, b_mat, c_mat, chunk=chunk, interpret=_interpret())


def rglru_scan(a, b, chunk=64, impl="pallas"):
    from repro.kernels.rglru_scan import rglru_scan as _pallas_rglru
    if _resolve(impl) == "ref":
        return ref_mod.ref_rglru_scan(a, b)
    return _pallas_rglru(a, b, chunk=chunk, interpret=_interpret())

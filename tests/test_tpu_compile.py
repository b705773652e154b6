"""The served attention kernels compile for one TPU v5e chip at the served
widths (Hq=32, head_dim=128, bf16, 16-token pages; Hkv 8 for Qwen3-4B, 4
for Yi-9B).

Nothing runs: the chip is described, not attached, and the TPU compiler
refuses here what it would refuse on the chip (block shapes off the (8, 128)
tiling, too much VMEM). The topology is described inside a fixture so
that only the worker that runs this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.paged_attention import (paged_attention,
                                           paged_decode_attention)

HQ, HKV, HD, PAGE = 32, 8, 128, 16
MAX_PAGES_PER_SEQ, POOL_PAGES, CHUNK = 64, 1024, 256
SERVED_PAGES_PER_SEQ = 256      # the benchmark cells' max_pages_per_seq


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("batch", [8, 64])
@pytest.mark.parametrize("hkv", [8, 4], ids=["qwen3-4b", "yi-9b"])
@pytest.mark.parametrize("kernel", ["paged_decode_attention", "legacy"])
def test_paged_decode_compiles_for_v5e(one_chip, kernel, hkv, batch):
    """A decode batch of 8 (the cells' max_running) and of 64 against a
    full pool, 256 pages a row in scalar-prefetch memory, at the v5e
    table's pages_per_block: the compiler checks the VMEM budget."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    tune = ops.kernel_tuning("v5e")
    fn = (functools.partial(paged_decode_attention,
                            pages_per_block=tune.pages_per_block)
          if kernel == "paged_decode_attention" else paged_attention)
    pool = sds((POOL_PAGES, PAGE, hkv, HD), jnp.bfloat16)
    _compile(fn, sds((batch, HQ, HD), jnp.bfloat16), pool, pool,
             sds((batch, SERVED_PAGES_PER_SEQ), jnp.int32),
             sds((batch,), jnp.int32))


def test_chunked_prefill_compiles_for_v5e(one_chip):
    """One prefill chunk against a gathered prefix of the full table, at
    the v5e table's tiles."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    tune = ops.kernel_tuning("v5e")
    kv = sds((MAX_PAGES_PER_SEQ * PAGE, HKV, HD), jnp.bfloat16)
    _compile(functools.partial(chunked_prefill_attention, blk_q=tune.blk_q,
                               blk_k=tune.blk_k),
             sds((CHUNK, HQ, HD), jnp.bfloat16), kv, kv,
             sds((), jnp.int32))


def test_untileable_head_dim_raises_naming_shape(one_chip):
    """head_dim 64 with several KV heads cannot be lane-sliced: the
    compiled kernel refuses it by shape instead of falling back."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = sds((POOL_PAGES, PAGE, HKV, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"\(1024, 16, 8, 64\)"):
        jax.jit(paged_decode_attention).lower(
            sds((8, HQ, 64), jnp.bfloat16), pool, pool,
            sds((8, MAX_PAGES_PER_SEQ), jnp.int32), sds((8,), jnp.int32))


class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_kernel_tuning_resolves_from_device_kind(monkeypatch):
    monkeypatch.setattr(jax, "devices",
                        lambda: [_Device("tpu", "TPU v5 lite")])
    assert ops.kernel_tuning(None) == ops.TUNING_PRESETS["v5e"]
    monkeypatch.setattr(jax, "devices",
                        lambda: [_Device("gpu", "NVIDIA A100-SXM4-40GB")])
    with pytest.raises(ValueError, match="NVIDIA A100-SXM4-40GB"):
        ops.kernel_tuning(None)

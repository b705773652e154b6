"""The served attention kernels, and the runner's prefill and decode
programs around them, compile for one TPU v5e chip at the served widths
(Hq=32, head_dim=128, bf16, 16-token pages; Hkv 8 for Qwen3-4B, 4 for
Yi-9B).

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

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.paged_attention import (paged_attention,
                                           paged_decode_attention)

HQ, HKV, HD, PAGE = 32, 8, 128, 16
MAX_PAGES_PER_SEQ, POOL_PAGES, CHUNK = 64, 1024, 256
SERVED_PAGES_PER_SEQ = 256      # the benchmark cells' max_pages_per_seq
LAYERS = 3                      # scanned layers of the runner's programs


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
    table's pages_per_block: the compiler checks the VMEM budget. The
    served kernel reads one layer of the stacked lane-layout pool."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    tune = ops.kernel_tuning("v5e")
    if kernel == "paged_decode_attention":
        fn = functools.partial(paged_decode_attention,
                               pages_per_block=tune.pages_per_block)
        pool = sds((LAYERS, POOL_PAGES, PAGE, hkv * HD), jnp.bfloat16)
        layer = (sds((), jnp.int32),)
    else:
        fn, layer = paged_attention, ()
        pool = sds((POOL_PAGES, PAGE, hkv, HD), jnp.bfloat16)
    _compile(fn, sds((batch, HQ, HD), jnp.bfloat16), pool, pool,
             sds((batch, SERVED_PAGES_PER_SEQ), jnp.int32),
             sds((batch,), jnp.int32), *layer)


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
    pool = sds((LAYERS, POOL_PAGES, PAGE, HKV * 64), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"\(3, 1024, 16, 512\)"):
        jax.jit(paged_decode_attention).lower(
            sds((8, HQ, 64), jnp.bfloat16), pool, pool,
            sds((8, MAX_PAGES_PER_SEQ), jnp.int32), sds((8,), jnp.int32),
            sds((), jnp.int32))


def _served_runner(hkv, num_pages):
    """A PagedRunner at the served attention widths over LAYERS scanned
    layers (d_model, d_ff and vocab kept small: they do not touch the
    pool), running the v5e kernels, with shape-only params."""
    from repro.core.engine import PagedRunner     # the engine's import order
    from repro.models import Model
    cfg = ModelConfig(name="served-attn", family="dense", source="test",
                      num_layers=LAYERS, d_model=512, vocab_size=1024,
                      num_heads=HQ, num_kv_heads=hkv, head_dim=HD,
                      qk_norm=True, d_ff=1024, dtype="bfloat16")
    model = Model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return PagedRunner(model, params, num_pages=num_pages, page_size=PAGE,
                       max_pages_per_seq=SERVED_PAGES_PER_SEQ,
                       chunk_size=CHUNK, attn_impl="paged_decode_attention",
                       kernel_profile="v5e")


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("hkv", [8, 4], ids=["qwen3-4b", "yi-9b"])
def test_runner_updates_the_pool_in_place_on_v5e(one_chip, monkeypatch,
                                                 hkv, program):
    """The decode program (8 rows, 256-page tables) and the prefill
    program (one 256-token chunk) over a 1,024-page pool: the output pool
    aliases the donated input, and no temporary holds as much as one
    layer's K and V pool (a layer sliced out of the stack, or relaid out
    for the kernel, would)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    runner = _served_runner(hkv, num_pages=1)
    pool = jax.eval_shape(lambda: _served_runner(hkv, POOL_PAGES).pages)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def i32(*shape):
        return spec(jax.ShapeDtypeStruct(shape, jnp.int32))

    params, pages = jax.tree.map(spec, (runner.params, pool))
    if program == "decode":
        rows = 8
        lowered = runner._decode_jit.lower(
            params, i32(rows), i32(rows, SERVED_PAGES_PER_SEQ), i32(rows),
            pages)
    else:
        lowered = runner._prefill_jit.lower(
            params, i32(CHUNK), i32(), i32(), i32(SERVED_PAGES_PER_SEQ),
            pages)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // LAYERS


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

"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.chunked_prefill import chunked_prefill_attention
from repro.kernels.paged_attention import (paged_attention,
                                           paged_decode_attention)
from repro.kernels.ssd_scan import ssd_scan


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,hd,bs,nblk", [
    (2, 4, 2, 32, 8, 4),
    (3, 8, 1, 64, 16, 3),     # MQA
    (1, 6, 6, 16, 8, 2),      # MHA
])
def test_paged_attention_sweep(dtype, b, hq, hkv, hd, bs, nblk):
    rng = jax.random.PRNGKey(b * 31 + hq)
    ks = jax.random.split(rng, 4)
    p = nblk * b + 2
    q = jax.random.normal(ks[0], (b, hq, hd), dtype)
    kp = jax.random.normal(ks[1], (p, bs, hkv, hd), dtype)
    vp = jax.random.normal(ks[2], (p, bs, hkv, hd), dtype)
    bt = jax.random.randint(ks[3], (b, nblk), 0, p)
    cl = jnp.asarray(np.random.default_rng(0).integers(1, nblk * bs, b), jnp.int32)
    out = paged_attention(q, kp, vp, bt, cl, interpret=True)
    want = ref.ref_paged_attention(q, kp, vp, bt, cl)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sc,t,hq,hkv,hd,ctx", [
    (64, 128, 4, 2, 32, 0),
    (64, 128, 4, 2, 32, 37),
    (32, 64, 2, 1, 64, 30),
])
def test_chunked_prefill_sweep(dtype, sc, t, hq, hkv, hd, ctx):
    rng = jax.random.PRNGKey(sc + ctx)
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (sc, hq, hd), dtype)
    k = jax.random.normal(ks[1], (t, hkv, hd), dtype)
    v = jax.random.normal(ks[2], (t, hkv, hd), dtype)
    out = chunked_prefill_attention(q, k, v, ctx, blk_q=32, blk_k=32,
                                    interpret=True)
    want = ref.ref_chunked_prefill_attention(q, k, v, ctx)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 2, 8, 4, 16),
    (1, 128, 4, 16, 8, 32),
    (3, 32, 1, 4, 16, 16),
])
def test_ssd_scan_sweep(b, s, h, p, n, chunk):
    rng = jax.random.PRNGKey(s + h)
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dta = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    y, fs = ssd_scan(x, dta, bm, cm, chunk=chunk, interpret=True)
    y_ref, fs_ref = ref.ref_ssd_sequential(x, dta, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fs), np.asarray(fs_ref),
                               rtol=2e-4, atol=2e-4)


def test_paged_attention_ignores_garbage_pages():
    """Pages not referenced by the block table must not affect output."""
    rng = jax.random.PRNGKey(9)
    ks = jax.random.split(rng, 4)
    b, hq, hkv, hd, bs, nblk, p = 1, 2, 1, 16, 8, 2, 6
    q = jax.random.normal(ks[0], (b, hq, hd))
    kp = jax.random.normal(ks[1], (p, bs, hkv, hd))
    vp = jax.random.normal(ks[2], (p, bs, hkv, hd))
    bt = jnp.array([[1, 3]], jnp.int32)
    cl = jnp.array([12], jnp.int32)
    out1 = paged_attention(q, kp, vp, bt, cl, interpret=True)
    kp2 = kp.at[0].set(999.0).at[2].set(-999.0)
    vp2 = vp.at[4].set(123.0)
    out2 = paged_attention(q, kp2, vp2, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


def _paged_case(seed, b, hq, hkv, hd, bs, nblk, ctx_lens, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = nblk * b + 2
    q = jax.random.normal(ks[0], (b, hq, hd), dtype)
    kp = jax.random.normal(ks[1], (p, bs, hkv, hd), dtype)
    vp = jax.random.normal(ks[2], (p, bs, hkv, hd), dtype)
    bt = jax.random.randint(ks[3], (b, nblk), 0, p)
    cl = jnp.asarray(ctx_lens, jnp.int32)
    return q, kp, vp, bt, cl


LAYERS = 3      # layers of a stacked pool the decode kernel reads one of


def _stacked(pool, layer, fill=None):
    """A ``(P, bs, Hkv, hd)`` pool as layer ``layer`` of a
    ``(LAYERS, P, bs, Hkv*hd)`` stack in the lane layout, the serving
    pool's form; the other layers hold ``fill`` (other values if None)."""
    lane = pool.reshape(*pool.shape[:2], -1)
    shape = (LAYERS,) + lane.shape
    other = (jax.random.normal(jax.random.PRNGKey(layer), shape, pool.dtype)
             if fill is None else jnp.full(shape, fill, pool.dtype))
    return other.at[layer].set(lane)


def _decode(q, kp, vp, bt, cl, layer, pages_per_block, fill=None):
    """The decode kernel on pools ``kp``/``vp`` placed at ``layer``."""
    return paged_decode_attention(q, _stacked(kp, layer, fill),
                                  _stacked(vp, layer, fill), bt, cl, layer,
                                  pages_per_block=pages_per_block,
                                  interpret=True)


PAGED_DECODE_CASES = [
    # (b, hq, hkv, hd, bs, nblk, ctx_lens) — GQA group sizes 1 / 4 / 8,
    # ragged batches, and contexts shorter than a single page
    (2, 4, 4, 32, 8, 4, [32, 17]),            # g=1 (MHA)
    (3, 8, 2, 64, 16, 6, [96, 5, 48]),        # g=4, ragged + ctx < page
    (2, 8, 1, 32, 8, 5, [40, 3]),             # g=8 (MQA), ctx < page
    (4, 4, 1, 16, 4, 3, [12, 1, 7, 9]),       # g=4, every ctx ragged
]


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", PAGED_DECODE_CASES)
@pytest.mark.parametrize("pages_per_block", [1, 2, 4])
def test_paged_attention_splitk_sweep(dtype, case, pages_per_block, layer):
    """The decode kernel (``paged_decode_attention``, which replaced the
    split-K schedule) against the oracle, 1-4 pages a block, reading the
    first or the last layer of a stacked pool."""
    b, hq, hkv, hd, bs, nblk, ctx_lens = case
    q, kp, vp, bt, cl = _paged_case(b * 7 + hq, b, hq, hkv, hd, bs, nblk,
                                    ctx_lens, dtype)
    out = _decode(q, kp, vp, bt, cl, layer, pages_per_block)
    want = ref.ref_paged_attention(q, kp, vp, bt, cl)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", PAGED_DECODE_CASES)
def test_paged_attention_legacy_sweep(dtype, case):
    """Same sweep through the legacy single-pass kernel: both code paths
    must agree with the oracle on identical inputs."""
    b, hq, hkv, hd, bs, nblk, ctx_lens = case
    q, kp, vp, bt, cl = _paged_case(b * 7 + hq, b, hq, hkv, hd, bs, nblk,
                                    ctx_lens, dtype)
    out = paged_attention(q, kp, vp, bt, cl, interpret=True)
    want = ref.ref_paged_attention(q, kp, vp, bt, cl)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_paged_attention_splitk_oversized_split():
    """A block larger than the whole table degenerates to one block a row
    and must still match."""
    q, kp, vp, bt, cl = _paged_case(3, 2, 4, 2, 32, 8, 4, [32, 9], jnp.float32)
    out = _decode(q, kp, vp, bt, cl, LAYERS - 1, pages_per_block=64)
    want = ref.ref_paged_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _poison_dead_pages(kp, vp, bt, cl, bs):
    """NaN in every pool slot no row reads: the tail of each row's last
    live page, and the pages its table names past that page."""
    live = np.zeros(kp.shape[:2], bool)
    for row, ctx in zip(np.asarray(bt), np.asarray(cl)):
        for t in range(int(ctx)):
            live[row[t // bs], t % bs] = True
    dead = jnp.asarray(~live)[:, :, None, None]
    return jnp.where(dead, jnp.nan, kp), jnp.where(dead, jnp.nan, vp)


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("pages_per_block", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_never_reads_dead_pages(dtype, pages_per_block, layer):
    """K and V past each row's ctx hold NaN, and so does every other layer
    of the stack: the output stays finite and matches the oracle on the
    clean pool."""
    b, bs, nblk = 3, 8, 6
    # distinct pages a row, so no live page is also another row's dead one
    q, kp, vp, _, cl = _paged_case(5, b, 8, 2, 32, bs, nblk, [9, 30, 17],
                                   dtype)
    bt = jnp.arange(b * nblk, dtype=jnp.int32).reshape(b, nblk)[:, ::-1]
    want = ref.ref_paged_attention(q, kp, vp, bt, cl)
    kp_nan, vp_nan = _poison_dead_pages(kp, vp, bt, cl, bs)
    out = _decode(q, kp_nan, vp_nan, bt, cl, layer, pages_per_block,
                  fill=jnp.nan)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_paged_decode_padded_row_writes_zeros():
    """A padded row (ctx 0) gives zeros, not NaN, beside live rows that
    still match; its table points at NaN pages it must not read."""
    q, kp, vp, _, cl = _paged_case(13, 3, 4, 2, 32, 8, 4, [20, 0, 7],
                                   jnp.float32)
    bt = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    kp, vp = kp.at[bt[1]].set(jnp.nan), vp.at[bt[1]].set(jnp.nan)
    want = ref.ref_paged_attention(q, kp, vp, bt, cl)
    out = np.asarray(_decode(q, kp, vp, bt, cl, 1, pages_per_block=2))
    np.testing.assert_array_equal(out[1], 0.0)
    np.testing.assert_allclose(out[[0, 2]], np.asarray(want)[[0, 2]],
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("ctx_lens", [[16, 32], [17, 33], [15, 48]])
def test_paged_decode_block_edges(ctx_lens):
    """ctx exactly at a block boundary (2 pages of 8), one token past it
    and one short of it."""
    q, kp, vp, bt, cl = _paged_case(17, 2, 4, 2, 32, 8, 6, ctx_lens,
                                    jnp.float32)
    out = _decode(q, kp, vp, bt, cl, 0, pages_per_block=2)
    want = ref.ref_paged_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("hkv", [8, 4], ids=["qwen3-4b", "yi-9b"])
def test_paged_decode_served_gqa_shapes(hkv, layer):
    """The served configurations' heads at head_dim 128 in bf16: Qwen3-4B
    (8 KV heads, group 4) and Yi-9B (4 KV heads, group 8), 32 query
    heads, on a small stacked pool of 16-token pages."""
    q, kp, vp, bt, cl = _paged_case(19, 4, 32, hkv, 128, 16, 8,
                                    [100, 1, 64, 65], jnp.bfloat16)
    out = _decode(q, kp, vp, bt, cl, layer, pages_per_block=4)
    want = ref.ref_paged_attention(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sc,t,hq,hkv,hd,ctx,blk_q,blk_k", [
    (100, 420, 4, 1, 32, 250, 32, 64),   # nothing divides anything
    (65, 131, 8, 2, 32, 66, 32, 32),     # off-by-one past block edges
    (7, 16, 4, 4, 16, 9, 32, 32),        # chunk smaller than one block
    (64, 192, 8, 8, 32, 128, 16, 48),    # g=1, blk_k not a divisor of t
])
def test_chunked_prefill_nondivisible_sweep(dtype, sc, t, hq, hkv, hd, ctx,
                                            blk_q, blk_k):
    rng = jax.random.PRNGKey(sc * 3 + ctx)
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (sc, hq, hd), dtype)
    k = jax.random.normal(ks[1], (t, hkv, hd), dtype)
    v = jax.random.normal(ks[2], (t, hkv, hd), dtype)
    out = chunked_prefill_attention(q, k, v, ctx, blk_q=blk_q, blk_k=blk_k,
                                    interpret=True)
    assert out.shape == q.shape and out.dtype == q.dtype
    want = ref.ref_chunked_prefill_attention(q, k, v, ctx)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_ops_dispatch_and_tuning():
    """ops-layer routing over a stacked pool: impl="ref" is the oracle on
    the layer it names, impl="paged_decode_attention"/"pallas" agree with
    it, presets resolve to per-backend tuning tables."""
    q, kp, vp, bt, cl = _paged_case(11, 2, 8, 2, 32, 8, 4, [32, 11],
                                    jnp.float32)
    ks, vs = _stacked(kp, 1), _stacked(vp, 1)
    want = ops.paged_attention(q, ks, vs, bt, cl, 1, impl="ref")
    np.testing.assert_allclose(
        np.asarray(ref.ref_paged_attention(q, kp, vp, bt, cl)),
        np.asarray(want), rtol=0, atol=0)
    for impl in ("paged_decode_attention", "pallas"):
        got = ops.paged_attention(q, ks, vs, bt, cl, 1, impl=impl,
                                  preset="cpu")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    assert ops.kernel_tuning("v5e").pages_per_block > \
        ops.kernel_tuning("cpu").pages_per_block
    assert ops.kernel_tuning(None) == ops.kernel_tuning("cpu")  # CPU backend
    with pytest.raises(ValueError):
        ops.kernel_tuning("tpu9000")


@pytest.mark.parametrize("b,s,w,chunk,blk_w", [
    (2, 64, 32, 16, 32),
    (1, 128, 64, 32, 32),
    (3, 32, 16, 16, 16),
])
def test_rglru_scan_sweep(b, s, w, chunk, blk_w):
    from repro.kernels.rglru_scan import rglru_scan
    rng = jax.random.PRNGKey(s + w)
    ks = jax.random.split(rng, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, s, w)))
    bb = jax.random.normal(ks[1], (b, s, w))
    got = rglru_scan(a, bb, chunk=chunk, blk_w=blk_w, interpret=True)
    want = ref.ref_rglru_scan(a, bb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

"""The paged runner updates its pool in place: each model call donates the
pool, a layer writes only its own rows at its own positions in the
stacked lane-layout pool, padded rows are dropped, and the host KV tier
still moves ``(n_layers, page_size, Hkv, hd)`` pages."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig

LAYERS, PAGE, PAGES, CHUNK = 3, 4, 8, 8


@pytest.fixture(scope="module")
def model():
    from repro.models import Model
    cfg = ModelConfig(name="tiny-3layer", family="dense", source="test",
                      num_layers=LAYERS, d_model=64, vocab_size=128,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      dtype="float32", rope_theta=10_000.0)
    m = Model(cfg)
    return m, m.init(jax.random.PRNGKey(3))


def _runner(model):
    from repro.core.engine import PagedRunner     # the engine's import order
    m, params = model
    return PagedRunner(m, params, num_pages=PAGES, page_size=PAGE,
                       max_pages_per_seq=4, chunk_size=CHUNK)


def _pool(runner):
    """K and V of the one scanned segment, (LAYERS, P, bs, Hkv, hd)."""
    cfg = runner.model.cfg
    (seg,) = runner.pages
    (pg,) = seg
    return {n: np.asarray(pg[n]).reshape(LAYERS, PAGES, PAGE,
                                         cfg.num_kv_heads, cfg.head_dim)
            for n in ("k", "v")}


def _dense_kv(model, tokens):
    """Per-layer K and V of ``tokens`` from the dense path, (LAYERS, S,
    Hkv, hd) each."""
    m, params = model
    _, caches = m.prefill(params, jnp.asarray([tokens], jnp.int32))
    (seg,) = caches
    (c,) = seg
    return {n: np.asarray(c[n])[:, 0] for n in ("k", "v")}


def _written(pool):
    return {n: np.abs(a).sum(axis=(-2, -1)) != 0 for n, a in pool.items()}


def test_pool_is_kept_in_the_lane_layout(model):
    runner = _runner(model)
    cfg = runner.model.cfg
    for leaf in jax.tree.leaves(runner.pages):
        assert leaf.shape == (LAYERS, PAGES, PAGE,
                              cfg.num_kv_heads * cfg.head_dim)


def test_prefill_writes_each_layers_rows_at_its_positions(model):
    """Six tokens of an 8-token chunk over pages 3 and 5: each layer's K/V
    land at [layer, 3, 0:4] and [layer, 5, 0:2] and equal the dense path's
    K/V of that layer; the two padded rows and every other slot stay
    zero. The passed pool is donated and the logits are the dense ones."""
    runner = _runner(model)
    toks = [5, 17, 99, 3, 64, 8]
    before = runner.pages
    logits = runner.prefill_chunk(toks, 0, [3, 5])
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    m, params = model
    want, _ = m.prefill(params, jnp.asarray([toks], jnp.int32))
    np.testing.assert_allclose(logits, np.asarray(want[0]), rtol=2e-5,
                               atol=2e-5)

    pool, dense = _pool(runner), _dense_kv(model, toks)
    expect = np.zeros((PAGES, PAGE), bool)
    expect[3, :] = expect[5, :2] = True
    for name in ("k", "v"):
        for layer in range(LAYERS):
            np.testing.assert_array_equal(_written(pool)[name][layer], expect)
            got = np.concatenate([pool[name][layer, 3],
                                  pool[name][layer, 5, :2]])
            np.testing.assert_allclose(got, dense[name][layer], rtol=1e-5,
                                       atol=1e-5)


def test_decode_writes_live_rows_only_and_matches_dense(model):
    """A decode batch of two live rows beside two padded ones (pos -1,
    tables pointing at page 0): each layer gains the live rows' K/V at
    their positions, page 0 stays zero, the logits equal the dense decode
    step, and the passed pool is donated."""
    m, params = model
    runner = _runner(model)
    prompts = {1: [7, 8, 9, 10, 11], 6: [40, 41, 42]}
    tables = {1: [1, 2], 6: [6]}
    for pid, toks in prompts.items():
        runner.prefill_chunk(toks, 0, tables[pid])
    filled = _written(_pool(runner))

    nxt = {1: 12, 6: 43}
    before = runner.pages
    logits = runner.decode([nxt[1], nxt[6]], [tables[1], tables[6]],
                           [len(prompts[1]), len(prompts[6])])
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    pool = _pool(runner)
    for row, pid in enumerate((1, 6)):
        full = prompts[pid] + [nxt[pid]]
        dense = _dense_kv(model, full)
        pos = len(prompts[pid])
        page, slot = tables[pid][pos // PAGE], pos % PAGE
        for name in ("k", "v"):
            np.testing.assert_allclose(pool[name][:, page, slot],
                                       dense[name][:, pos], rtol=1e-5,
                                       atol=1e-5)
        want, _ = m.prefill(params, jnp.asarray([full], jnp.int32))
        np.testing.assert_allclose(logits[row], np.asarray(want[0]),
                                   rtol=2e-5, atol=2e-5)
    written = _written(pool)
    for name in ("k", "v"):
        grew = written[name] & ~filled[name]
        assert grew.sum() == 2 * LAYERS          # two live rows a layer
        assert not written[name][:, 0].any()     # padded rows: dropped

    # a padded batch alone writes nothing
    snap = _pool(runner)
    runner.decode([0, 0], [[0], [0]], [-1, -1])
    after = _pool(runner)
    for name in ("k", "v"):
        np.testing.assert_array_equal(after[name], snap[name])


def test_snapshot_before_a_donating_call_keeps_the_old_page(model):
    """A block snapshot dispatched before a call that overwrites its page
    in the donated pool still reads the page as it was; its payload is
    (n_layers, page_size, Hkv, hd)."""
    runner = _runner(model)
    runner.prefill_chunk([1, 2, 3, 4], 0, [2])
    old = _pool(runner)
    snap = runner.snapshot_block(2)
    runner.prefill_chunk([9, 9, 9, 9], 0, [2])        # overwrites page 2
    assert not np.array_equal(_pool(runner)["k"][:, 2], old["k"][:, 2])
    cfg = runner.model.cfg
    for blk in jax.tree.leaves(runner.materialize(snap)):
        assert blk.shape == (LAYERS, PAGE, cfg.num_kv_heads, cfg.head_dim)
    (seg,) = runner.materialize(snap)
    (blk,) = seg
    for name in ("k", "v"):
        np.testing.assert_array_equal(blk[name], old[name][:, 2])


def test_write_block_takes_host_shaped_pages(model):
    """A (n_layers, page_size, Hkv, hd) payload restores into the lane
    layout at its page only."""
    runner = _runner(model)
    runner.prefill_chunk([1, 2, 3, 4, 5, 6, 7], 0, [4, 6])
    payload = runner.read_block(4)
    before = _pool(runner)
    runner.write_block(7, payload)
    after = _pool(runner)
    for name in ("k", "v"):
        np.testing.assert_array_equal(after[name][:, 7], before[name][:, 4])
        keep = np.arange(PAGES) != 7
        np.testing.assert_array_equal(after[name][:, keep],
                                      before[name][:, keep])


def test_unrolled_segment_matches_dense():
    """A block pattern that leaves an unrolled remainder (3 layers of a
    2-layer pattern: one scanned unit, one unrolled layer) writes and reads
    its pool at a static layer index: prefill and decode logits equal the
    dense path's."""
    from repro.core.engine import PagedRunner
    from repro.models import Model
    from repro.models import transformer as tfm
    cfg = ModelConfig(name="tiny-pattern", family="dense", source="test",
                      num_layers=3, d_model=64, vocab_size=128, num_heads=4,
                      num_kv_heads=2, head_dim=16, d_ff=128, dtype="float32",
                      rope_theta=10_000.0, block_pattern=("attn", "attn"))
    assert [s[0] for s in tfm.segments(cfg)] == ["scan", "unroll"]
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    runner = PagedRunner(m, params, num_pages=PAGES, page_size=PAGE,
                         max_pages_per_seq=4, chunk_size=CHUNK)
    toks = [5, 6, 7, 8, 9]
    got = runner.prefill_chunk(toks, 0, [1, 2])
    want, _ = m.prefill(params, jnp.asarray([toks], jnp.int32))
    np.testing.assert_allclose(got, np.asarray(want[0]), rtol=2e-5, atol=2e-5)
    got = runner.decode([10], [[1, 2]], [len(toks)])
    want, _ = m.prefill(params, jnp.asarray([toks + [10]], jnp.int32))
    np.testing.assert_allclose(got[0], np.asarray(want[0]), rtol=2e-5,
                               atol=2e-5)

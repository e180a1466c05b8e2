"""Paged cache tests: alloc/free/evict lifecycle, per-family pad walks,
ring re-layout, page accounting, and write/read roundtrips through a real
model prefill."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import build_model
from repro.serve.kv import PagedKVCache, pad_caches_to, ring_modulus


def _tiny_model(arch="tinyllama-1.1b"):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# slot lifecycle
# ---------------------------------------------------------------------------


def test_alloc_free_exhaustion():
    _cfg, model, _params = _tiny_model()
    kv = PagedKVCache(model, max_slots=3, max_len=8, page_size=4)
    slots = [kv.alloc() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert kv.alloc() is None  # exhausted
    assert kv.num_free == 0 and kv.num_live == 3
    kv.free(1)
    assert kv.alloc() == 1  # freed slot is reused
    with pytest.raises(ValueError):
        kv.free(7)  # never allocated


def test_eviction_is_counted_and_reusable():
    _cfg, model, _params = _tiny_model()
    kv = PagedKVCache(model, max_slots=2, max_len=8, page_size=4)
    a = kv.alloc()
    kv.evict(a)
    assert kv.stats()["evictions"] == 1
    assert kv.alloc() == a  # evicted slot back in the pool
    assert kv.stats()["allocs"] == 2
    assert kv.stats()["peak_live"] == 1


# ---------------------------------------------------------------------------
# pad walks (per-family cache layout knowledge)
# ---------------------------------------------------------------------------


def test_pad_gqa_and_passthrough():
    node = {
        "attn": {"k": jnp.ones((2, 1, 4, 2, 3)), "v": jnp.ones((2, 1, 4, 2, 3))},
        "ssm": {"state": jnp.ones((1, 2, 3, 4)), "conv": jnp.ones((1, 8, 4))},
        "cross": {"k": jnp.ones((1, 5, 2, 3)), "v": jnp.ones((1, 5, 2, 3))},
    }
    out = pad_caches_to(node, 3)
    assert out["attn"]["k"].shape == (2, 1, 7, 2, 3)  # scan-stacked seq pad
    assert out["ssm"]["state"].shape == (1, 2, 3, 4)  # fixed-size passthrough
    assert out["cross"]["k"].shape == (1, 5, 2, 3)  # static encoder K/V


def test_pad_mla():
    node = {"attn": {"ckv": jnp.ones((1, 4, 6)), "krope": jnp.ones((1, 4, 2))}}
    out = pad_caches_to(node, 2)
    assert out["attn"]["ckv"].shape == (1, 6, 6)
    assert out["attn"]["krope"].shape == (1, 6, 2)
    # pad region is zero; original values preserved
    np.testing.assert_array_equal(np.asarray(out["attn"]["ckv"])[:, 4:], 0.0)
    np.testing.assert_array_equal(np.asarray(out["attn"]["ckv"])[:, :4], 1.0)


def test_ring_growth_relayout():
    # ring of modulus 3 holding positions [0, 1, 2] grows to modulus 5:
    # entry at position p must land at slot p % 5, empty slots pos == -1
    k = jnp.arange(3, dtype=jnp.float32).reshape(1, 3, 1, 1)
    node = {"attn": {"k": k, "v": k + 10, "pos": jnp.asarray([0, 1, 2], jnp.int32)}}
    out = pad_caches_to(node, 0, ring_w=5)["attn"]
    assert ring_modulus({"attn": out}) == 5
    np.testing.assert_array_equal(np.asarray(out["pos"]), [0, 1, 2, -1, -1])
    np.testing.assert_array_equal(np.asarray(out["k"]).ravel(), [0, 1, 2, 0, 0])
    np.testing.assert_array_equal(np.asarray(out["v"]).ravel(), [10, 11, 12, 0, 0])
    with pytest.raises(ValueError):
        pad_caches_to(node, 0, ring_w=2)  # shrink is invalid


# ---------------------------------------------------------------------------
# write/read roundtrip through a real prefill
# ---------------------------------------------------------------------------


def test_write_roundtrip_matches_prefill():
    cfg, model, params = _tiny_model()
    S, MAX = 6, 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0, cfg.vocab_size)
    _logits, cache = jax.jit(model.prefill)(params, {"tokens": tokens})

    kv = PagedKVCache(model, max_slots=2, max_len=MAX, page_size=4)
    slot = kv.alloc(kv.pages_for(S))
    kv.write(slot, cache, S)
    got = kv.read_slot(slot)

    def check(path_cache, path_got):
        if isinstance(path_cache, dict):
            for k in path_cache:
                check(path_cache[k], path_got[k])
            return
        a, b = np.asarray(path_cache), np.asarray(path_got)
        # seq axis was padded out to MAX; prefix must match exactly
        sl = [slice(None)] * b.ndim
        for ax in range(b.ndim):
            if a.shape[ax] != b.shape[ax]:
                sl[ax] = slice(0, a.shape[ax])
        np.testing.assert_array_equal(a, b[tuple(sl)])

    check(cache, got)


def test_write_rejects_dead_slot_and_overflow():
    """One page the size of the whole sequence: the slot holds every
    position a write may name, so only a dead slot and overflow refuse."""
    cfg, model, params = _tiny_model()
    tokens = jnp.zeros((1, 4), jnp.int32)
    _logits, cache = jax.jit(model.prefill)(params, {"tokens": tokens})
    kv = PagedKVCache(model, max_slots=1, max_len=8, page_size=8)
    with pytest.raises(ValueError):
        kv.write(0, cache, 4)  # not allocated
    slot = kv.alloc()
    with pytest.raises(ValueError):
        kv.write(slot, cache, 9)  # exceeds max_len


# ---------------------------------------------------------------------------
# paged pool (DESIGN.md §13)
# ---------------------------------------------------------------------------


def test_paged_page_accounting():
    _cfg, model, _params = _tiny_model()
    kv = PagedKVCache(model, max_slots=3, max_len=24, page_size=8)
    assert kv.pages_per_seq == 3 and kv.num_pages == 9
    s = kv.alloc(kv.pages_for(5))  # 5 tokens -> 1 page
    assert kv.capacity_tokens(s) == 8 and kv.pages_live == 1
    assert kv.grow_to(s, 17)  # 3 pages
    assert kv.capacity_tokens(s) == 24
    assert not kv.grow_to(s, 25)  # beyond max_len
    t = kv.alloc(2)
    assert kv.pages_live == 5 and kv.free_pages == 4
    kv.free(t)
    assert kv.pages_live == 3 and kv.free_pages == 6
    st = kv.stats()
    assert st["page_allocs"] == 5 and st["page_frees"] == 2
    assert st["peak_pages_live"] == 5


def test_paged_grow_is_all_or_nothing():
    """Page pressure: a grow that cannot be fully served allocates nothing
    (the engine preempts instead of holding a partial claim)."""
    _cfg, model, _params = _tiny_model()
    kv = PagedKVCache(model, max_slots=2, max_len=16, page_size=4, num_pages=4)
    a = kv.alloc(1)
    b = kv.alloc(2)
    assert kv.free_pages == 1
    assert not kv.grow_to(a, 12)  # needs 2 more, only 1 free
    assert kv.capacity_tokens(a) == 4  # nothing was taken
    assert kv.grow_to(a, 8)  # 1 more page fits
    assert kv.free_pages == 0
    kv.free(b)
    assert kv.grow_to(a, 12)  # freed pages are reusable
    assert kv.alloc(1) == b  # the slot too


def test_paged_validates_sizing():
    _cfg, model, _params = _tiny_model()
    with pytest.raises(ValueError):  # pool cannot hold one full sequence
        PagedKVCache(model, max_slots=2, max_len=16, page_size=4, num_pages=3)
    kv = PagedKVCache(model, max_slots=1, max_len=6, page_size=64)
    assert kv.page_size == 6  # clamped to max_len
    assert kv.alloc(kv.pages_per_seq + 1) is None  # over per-seq table size


def test_paged_occupancy_and_fragmentation_stats():
    """§13 satellite: the cache reports page-occupancy and internal
    fragmentation, which a page bounds to ``page_size - 1`` tokens."""
    _cfg, model, _params = _tiny_model()
    MAX = 32
    paged = PagedKVCache(model, max_slots=2, max_len=MAX, page_size=8)
    st = paged.stats()
    assert st["pages_live"] == 0 and st["page_occupancy"] == 0.0
    assert st["fragmentation"] == 0.0  # vacuously: nothing live

    ps = paged.alloc(paged.pages_for(10))  # 2 pages of 8
    paged.grow_to(ps, 10)
    st = paged.stats()
    assert st["pages_live"] == 2 and st["page_occupancy"] == 2 / 8
    assert st["fragmentation"] == pytest.approx(1 - 10 / 16)  # only 6 wasted
    paged.free(ps)
    assert paged.stats()["fragmentation"] == 0.0


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-1.3b"])
def test_paged_write_read_matches_the_padded_prefill(arch):
    """Bit-identity invariant: a prefill written to pages and gathered back
    equals the prefill cache zero-padded to max_len (zero page == zero
    padding), rings grown to the cache's modulus."""
    cfg, model, params = _tiny_model(arch)
    S, MAX = 5, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, S), 0, cfg.vocab_size)
    _logits, cache = jax.jit(model.prefill)(params, {"tokens": tokens})

    paged = PagedKVCache(model, max_slots=2, max_len=MAX, page_size=4)
    ps = paged.alloc(paged.pages_for(S))
    paged.write(ps, cache, S)
    want = pad_caches_to(cache, MAX - S, ring_w=ring_modulus(model.cache_shapes(1, MAX)))
    got = paged.read_slot(ps)
    eq = jax.tree.map(lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), want, got)
    assert all(jax.tree.leaves(eq)), eq


def test_paged_write_validates():
    cfg, model, params = _tiny_model()
    tokens = jnp.zeros((1, 4), jnp.int32)
    _logits, cache = jax.jit(model.prefill)(params, {"tokens": tokens})
    kv = PagedKVCache(model, max_slots=1, max_len=8, page_size=4)
    with pytest.raises(ValueError):
        kv.write(0, cache, 4)  # not allocated
    slot = kv.alloc(1)
    with pytest.raises(ValueError):
        kv.write(slot, cache, 9)  # exceeds max_len
    with pytest.raises(ValueError):
        kv.write(slot, cache, 8)  # needs 2 pages, slot holds 1

"""Paged KV cache: equivalence with the contiguous cache + allocator
invariants (property-based under hypothesis, fixed examples without it)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.kernels.decode_attention import ref as da_ref
from repro.models import paged_cache as pc


def test_paged_decode_equals_contiguous():
    """Attention over gathered pages == attention over a contiguous cache."""
    key = jax.random.PRNGKey(0)
    B, S, kv, hd, page = 3, 64, 2, 16, 8
    P = S // page
    ks = jax.random.split(key, 4)
    contiguous_k = jax.random.normal(ks[0], (B, S, kv, hd))
    contiguous_v = jax.random.normal(ks[1], (B, S, kv, hd))
    q = jax.random.normal(ks[2], (B, 1, 4, hd))
    lens = jnp.array([13, 40, 64])

    # scatter the contiguous cache into a shuffled page pool, head-major,
    # at layer 1 of a two-layer stack
    n_pages = B * P + 5
    pages_k = jnp.zeros((2, n_pages, kv, page, hd))
    pages_v = jnp.zeros((2, n_pages, kv, page, hd))
    rng = np.random.default_rng(0)
    ids = rng.permutation(n_pages)[: B * P].reshape(B, P)
    for b in range(B):
        for p in range(P):
            pages_k = pages_k.at[1, ids[b, p]].set(jnp.swapaxes(
                contiguous_k[b, p * page:(p + 1) * page], 0, 1))
            pages_v = pages_v.at[1, ids[b, p]].set(jnp.swapaxes(
                contiguous_v[b, p * page:(p + 1) * page], 0, 1))
    table = jnp.asarray(ids, jnp.int32)

    gk = pc.gather_sequence(pages_k, table, layer=1)
    gv = pc.gather_sequence(pages_v, table, layer=1)
    assert float(jnp.abs(pc.gather_sequence(pages_k, table)).sum()) == 0.0
    np.testing.assert_allclose(np.asarray(gk), np.asarray(contiguous_k))
    out_pc = da_ref.decode_attention_ref(q, gk, gv, lens)
    out_ct = da_ref.decode_attention_ref(q, contiguous_k, contiguous_v, lens)
    np.testing.assert_allclose(np.asarray(out_pc), np.asarray(out_ct))


def test_write_token_lands_in_right_page():
    B, kv, hd, page, P = 2, 2, 8, 4, 3
    pages_k = jnp.zeros((3, 10, kv, page, hd))
    pages_v = jnp.zeros((3, 10, kv, page, hd))
    table = jnp.asarray([[7, 2, 5], [1, 3, 9]], jnp.int32)
    lens = jnp.asarray([5, 2])          # -> page idx 1 off 1 ; page idx 0 off 2
    nk = jnp.ones((B, 1, kv, hd))
    nv = jnp.full((B, 1, kv, hd), 2.0)
    pages_k, pages_v = pc.write_token(pages_k, pages_v, table, lens, nk, nv,
                                      layer=1)
    assert float(pages_k[1, 2, 0, 1, 0]) == 1.0    # slot 0: table[0,1]=2, off 1
    assert float(pages_k[1, 2, 1, 1, 0]) == 1.0    # ... every kv head
    assert float(pages_k[1, 1, 0, 2, 0]) == 1.0    # slot 1: table[1,0]=1, off 2
    assert float(pages_v[1, 2, 0, 1, 0]) == 2.0
    # exactly the two tokens' rows of layer 1, nothing in other layers
    assert float(pages_k.sum()) == B * kv * hd
    assert float(pages_k[0].sum()) == float(pages_k[2].sum()) == 0.0


def _check_allocator_conservation(lengths):
    alloc = pc.PageAllocator(n_pages=256, page_size=8, max_pages_per_seq=16)
    total = alloc.n_pages
    for slot, n in enumerate(lengths):
        alloc.alloc_for(slot, n)
    # no page handed out twice
    seen = [p for pages in alloc.owned.values() for p in pages]
    assert len(seen) == len(set(seen))
    assert len(seen) + len(alloc.free) == total
    for slot in range(len(lengths)):
        alloc.release(slot)
    assert len(alloc.free) == total
    assert alloc.utilization == 0.0


if HAVE_HYPOTHESIS:
    @given(st.lists(st.integers(min_value=1, max_value=100), min_size=1,
                    max_size=12))
    @settings(max_examples=40, deadline=None)
    def test_allocator_conservation(lengths):
        _check_allocator_conservation(lengths)
else:
    @pytest.mark.parametrize("lengths", [
        [1], [100], [8, 16, 3], [7] * 12, list(range(1, 13))])
    def test_allocator_conservation(lengths):
        _check_allocator_conservation(lengths)


def test_write_prompt_scatter_and_unmapped_drop():
    """write_prompt lands each position in its page; padding beyond the
    prompt length and unmapped (-1) table rows never touch the pool."""
    kv, hd, page, P = 2, 4, 4, 3
    pages_k = jnp.zeros((1, 8, kv, page, hd))
    pages_v = jnp.zeros((1, 8, kv, page, hd))
    row = jnp.asarray([5, 1, -1], jnp.int32)
    S = 12
    k = jnp.arange(1, S + 1, dtype=jnp.float32)[None, :, None, None] \
        * jnp.ones((1, S, kv, hd))
    pk, pv = pc.write_prompt(pages_k, pages_v, row, k, 2 * k,
                             jnp.asarray(6, jnp.int32))
    pk, pv = pk[0], pv[0]
    # positions 0..3 -> page 5, positions 4..5 -> page 1, rest dropped
    np.testing.assert_allclose(np.asarray(pk[5, 0, :, 0]), [1, 2, 3, 4])
    np.testing.assert_allclose(np.asarray(pk[5, 1, :, 0]), [1, 2, 3, 4])
    np.testing.assert_allclose(np.asarray(pk[1, 0, :2, 0]), [5, 6])
    assert float(jnp.abs(pk[1, :, 2:]).sum()) == 0.0   # beyond prompt_len
    untouched = [p for p in range(8) if p not in (1, 5)]
    for p in untouched:
        assert float(jnp.abs(pk[p]).sum()) == 0.0
    np.testing.assert_allclose(np.asarray(pv[5, 0, :, 0]), [2, 4, 6, 8])


def test_write_token_unmapped_row_is_dropped():
    """A freed slot (block-table row -1) must not corrupt the pool — its
    pages may already belong to another request."""
    kv, hd, page = 1, 2, 4
    pages_k = jnp.ones((1, 4, kv, page, hd))
    pages_v = jnp.ones((1, 4, kv, page, hd))
    table = jnp.asarray([[-1, -1]], jnp.int32)
    nk = jnp.full((1, 1, kv, hd), 9.0)
    pk, pv = pc.write_token(pages_k, pages_v, table, jnp.asarray([2]), nk, nk)
    np.testing.assert_allclose(np.asarray(pk), np.ones((1, 4, kv, page, hd)))
    np.testing.assert_allclose(np.asarray(pv), np.ones((1, 4, kv, page, hd)))


def test_allocator_fork_shares_full_pages_and_copies_tail():
    alloc = pc.PageAllocator(n_pages=16, page_size=4, max_pages_per_seq=8)
    src = alloc.alloc_for(0, 10)                   # 2 full pages + tail
    dst, tail_src, tail_dst = alloc.fork(0, 1, n_tokens=10)
    assert dst[:2] == src[:2]                      # full pages shared
    assert tail_src == src[2] and tail_dst == dst[2] and tail_dst != tail_src
    assert [alloc.refcount[p] for p in src] == [2, 2, 1]
    assert alloc.refcount[tail_dst] == 1
    assert alloc.pages_shared == 2
    assert alloc.logical_pages == 6                # 3 + 3 chains
    assert alloc.pages_in_use == 4                 # 3 + 1 physical
    assert alloc.unique_pages(0) == 1 and alloc.unique_pages(1) == 1
    # releasing the fork must not free pages the source still references
    alloc.release(1)
    assert all(alloc.refcount[p] == 1 for p in src)
    assert alloc.pages_in_use == 3
    alloc.release(0)
    assert alloc.pages_in_use == 0
    assert sorted(alloc.free) == list(range(16))
    assert all(c == 0 for c in alloc.refcount)


def test_allocator_fork_aligned_prefix_needs_no_copy():
    alloc = pc.PageAllocator(n_pages=8, page_size=4, max_pages_per_seq=4)
    src = alloc.alloc_for(0, 8)                    # exactly 2 full pages
    dst, tail_src, tail_dst = alloc.fork(0, 1, n_tokens=8)
    assert dst == src and tail_src == tail_dst     # pure sharing
    assert alloc.pages_in_use == 2
    assert alloc.fork_cost(8) == 0 and alloc.fork_cost(9) == 1


def test_allocator_cow_page_unshares_before_write():
    alloc = pc.PageAllocator(n_pages=8, page_size=4, max_pages_per_seq=4)
    src = alloc.alloc_for(0, 8)
    alloc.fork(0, 1, n_tokens=8)                   # both pages shared
    cow = alloc.cow_page(1, pos=4)                 # page idx 1
    assert cow is not None
    old, new = cow
    assert old == src[1] and alloc.owned[1][1] == new
    assert alloc.refcount[old] == 1 and alloc.refcount[new] == 1
    assert alloc.cow_page(1, pos=4) is None        # already private
    assert alloc.cow_page(0, pos=7) is None        # src side now unique too
    alloc.release(0)
    alloc.release(1)
    assert alloc.pages_in_use == 0
    assert sorted(alloc.free) == list(range(8))


def test_copy_page_device_op():
    pages = jnp.arange(2 * 4 * 3 * 1 * 2, dtype=jnp.float32
                       ).reshape(2, 4, 3, 1, 2)
    out = pc.copy_page(pages, 1, 3)
    np.testing.assert_allclose(np.asarray(out[:, 3]), np.asarray(pages[:, 1]))
    np.testing.assert_allclose(np.asarray(out[:, :3]),
                               np.asarray(pages[:, :3]))
    # src == dst must be a no-op (used when a fork has no partial tail)
    np.testing.assert_allclose(np.asarray(pc.copy_page(pages, 2, 2)),
                               np.asarray(pages))


def test_allocator_extend_and_exhaustion():
    alloc = pc.PageAllocator(n_pages=4, page_size=4, max_pages_per_seq=4)
    alloc.alloc_for(0, 4)                  # 1 page
    assert alloc.extend(0, 5) is not None  # crosses boundary -> new page
    assert alloc.extend(0, 6) is None      # still fits
    alloc.alloc_for(1, 8)                  # 2 more
    try:
        alloc.alloc_for(2, 4)
        assert False, "pool should be exhausted"
    except MemoryError:
        pass

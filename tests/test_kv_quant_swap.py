"""Quantized KV pages (`cfg.kv_dtype`) and the host-tier page swap
(`host_swap`): write/read roundtrips and the layered tolerance contract,
quant kernels vs the dequant oracle, allocator demote/promote invariants
(property-based under hypothesis, fixed seeds without it), engine
end-to-end behavior, and the predicted-occupancy admission signal."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core.profiler import RuntimeMonitor
from repro.kernels.paged_decode_attention import ops as pda_ops
from repro.kernels.paged_decode_attention import ref as pda_ref
from repro.kernels.paged_prefill_attention import ops as ppa_ops
from repro.kernels.paged_prefill_attention import ref as ppa_ref
from repro.models import paged_cache as pc
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serving.engine import InferenceEngine

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                   max_seq_len=512, dtype="float32", remat=False)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.PRNGKey(0))


def _engine(params, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 128)
    cfg = TINY.with_(kv_dtype=kw.pop("kv_dtype")) if "kv_dtype" in kw \
        else TINY
    return InferenceEngine(cfg, params, **kw)


# ---------------------------------------------------------------------------
# quantize-on-write / dequantize-on-read roundtrip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_prompt_quant_roundtrip_error_bounded(kv_dtype):
    """Bulk write then dequant-gather: every element lands within one
    quantization step of the original (round -> half a step, plus fp8
    mantissa rounding)."""
    page, P, kv, hd = 8, 4, 2, 16
    n_pages = P + 1
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, P * page, kv, hd)) * 3.0
    pages = jnp.zeros((1, n_pages, kv, page, hd),
                      pc.kv_storage_dtype(kv_dtype))
    scales = jnp.ones((1, n_pages, kv), jnp.float32)
    row = jnp.asarray(list(range(P)) + [-1], jnp.int32)
    pages, pages2, scales, scales2 = pc.write_prompt_quant(
        pages, pages, scales, scales, row, x, x, P * page, kv_dtype)
    dq = pc.gather_sequence_dequant(pages, scales, row[None])[:, :P * page]
    # per-(page, head) step = scale; error <= step (int8: half a step from
    # the round, doubled for slack; fp8 adds relative mantissa error)
    step = np.asarray(scales)[0][np.asarray(row[:P])]        # (P, kv)
    step = np.repeat(step[:, None, :], page, axis=1).reshape(
        1, P * page, kv)[..., None]                          # (1, S, kv, 1)
    err = np.abs(np.asarray(dq) - np.asarray(x))
    bound = step * (0.75 if kv_dtype == "int8" else 1.0) \
        + 0.1 * np.abs(np.asarray(x))
    assert (err <= bound).all(), float((err - bound).max())


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_incremental_writes_match_bulk_within_requant_bound(kv_dtype):
    """Token-by-token `write_token_quant` re-rounds the tail page against a
    growing abs-max; the final page must stay within a couple of
    quantization steps of the bulk-written one (docs/serving.md bound)."""
    page, kv, hd = 8, 2, 16
    n_pages = 3
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (1, page, kv, hd)) * 2.0
    row = jnp.asarray([0, -1, -1], jnp.int32)

    zp = jnp.zeros((1, n_pages, kv, page, hd), pc.kv_storage_dtype(kv_dtype))
    zs = jnp.ones((1, n_pages, kv), jnp.float32)
    bk, bv, bks, bvs = pc.write_prompt_quant(zp, zp, zs, zs, row, x, x,
                                             page, kv_dtype)
    ik, iv, iks, ivs = zp, zp, zs, zs
    table = row[None]
    for t in range(page):
        ik, iv, iks, ivs = pc.write_token_quant(
            ik, iv, iks, ivs, table, jnp.asarray([t], jnp.int32),
            x[:, t:t + 1], x[:, t:t + 1], kv_dtype)
    # after the full page both paths saw the same abs-max
    np.testing.assert_allclose(np.asarray(iks[0, 0]), np.asarray(bks[0, 0]),
                               rtol=1e-6)
    dq_b = pc.gather_sequence_dequant(bk, bks, table)[:, :page]
    dq_i = pc.gather_sequence_dequant(ik, iks, table)[:, :page]
    step = np.asarray(bks)[0, 0][None, None, :, None]        # (1,1,kv,1)
    # int8's step is uniform (scale); fp8's is relative (3-bit mantissa,
    # ~12.5% spacing), so re-rounding drift scales with the value
    rel = 0.0 if kv_dtype == "int8" else 0.30
    assert (np.abs(np.asarray(dq_i) - np.asarray(dq_b))
            <= 2.0 * step + rel * np.abs(np.asarray(dq_b)) + 1e-6).all()


def test_quant_write_respects_unmapped_and_inactive_rows():
    """Quantized token writes drop unmapped (-1) rows and active-masked
    rows exactly like the float writer — a stale table row must never
    requantize a page a COW sibling owns."""
    page, kv, hd = 8, 2, 4
    pages = jnp.zeros((2, 4, kv, page, hd), jnp.int8)
    scales = jnp.ones((2, 4, kv), jnp.float32)
    table = jnp.asarray([[2], [3]], jnp.int32)
    lens = jnp.asarray([0, 0], jnp.int32)
    new = jnp.full((2, 1, kv, hd), 5.0)
    k, v, ks, vs = pc.write_token_quant(
        pages, pages, scales, scales, table, lens, new, new,
        "int8", active=jnp.asarray([True, False]), layer=1)
    assert np.asarray(k[1, 2]).any(), "active row must write its page"
    assert not np.asarray(k[1, 3]).any(), "inactive row must be dropped"
    assert not np.asarray(k[0]).any(), "other layers stay untouched"
    np.testing.assert_array_equal(np.asarray(ks[1, 3]), np.ones((kv,)))


# ---------------------------------------------------------------------------
# quant kernels vs dequant oracle (tight) vs float oracle (loose)
# ---------------------------------------------------------------------------

def _quant_pool(key, n_pages, page, kv, hd, kv_dtype, n_rows, lens,
                layer=0, n_layers=3):
    """Float pool + its quantized counterpart written through the real
    prompt writer at layer `layer` of an `n_layers` stack, sharing one
    chained block table."""
    P = max(-(-int(ln) // page) for ln in lens)
    tbl = np.full((n_rows, P), -1, np.int64)
    nxt = 0
    for b, ln in enumerate(lens):
        live = -(-int(ln) // page)
        tbl[b, :live] = np.arange(nxt, nxt + live)
        nxt += live
    table = jnp.asarray(tbl, jnp.int32)
    # layer `layer` holds the values a one-layer token-major pool drew from
    # `key`; the other layers hold other draws
    def stacked(k):
        one = jnp.swapaxes(jax.random.normal(k, (n_pages, page, kv, hd)),
                           1, 2) * 1.5
        rest = jax.random.normal(jax.random.fold_in(k, 1),
                                 (n_layers,) + one.shape)
        return rest.at[layer].set(one)
    kf = stacked(key)
    vf = stacked(jax.random.split(key)[0])
    kq = jnp.zeros(kf.shape, pc.kv_storage_dtype(kv_dtype))
    vq = jnp.zeros_like(kq)
    ks = jnp.ones((n_layers, n_pages, kv), jnp.float32)
    vs = jnp.ones((n_layers, n_pages, kv), jnp.float32)
    for b, ln in enumerate(lens):
        if not ln:
            continue
        seq_k = pc.gather_sequence(kf, table[b:b + 1], layer)[:, :int(ln)]
        seq_v = pc.gather_sequence(vf, table[b:b + 1], layer)[:, :int(ln)]
        kq, vq, ks, vs = pc.write_prompt_quant(
            kq, vq, ks, vs, table[b], seq_k, seq_v, int(ln), kv_dtype,
            layer=layer)
    return kf, vf, kq, vq, ks, vs, table


@pytest.mark.parametrize("P", [4, 32])
@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_paged_decode_quant_kernel_matches_dequant_oracle(kv_dtype, layer, P):
    """Rows: length 0 (unmapped, -1 everywhere), mid-page, full, and a COW
    fork reading the full row's first two pages; at layer 0 and the middle
    layer of a three-layer stack. A grid step streams 16 pages: at 4 pages
    a row the scales of a part step, at 32 a full row over two steps."""
    page, kv, Hq, hd = 8, 2, 8, 32
    lens = [0, 13, P * page]
    key = jax.random.PRNGKey(2)
    kf, vf, kq, vq, ks, vs, table = _quant_pool(
        key, 3 * P + 2, page, kv, hd, kv_dtype, 3, lens, layer=layer)
    table = jnp.concatenate([table, jnp.full((1, P), -1, jnp.int32)
                             .at[0, :2].set(table[2, :2])])
    lens = jnp.asarray(lens + [2 * page], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(3), (4, 1, Hq, hd))
    out = pda_ops.paged_decode_attention_quant(q, kq, vq, ks, vs, table,
                                               lens, layer=layer)
    ref = pda_ref.paged_decode_attention_quant_ref(q, kq, vq, ks, vs,
                                                   table, lens, layer)
    # same quantized pool, two reduction orders: tight
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    # vs the float pool: quantization error only — loose contract
    ref_f = pda_ref.paged_decode_attention_ref(q, kf, vf, table, lens, layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_f),
                               rtol=0.15, atol=0.1)


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("kv_dtype", ["int8"])
def test_paged_prefill_quant_kernel_matches_dequant_oracle(kv_dtype, layer):
    """The ragged quant kernel vs its oracles at layer 0 and a middle
    layer, for one slot alone (a one-row batch) and in a three-row batch:
    the slot, a COW fork of it that shares its first page, and an unmapped
    padding row of length 0."""
    page, P, kv, Hq, hd = 8, 4, 2, 8, 32
    ctx, C = 11, 8                       # chunk starts mid-page
    key = jax.random.PRNGKey(4)
    kf, vf, kq, vq, ks, vs, table = _quant_pool(
        key, P + 4, page, kv, hd, kv_dtype, 1, [ctx + C], layer=layer)
    q = jax.random.normal(jax.random.PRNGKey(5), (3, C, Hq, hd))
    one = (table[:1], jnp.asarray([ctx], jnp.int32),
           jnp.asarray([C], jnp.int32))
    out = ppa_ops.paged_prefill_attention_ragged_quant(
        q[:1], kq, vq, ks, vs, *one, layer=layer)
    ref = ppa_ref.paged_prefill_attention_ragged_quant_ref(
        q[:1], kq, vq, ks, vs, *one, layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    ref_f = ppa_ref.paged_prefill_attention_ragged_ref(q[:1], kf, vf, *one,
                                                       layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_f),
                               rtol=0.15, atol=0.1)
    rows = jnp.stack([table[0], table[0].at[1:].set([P, P + 1]),
                      jnp.full_like(table[0], -1)])
    offs = jnp.asarray([ctx, ctx, 0], jnp.int32)
    lens = jnp.asarray([C, C, 0], jnp.int32)
    out_r = ppa_ops.paged_prefill_attention_ragged_quant(
        q, kq, vq, ks, vs, rows, offs, lens, layer=layer)
    ref_r = ppa_ref.paged_prefill_attention_ragged_quant_ref(
        q, kq, vq, ks, vs, rows, offs, lens, layer)
    np.testing.assert_allclose(np.asarray(out_r[:2]), np.asarray(ref_r[:2]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out_r[:1]), np.asarray(out))


def test_paged_prefill_ragged_quant_rows_match_single():
    """Each ragged row is bitwise the same row ingested alone, as a one-row
    batch, on the same pool (batching adds rows, never changes a row's
    reduction order)."""
    page, P, kv, Hq, hd = 8, 3, 2, 4, 32
    lens_total = [19, 8]
    chunk = [8, 8]
    offs = [11, 0]
    key = jax.random.PRNGKey(6)
    _, _, kq, vq, ks, vs, table = _quant_pool(
        key, 2 * P + 2, page, kv, hd, "int8", 2, lens_total)
    C = max(chunk)
    q = jax.random.normal(jax.random.PRNGKey(7), (2, C, Hq, hd))
    out = ppa_ops.paged_prefill_attention_ragged_quant(
        q, kq, vq, ks, vs, table, jnp.asarray(offs, jnp.int32),
        jnp.asarray(chunk, jnp.int32))
    for r in range(2):
        single = ppa_ops.paged_prefill_attention_ragged_quant(
            q[r:r + 1], kq, vq, ks, vs, table[r:r + 1],
            jnp.asarray(offs[r:r + 1], jnp.int32),
            jnp.asarray(chunk[r:r + 1], jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(out[r:r + 1, :chunk[r]]),
            np.asarray(single[:, :chunk[r]]))


# ---------------------------------------------------------------------------
# allocator host tier: demote / promote / drop
# ---------------------------------------------------------------------------

def test_demote_frees_unique_pages_and_pins_shared():
    alloc = pc.PageAllocator(n_pages=16, page_size=8, max_pages_per_seq=8)
    alloc.alloc_for(0, 24)                         # 3 pages
    alloc.fork(0, 1, 20)                           # shares 2, copies tail
    free0 = len(alloc.free)
    swapped = alloc.demote(1, req_id="r1")
    # only the private tail page was uniquely owned by the fork
    assert [i for i, _ in swapped] == [2]
    assert len(alloc.free) == free0 + 1
    ent = alloc.hosted["r1"]
    assert [i for i, _ in ent["resident"]] == [0, 1]
    for _, p in ent["resident"]:
        assert alloc.refcount[p] == 2, "demoted chain must hold its ref"
    assert alloc.hosted_pages("r1") == 1
    # the parent can still release without freeing the pinned prefix
    alloc.release(0)
    for _, p in ent["resident"]:
        assert alloc.refcount[p] == 1


def test_promote_rebuilds_chain_in_logical_order():
    alloc = pc.PageAllocator(n_pages=16, page_size=8, max_pages_per_seq=8)
    pages = alloc.alloc_for(0, 30)                 # 4 pages
    alloc.fork(0, 1, 16)                           # pages[0:2] shared
    swapped = alloc.demote(0, req_id="q")
    assert [i for i, _ in swapped] == [2, 3]
    uploads = alloc.promote("q", slot=5)
    assert [i for i, _ in uploads] == [2, 3]
    chain = alloc.owned[5]
    assert len(chain) == 4
    assert chain[:2] == pages[:2], "shared prefix pages rejoin in place"
    assert chain[2:] == [p for _, p in uploads]
    assert "q" not in alloc.hosted
    # conservation: every page accounted exactly once per reference
    for p in range(alloc.n_pages):
        refs = sum(1 for ch in alloc.owned.values() for x in ch if x == p)
        assert alloc.refcount[p] == refs


def test_promote_when_dry_raises_and_drop_hosted_releases():
    alloc = pc.PageAllocator(n_pages=4, page_size=8, max_pages_per_seq=4)
    alloc.alloc_for(0, 32)                         # whole pool
    alloc.demote(0, req_id="a")                    # all 4 swapped
    alloc.alloc_for(1, 32)                         # pool refilled elsewhere
    with pytest.raises(MemoryError):
        alloc.promote("a", slot=2)
    assert "a" in alloc.hosted, "failed promote must keep the host entry"
    alloc.release(1)
    alloc.alloc_for(1, 8)
    alloc.fork(1, 2, 8)                            # page-aligned: shared
    alloc.demote(2, req_id="b")                    # nothing unique: resident
    assert alloc.hosted_pages("b") == 0
    shared = alloc.owned[1][0]
    assert alloc.refcount[shared] == 2
    alloc.drop_hosted("b")
    assert alloc.refcount[shared] == 1, "drop must release the pinned ref"
    alloc.drop_hosted("missing")                   # no-op


def _alloc_invariants(alloc):
    """Refcount conservation across device chains, host pins, free list."""
    assert len(set(alloc.free)) == len(alloc.free)
    for p in alloc.free:
        assert alloc.refcount[p] == 0
    for p in range(alloc.n_pages):
        refs = sum(1 for ch in alloc.owned.values() for x in ch if x == p)
        refs += sum(1 for ent in alloc.hosted.values()
                    for _, x in ent["resident"] if x == p)
        assert alloc.refcount[p] == refs, f"page {p}: rc != references"
    assert alloc.pages_in_use == alloc.n_pages - len(alloc.free)


def _run_op_sequence(codes):
    """Interpret a flat int list as allocator ops; invariants hold after
    every step regardless of order (MemoryError is a legal outcome)."""
    alloc = pc.PageAllocator(n_pages=24, page_size=8, max_pages_per_seq=6)
    next_slot, next_req = 0, 0
    for code in codes:
        op = code % 6
        arg = code // 6
        try:
            if op == 0:                            # alloc a fresh slot
                alloc.alloc_for(next_slot, 1 + arg % 40)
                next_slot += 1
            elif op == 1 and alloc.owned:          # fork an existing chain
                src = sorted(alloc.owned)[arg % len(alloc.owned)]
                n_tok = 1 + arg % (len(alloc.owned[src]) * alloc.page_size)
                alloc.fork(src, next_slot, n_tok)
                next_slot += 1
            elif op == 2 and alloc.owned:          # cow guard
                s = sorted(alloc.owned)[arg % len(alloc.owned)]
                alloc.cow_page(s, arg % (len(alloc.owned[s])
                                         * alloc.page_size))
            elif op == 3 and alloc.owned:          # release
                s = sorted(alloc.owned)[arg % len(alloc.owned)]
                alloc.release(s)
            elif op == 4 and alloc.owned:          # demote
                s = sorted(alloc.owned)[arg % len(alloc.owned)]
                alloc.demote(s, f"req{next_req}")
                next_req += 1
            elif op == 5 and alloc.hosted:         # promote or drop
                r = sorted(alloc.hosted)[arg % len(alloc.hosted)]
                if arg % 2:
                    alloc.drop_hosted(r)
                else:
                    alloc.promote(r, next_slot)
                    next_slot += 1
        except MemoryError:
            pass
        _alloc_invariants(alloc)


if HAVE_HYPOTHESIS:
    @given(st.lists(st.integers(min_value=0, max_value=2 ** 16),
                    min_size=1, max_size=40))
    @settings(max_examples=60, deadline=None)
    def test_allocator_swap_invariants(codes):
        _run_op_sequence(codes)
else:
    @pytest.mark.parametrize("seed", range(8))
    def test_allocator_swap_invariants(seed):
        rng = np.random.default_rng(seed)
        _run_op_sequence([int(c) for c in rng.integers(0, 2 ** 16, 60)])


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def test_engine_int8_pool_generates_and_tracks_read_bytes(params):
    eng = _engine(params, kv_dtype="int8", kv_backend="paged", page_size=16)
    for seg in eng.cache["segments"]:
        if "k_pages" in seg:
            assert seg["k_pages"].dtype == jnp.int8
            assert seg["k_scale"].dtype == jnp.float32
    outs = eng.generate([[3, 4, 5, 6], [9, 8]], max_new=6)
    assert all(len(t) >= 1 for t, _ in outs)
    assert all(np.isfinite(lp) for _, lps in outs for lp in lps)
    assert eng.kv_bytes_read > 0, "decode must account its KV traffic"


def test_engine_int8_tracks_float_reference(params):
    """Greedy decode over an int8 pool follows the float engine closely —
    quantization error, not divergence (tokens may legitimately differ at
    near-ties, so the assert is on prompt-conditioned logprobs)."""
    prompts = [[7, 8, 9, 10, 11], [20, 21, 22]]
    ref = _engine(params, kv_backend="paged", page_size=16)
    out_f = ref.generate(prompts, max_new=4)
    eng = _engine(params, kv_dtype="int8", kv_backend="paged", page_size=16)
    out_q = eng.generate(prompts, max_new=4)
    for (tf, lf), (tq, lq) in zip(out_f, out_q):
        assert abs(lf[0] - lq[0]) < 0.15, "first-token logprob drifted"


def test_dense_backend_rejects_quantized_kv(params):
    with pytest.raises(AssertionError):
        _engine(params, kv_dtype="int8")


def test_swap_eviction_is_bit_identical_to_dense(params):
    """Forced preemption under host_swap: the demote/promote path restores
    KV byte-exactly and re-enters decode without a PRNG draw, so greedy
    outputs stay bitwise the dense engine's."""
    prompts = [[65, 66, 67, 68], [70, 71], [80, 81, 82]]
    ref = _engine(params, max_len=64).generate(prompts, max_new=24)
    eng = _engine(params, kv_backend="paged", page_size=8, n_pages=6,
                  max_len=64, host_swap=True)
    out = eng.generate(prompts, max_new=24)
    assert eng.evictions > 0, "a 6-page pool must preempt"
    assert eng.swap_outs > 0 and eng.swap_ins > 0
    assert eng.swap_bytes > 0
    for (td, ld), (tp, lp) in zip(ref, out):
        assert td == tp
        np.testing.assert_array_equal(ld, lp)


def test_swap_resume_skips_prefill_replay(params):
    """An explicit evict/resume cycle: the swap path must re-enter decode
    directly (no pending prefill chunks) and continue the exact token
    stream an uninterrupted engine produces."""
    prompt = [5, 6, 7, 8, 9, 10]
    ref = _engine(params, kv_backend="paged", page_size=8,
                  max_len=64).generate([prompt], max_new=8)
    eng = _engine(params, kv_backend="paged", page_size=8, max_len=64,
                  host_swap=True)
    eng.add_request(0, prompt, max_new=8)
    for _ in range(3):
        eng.step()
    eng._harvest()
    n_before = len(eng.slots[0].tokens)
    assert eng._evict_victim(protect=-1)
    r = eng._resume_queue.pop(0)
    # the newest sampled token's KV is written on the NEXT step, so the
    # snapshotted context is one short of the visible token count
    assert r.swap is not None
    assert r.swap["ctx_len"] == len(prompt) + n_before - 1
    slot = eng._admit_swapped(r)
    assert not eng.slots[slot].prefill_toks, "swap resume must not replay"
    assert len(eng.slots[slot].tokens) == n_before
    while eng.slots[slot].active:
        eng.step()
    (t_ref, l_ref), = ref
    assert eng.slots[slot].tokens == t_ref
    np.testing.assert_array_equal(eng.slots[slot].logprobs, l_ref)


def test_replay_engine_still_bit_identical(params):
    """host_swap=False keeps the legacy evict-and-replay semantics."""
    prompts = [[65, 66, 67, 68], [70, 71], [80, 81, 82]]
    ref = _engine(params, max_len=64).generate(prompts, max_new=24)
    eng = _engine(params, kv_backend="paged", page_size=8, n_pages=6,
                  max_len=64, host_swap=False)
    out = eng.generate(prompts, max_new=24)
    assert eng.evictions > 0 and eng.swap_outs == 0
    for (td, _), (tp, _) in zip(ref, out):
        assert td == tp


def test_swap_eviction_with_int8_pool_recovers(params):
    """Quantized pool + host swap composes: the snapshot moves quantized
    bytes + scales, and the byte-exact restore keeps the quantized stream
    self-consistent (same tokens as an uninterrupted int8 engine)."""
    prompts = [[65, 66, 67, 68], [70, 71], [80, 81, 82]]
    big = _engine(params, kv_dtype="int8", kv_backend="paged", page_size=8,
                  max_len=64)
    ref = big.generate(prompts, max_new=24)
    eng = _engine(params, kv_dtype="int8", kv_backend="paged", page_size=8,
                  n_pages=6, max_len=64, host_swap=True)
    out = eng.generate(prompts, max_new=24)
    assert eng.swap_outs > 0
    for (td, _), (tp, _) in zip(ref, out):
        assert td == tp


# ---------------------------------------------------------------------------
# predicted occupancy tightens admission (Eq.(2) feedback)
# ---------------------------------------------------------------------------

def test_predicted_occupancy_tightens_admission():
    """The length-predictor forecast must raise memory pressure BEFORE the
    pool fills: same physical occupancy, growing queued_expected_tokens ->
    monotonically rising pressure factor."""
    from repro.core.profiler import LatencyModel
    from repro.core.scheduler import DynamicScheduler, EdgeModelInfo
    from repro.serving.network import NetworkModel
    cloud = LatencyModel(t0=0.5, rate=20.0)
    edges = [EdgeModelInfo(name="e", latency=LatencyModel(t0=0.5, rate=25.0),
                           capability=0.5)]
    sched = DynamicScheduler(cloud, edges, NetworkModel(), 4)
    mon = sched.monitor
    mon.update_memory(pages_used=40, pages_total=100)
    mon.kv_page_tokens = 16
    factors = []
    for queued in (0.0, 400.0, 700.0):
        mon.queued_expected_tokens = queued
        factors.append(sched.memory_pressure_factor())
    assert factors[0] < factors[1] < factors[2]
    # forecast occupancy is physical pages + ceil(queued tokens / page)
    mon.queued_expected_tokens = 400.0
    assert mon.kv_predicted_utilization == pytest.approx(
        (40 + np.ceil(400 / 16)) / 100)
    # no geometry observed -> forecast collapses to the physical signal
    mon.kv_page_tokens = 0
    assert mon.kv_predicted_utilization == mon.kv_utilization
    # and an empty queue reproduces the seed behavior exactly
    mon.queued_expected_tokens = 0.0
    mon.kv_page_tokens = 16
    assert mon.kv_predicted_utilization == mon.kv_utilization


def test_monitor_learns_page_geometry_from_engines(params):
    eng = _engine(params, kv_backend="paged", page_size=16)
    mon = RuntimeMonitor()
    mon.observe_engines([eng])
    assert mon.kv_page_tokens == 16

"""Chunked, batched multi-token prefill into the paged-KV engine.

Agreement contract: a chunked engine must reproduce the monolithic
engine's outputs — identical greedy tokens, logprobs within float32
rounding — across chunk sizes, page sizes, fork-suffix replay, and
eviction-resume (including mid-prefill preemption); sampled decode matches
wherever the PRNG streams align (one slot, or fan-out from a parked
prefix). Plus kernel-vs-oracle parity for kernels/paged_prefill_attention
at ragged chunk boundaries.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_prefill_attention import ops as ppa_ops
from repro.kernels.paged_prefill_attention import ref as ppa_ref
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serving.engine import InferenceEngine
from repro.serving.sampler import SamplerConfig

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                   max_seq_len=512, dtype="float32", remat=False)

# mixed prompt lengths: shorter than any chunk, page-unaligned, one chunk
# exactly, spanning several chunks and pages
PROMPTS = [[65 + i for i in range(43)], [70, 71], [80] * 40, [90] * 17,
           [5] * 64]


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.PRNGKey(0))


def _engine(params, chunk=0, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 128)
    kw.setdefault("kv_backend", "paged")
    kw.setdefault("page_size", 16)
    cfg = kw.pop("cfg", TINY).with_(prefill_chunk=chunk)
    return InferenceEngine(cfg, params, **kw)


# Chunked, monolithic and dense prefill put different rows into each call
# and reduce in different orders (XLA picks kernels by shape), so logprobs
# agree to float32 rounding, not bit for bit: on XLA:CPU under JAX 0.9.0
# they differ by at most 1.9e-6. 1e-5 is five times that and far below any
# change of the chosen token. Tokens stay exactly equal.
LOGPROB_ATOL = 1e-5


def _assert_same(a, b):
    for i, ((ta, la), (tb, lb)) in enumerate(zip(a, b)):
        assert ta == tb, f"request {i}: tokens diverge"
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=0, atol=LOGPROB_ATOL,
                                   err_msg=f"request {i}: logprobs diverge")


def _assert_same_replay(a, b):
    """Replay scenarios (fork suffix / eviction resume): tokens must be
    bitwise identical — the chunk-rebuilt KV is — but the one logprob read
    right after a replay comes from (1, V) chunk logits where the
    monolithic path read a (B, V) decode row, and XLA lowers the unembed
    matvec differently by shape (~1 ulp; same precedent as the monolithic
    resume path, whose eviction test also asserts tokens). Every other
    logprob is asserted bitwise via a tight allclose."""
    for i, ((ta, la), (tb, lb)) in enumerate(zip(a, b)):
        assert ta == tb, f"request {i}: tokens diverge"
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"request {i}: logprobs diverge")


# ---------------------------------------------------------------------------
# config contract
# ---------------------------------------------------------------------------

def test_prefill_chunk_validation():
    cfg = TINY.with_(prefill_chunk=256)
    with pytest.raises(AssertionError):
        cfg.validate_paged(16, 128)          # chunk > max_len
    TINY.with_(prefill_chunk=48).validate_paged(16, 128)
    with pytest.raises(AssertionError):
        TINY.with_(prefill_chunk=20, use_pallas=True).validate_paged(16, 128)
    TINY.with_(prefill_chunk=24, use_pallas=True).validate_paged(16, 128)


def test_recurrent_family_falls_back_to_monolithic(params):
    """SSM stacks cannot resume their scan state mid-prompt: the engine must
    silently keep the monolithic path (prefill_chunk forced to 0)."""
    ssm = ModelConfig(name="t-ssm", family="ssm", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                      ssm_state=16, dtype="float32", remat=False,
                      prefill_chunk=16)
    p = transformer.init_params(ssm, jax.random.PRNGKey(0))
    eng = InferenceEngine(ssm, p, max_batch=2, max_len=64,
                          kv_backend="paged", page_size=16)
    assert eng.prefill_chunk == 0
    (toks, _), = eng.generate([[5, 6, 7]], max_new=4)
    assert len(toks) >= 1


# ---------------------------------------------------------------------------
# chunked vs monolithic bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [16, 48])
@pytest.mark.parametrize("page", [8, 16])
def test_chunked_matches_monolithic_greedy(params, chunk, page):
    mono = _engine(params, chunk=0, page_size=page)
    chunked = _engine(params, chunk=chunk, page_size=page)
    _assert_same(mono.generate(PROMPTS, max_new=12),
                 chunked.generate(PROMPTS, max_new=12))
    assert chunked.alloc.pages_in_use == 0


def test_chunked_matches_dense_backend(params):
    """Transitively: chunked paged == monolithic paged == dense."""
    dense = InferenceEngine(TINY, params, max_batch=3, max_len=128)
    chunked = _engine(params, chunk=32)
    _assert_same(dense.generate(PROMPTS, max_new=12),
                 chunked.generate(PROMPTS, max_new=12))


def test_chunked_sampled_bit_identical_serialized(params):
    """With one slot the PRNG stream is position-for-position identical:
    the chunk path takes the same single (1, V) first-token draw a
    monolithic add_request takes, and no draw happens during ingestion."""
    sampler = SamplerConfig(temperature=0.9, top_k=20)
    a = _engine(params, chunk=0, max_batch=1,
                sampler=sampler).generate(PROMPTS[:3], max_new=10)
    b = _engine(params, chunk=16, max_batch=1,
                sampler=sampler).generate(PROMPTS[:3], max_new=10)
    _assert_same(a, b)


def test_chunked_context_capacity_terminates_identically(params):
    prompt = list(range(1, 65))
    mono = _engine(params, chunk=0, max_len=64)
    chunked = _engine(params, chunk=16, max_len=64)
    om = mono.generate([prompt], max_new=8)
    oc = chunked.generate([prompt], max_new=8)
    assert len(oc[0][0]) == 1
    _assert_same(om, oc)


def test_chunked_empty_prompt_does_not_crash(params):
    """A degenerate empty prompt must still produce a token (one
    zero-length chunk supplies the sampling logits, mirroring the
    monolithic path's zero-padded prefill)."""
    eng = _engine(params, chunk=16)
    (toks, lps), = eng.generate([[]], max_new=4)
    assert 1 <= len(toks) <= 4 and len(lps) == len(toks)
    assert eng.alloc.pages_in_use == 0


def test_generate_rejects_mismatched_priorities(params):
    eng = _engine(params, chunk=16)
    with pytest.raises(AssertionError):
        eng.generate([[1, 2], [3, 4]], max_new=2, priorities=[1])


def test_chunk_larger_than_prompt_single_padded_chunk(params):
    """A prompt shorter than one chunk takes exactly one padded ingest."""
    mono = _engine(params, chunk=0)
    chunked = _engine(params, chunk=64)
    _assert_same(mono.generate([[9, 8, 7]], max_new=6),
                 chunked.generate([[9, 8, 7]], max_new=6))


# ---------------------------------------------------------------------------
# fork-suffix replay through chunks (PR 2 nuance folded in)
# ---------------------------------------------------------------------------

FANOUT_PREFIX = [(i % 100) + 1 for i in range(70)]


def test_chunked_fanout_suffix_replay_matches_token_by_token(params):
    """Fork suffixes ingest through multi-token chunks instead of
    token-by-token teacher forcing; greedy tokens AND logprobs must match
    the monolithic engine's pending-token path bitwise (the grouped-SDPA
    chunk read reproduces C decode steps exactly)."""
    suffixes = [[5, 6, 7], [9], [11] * 20]
    mono = _engine(params, chunk=0, max_batch=4)
    chunked = _engine(params, chunk=16, max_batch=4)
    _assert_same_replay(
        mono.generate_fanout(FANOUT_PREFIX, suffixes, max_new=8),
        chunked.generate_fanout(FANOUT_PREFIX, suffixes, max_new=8))
    assert chunked.alloc.pages_in_use == 0
    assert all(c == 0 for c in chunked.alloc.refcount)


@pytest.mark.parametrize("plen", [15, 16, 70])
def test_chunked_fanout_sampled_empty_suffix(params, plen):
    """Empty-suffix fan-out: every fork samples its first token at
    admission in both engines, so even stochastic draws line up. The
    prefix is shorter than a chunk, exactly one, or several: each of its
    chunks enters as a one-row ragged ingest."""
    sampler = SamplerConfig(temperature=0.8, top_k=16)
    prefix = FANOUT_PREFIX[:plen]
    a = _engine(params, chunk=0, max_batch=4,
                sampler=sampler).generate_fanout(
        prefix, [[] for _ in range(3)], max_new=8)
    b = _engine(params, chunk=16, max_batch=4,
                sampler=sampler).generate_fanout(
        prefix, [[] for _ in range(3)], max_new=8)
    _assert_same(a, b)


def test_chunked_fanout_under_pressure_evicts_and_recovers(params):
    """Preempted forks resume by re-forking and chunk-replaying suffix +
    carry; results must match the unconstrained fan-out."""
    N = 3
    big = _engine(params, chunk=8, max_batch=N + 1, page_size=8)
    ref = big.generate_fanout(FANOUT_PREFIX, [[] for _ in range(N)],
                              max_new=12)
    small = _engine(params, chunk=8, max_batch=N + 1, page_size=8,
                    n_pages=12)
    out = small.generate_fanout(FANOUT_PREFIX, [[] for _ in range(N)],
                                max_new=12)
    assert small.evictions > 0
    _assert_same_replay(ref, out)
    assert small.alloc.pages_in_use == 0
    assert sorted(small.alloc.free) == list(range(small.n_pages))


# ---------------------------------------------------------------------------
# eviction-resume through chunks
# ---------------------------------------------------------------------------

def test_chunked_eviction_resume_matches_dense(params):
    prompts = [[65, 66, 67, 68], [70, 71], [80, 81, 82]]
    dense = InferenceEngine(TINY, params, max_batch=3, max_len=64)
    od = dense.generate(prompts, max_new=24)
    chunked = _engine(params, chunk=16, max_len=64, page_size=8, n_pages=6)
    oc = chunked.generate(prompts, max_new=24)
    assert chunked.evictions > 0, "a 6-page pool must preempt"
    _assert_same_replay(od, oc)
    assert chunked.alloc.pages_in_use == 0


def test_eviction_mid_prefill_restarts_chunks(params, monkeypatch):
    """A slot preempted while still ingesting chunks must restart its
    prompt from scratch on resume and still match the unconstrained run."""
    prompts = [[7] * 8, [9] * 8, [33] * 40]
    big = _engine(params, chunk=8, max_len=64, page_size=8)
    ref = big.generate(prompts, max_new=20)

    mid_prefill_evictions = []
    orig = InferenceEngine._evict_victim

    def spy(self, protect):
        ingesting = [i for i, s in enumerate(self.slots)
                     if s.active and s.prefill_toks]
        ok = orig(self, protect)
        if ok:
            mid_prefill_evictions.extend(
                i for i in ingesting if self.slots[i].evicted)
        return ok

    monkeypatch.setattr(InferenceEngine, "_evict_victim", spy)
    small = _engine(params, chunk=8, max_len=64, page_size=8, n_pages=8)
    out = small.generate(prompts, max_new=20)
    assert small.evictions > 0
    assert mid_prefill_evictions, \
        "scenario must preempt a slot while it is still ingesting chunks"
    _assert_same_replay(ref, out)
    assert small.alloc.pages_in_use == 0


# ---------------------------------------------------------------------------
# oracle vs Pallas kernel parity at ragged chunk boundaries
# ---------------------------------------------------------------------------

def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


def _stacked_pool(key, shape, dtype, layer, n_layers=3):
    """(n_layers, n_pages, Hkv, ps, hd) head-major pool whose layer `layer`
    holds what a one-layer token-major pool of `shape` (n_pages, ps, Hkv,
    hd) drew from `key`; the other layers hold other draws."""
    one = jnp.swapaxes(jax.random.normal(key, shape, dtype), 1, 2)
    rest = jax.random.normal(jax.random.fold_in(key, 1),
                             (n_layers,) + one.shape, dtype)
    return rest.at[layer].set(one)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Hq,Hkv,hd,ps,C,offset,clen", [
    (4, 2, 64, 16, 32, 0, 32),     # first chunk, exact fill
    (4, 2, 64, 16, 32, 32, 20),    # ragged final chunk
    (8, 2, 32, 8, 16, 23, 9),      # page-unaligned offset, partial chunk
    (4, 4, 64, 16, 24, 40, 24),    # q_per_kv == 1
])
@pytest.mark.parametrize("layer", [0, 1])
def test_paged_prefill_kernel_parity(dtype, Hq, Hkv, hd, ps, C, offset, clen,
                                     layer):
    """The ragged kernel vs its oracle, at layer 0 and the middle layer of
    a three-layer stack, for one slot alone (the one-row batch a prefix
    prefill dispatches) and in a three-row batch: the slot, a COW fork
    sharing its first page, and an unmapped padding row of length 0. The
    slot's row in the batch is bitwise its one-row call."""
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    n_pages, P = 14, 8
    q = jax.random.normal(ks[0], (1, C, Hq, hd), dtype)
    kp = _stacked_pool(ks[1], (n_pages, ps, Hkv, hd), dtype, layer)
    vp = _stacked_pool(ks[2], (n_pages, ps, Hkv, hd), dtype, layer)
    need = -(-(offset + clen) // ps)
    perm = np.asarray(jax.random.permutation(ks[3], n_pages))
    row = np.full((P,), -1, np.int32)
    row[:need] = perm[:need]
    fork = np.full((P,), -1, np.int32)
    fork[0], fork[1:need] = row[0], perm[need:2 * need - 1]
    row = jnp.asarray(row)
    one = (row[None], jnp.asarray([offset], jnp.int32),
           jnp.asarray([clen], jnp.int32))
    out = ppa_ops.paged_prefill_attention_ragged(q, kp, vp, *one, layer=layer)
    ref = ppa_ref.paged_prefill_attention_ragged_ref(q, kp, vp, *one, layer)
    np.testing.assert_allclose(
        np.asarray(out[:, :clen], np.float32),
        np.asarray(ref[:, :clen], np.float32), **_tol(dtype))
    assert not np.any(np.isnan(np.asarray(out[:, :clen], np.float32)))

    qr = jnp.concatenate(
        [q, jax.random.normal(jax.random.fold_in(ks[0], 1),
                              (2, C, Hq, hd), dtype)])
    rows = jnp.stack([row, jnp.asarray(fork), jnp.full_like(row, -1)])
    offs = jnp.asarray([offset, offset, 0], jnp.int32)
    lens = jnp.asarray([clen, clen, 0], jnp.int32)
    out_r = ppa_ops.paged_prefill_attention_ragged(qr, kp, vp, rows, offs,
                                                   lens, layer=layer)
    ref_r = ppa_ref.paged_prefill_attention_ragged_ref(qr, kp, vp, rows,
                                                       offs, lens, layer)
    np.testing.assert_allclose(
        np.asarray(out_r[:2, :clen], np.float32),
        np.asarray(ref_r[:2, :clen], np.float32), **_tol(dtype))
    np.testing.assert_array_equal(np.asarray(out_r[:1, :clen], np.float32),
                                  np.asarray(out[:, :clen], np.float32))


def test_paged_prefill_kernel_ignores_poisoned_pages():
    """NaN in unmapped pages and in positions past offset+chunk_len must
    never reach the output (zero-masked before the MXU)."""
    Hq, Hkv, hd, ps, C = 4, 2, 32, 8, 16
    offset, clen = 10, 12
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    n_pages, P = 8, 6
    q = jax.random.normal(ks[0], (1, C, Hq, hd))
    kp = np.array(_stacked_pool(ks[1], (n_pages, ps, Hkv, hd), jnp.float32,
                                1))
    vp = np.array(_stacked_pool(ks[2], (n_pages, ps, Hkv, hd), jnp.float32,
                                1))
    total = offset + clen
    need = -(-total // ps)
    row = np.full((1, P), -1, np.int32)
    row[0, :need] = np.arange(need)
    one = (jnp.asarray(row), jnp.asarray([offset], jnp.int32),
           jnp.asarray([clen], jnp.int32))
    clean = ppa_ops.paged_prefill_attention_ragged(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), *one, layer=1)
    kp[[0, 2]], vp[[0, 2]] = np.nan, np.nan               # other layers
    kp[1, need:], vp[1, need:] = np.nan, np.nan           # unmapped pages
    tail = total - (need - 1) * ps
    kp[1, need - 1, :, tail:] = np.nan                    # past total
    vp[1, need - 1, :, tail:] = np.nan
    out = ppa_ops.paged_prefill_attention_ragged(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), *one, layer=1)
    np.testing.assert_array_equal(np.asarray(out[:, :clen]),
                                  np.asarray(clean[:, :clen]))


def test_use_pallas_chunked_engine_matches_oracle(params):
    """cfg.use_pallas routes the chunk read through the kernel; greedy
    tokens must agree with the oracle engine (flash reassociation is not a
    bitwise guarantee, but greedy argmax agrees in practice)."""
    oracle = _engine(params, chunk=16)
    kern = _engine(params, chunk=16, cfg=TINY.with_(use_pallas=True))
    oo = oracle.generate(PROMPTS[:3], max_new=10)
    ok = kern.generate(PROMPTS[:3], max_new=10)
    for (to, _), (tk, _) in zip(oo, ok):
        assert to == tk


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_ttft_recorded_per_request(params):
    eng = _engine(params, chunk=16)
    eng.generate(PROMPTS[:3], max_new=6)
    assert sorted(eng.ttft) == [0, 1, 2]
    assert all(v > 0 for v in eng.ttft.values())

"""InferenceEngine unit tests: slot recycling, EOS termination, prompt
bucketing, result ordering, and dense-vs-paged backend equivalence."""
import jax
import numpy as np
import pytest

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serving.engine import InferenceEngine, _bucket
from repro.serving.sampler import SamplerConfig

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                   max_seq_len=512, dtype="float32", remat=False)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.PRNGKey(0))


def _engine(params, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 128)
    return InferenceEngine(TINY, params, **kw)


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def test_bucket_is_power_of_two_cover():
    assert _bucket(1) == 32
    assert _bucket(32) == 32
    assert _bucket(33) == 64
    assert _bucket(100) == 128


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_long_prompt_truncates_to_max_len(params, backend):
    kw = {"kv_backend": "paged", "page_size": 16} if backend == "paged" else {}
    eng = _engine(params, max_len=32, **kw)
    (toks, lps), = eng.generate([list(range(1, 100))], max_new=4)
    assert 1 <= len(toks) <= 4 and len(lps) == len(toks)


def test_context_capacity_terminates_identically(params):
    """A prompt that fills max_len exactly stops after one sampled token in
    both backends (decoding past capacity would overwrite live cache)."""
    prompt = list(range(1, 65))          # bucket 64 == max_len
    outs = {}
    for backend in ("dense", "paged"):
        kw = {"kv_backend": "paged", "page_size": 16} \
            if backend == "paged" else {}
        eng = _engine(params, max_len=64, **kw)
        (toks, _), = eng.generate([prompt], max_new=8)
        outs[backend] = toks
        assert len(toks) == 1
    assert outs["dense"] == outs["paged"]


# ---------------------------------------------------------------------------
# slot lifecycle
# ---------------------------------------------------------------------------

def test_slot_recycling_more_requests_than_slots(params):
    eng = _engine(params, max_batch=2)
    prompts = [[10 + i, 20, 30] for i in range(7)]
    outs = eng.generate(prompts, max_new=5)
    assert len(outs) == 7
    assert all(1 <= len(t) <= 5 for t, _ in outs)
    assert len(eng.free_slots()) == eng.max_batch


def test_add_request_raises_when_full(params):
    eng = _engine(params, max_batch=1)
    eng.add_request(0, [5, 6, 7], max_new=100)
    with pytest.raises(RuntimeError):
        eng.add_request(1, [8, 9], max_new=4)


def test_eos_frees_slot_immediately(params):
    eng = _engine(params, eos_id=0)
    slot = eng.add_request(0, [5, 6, 7], max_new=40)
    while eng.slots[slot].active:
        assert eng.step()
    s = eng.slots[slot]
    assert s.tokens[-1] == eng.eos_id or s.generated == s.max_new
    assert slot in eng.free_slots()
    # EOS anywhere in the stream must have ended generation right there
    if eng.eos_id in s.tokens:
        assert s.tokens.index(eng.eos_id) == len(s.tokens) - 1


def test_max_new_terminates(params):
    eng = _engine(params)
    (toks, _), = eng.generate([[9, 8, 7]], max_new=3)
    assert len(toks) <= 3


# ---------------------------------------------------------------------------
# ordering / isolation
# ---------------------------------------------------------------------------

def test_generate_preserves_order_with_mixed_lengths(params):
    eng = _engine(params, max_batch=2)
    prompts = [[40] * 60, [50, 51], [60] * 33, [70], [80] * 9]
    outs = eng.generate(prompts, max_new=6)
    assert len(outs) == len(prompts)
    # each prompt's result must match a solo run of the same prompt (greedy)
    for i in (1, 3):
        solo = _engine(params, max_batch=1)
        (ref, _), = solo.generate([prompts[i]], max_new=6)
        assert outs[i][0] == ref


def test_greedy_is_deterministic_across_engines(params):
    a = _engine(params).generate([[33, 34, 35]], max_new=8)
    b = _engine(params).generate([[33, 34, 35]], max_new=8)
    assert a == b


# ---------------------------------------------------------------------------
# dense vs paged equivalence
# ---------------------------------------------------------------------------

def test_dense_paged_equivalence_mixed_lengths(params):
    prompts = [[65, 66, 67, 68], [70, 71], [80] * 40, [90]]
    dense = _engine(params)
    paged = _engine(params, kv_backend="paged", page_size=16)
    od = dense.generate(prompts, max_new=16)
    op = paged.generate(prompts, max_new=16)
    for i, ((td, ld), (tp, lp)) in enumerate(zip(od, op)):
        assert td == tp, f"prompt {i}: tokens diverge"
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp),
                                      err_msg=f"prompt {i}: logprobs diverge")


def test_dense_paged_equivalence_with_sampling(params):
    """Same PRNG stream + same request order -> identical stochastic samples."""
    sampler = SamplerConfig(temperature=0.8, top_k=16)
    prompts = [[12, 13, 14], [25, 26]]
    od = _engine(params, sampler=sampler).generate(prompts, max_new=10)
    op = _engine(params, sampler=sampler,
                 kv_backend="paged", page_size=16).generate(prompts, max_new=10)
    for (td, _), (tp, _) in zip(od, op):
        assert td == tp


def test_paged_pages_freed_on_completion(params):
    eng = _engine(params, kv_backend="paged", page_size=16)
    eng.generate([[65, 66, 67], [70] * 20], max_new=12)
    assert eng.alloc.pages_in_use == 0
    assert eng.memory_stats()["utilization"] == 0.0
    assert eng.peak_pages > 0
    assert np.all(eng.block_table == -1)


def test_paged_pool_exhaustion_evicts_and_recovers(params):
    """A pool too small for the full batch must preempt (evict + resubmit)
    the youngest request, and still produce dense-identical results."""
    prompts = [[65, 66, 67, 68], [70, 71], [80, 81, 82]]
    paged = _engine(params, kv_backend="paged", page_size=8, n_pages=6,
                    max_len=64)
    dense = _engine(params, max_len=64)
    op = paged.generate(prompts, max_new=24)
    od = dense.generate(prompts, max_new=24)
    assert paged.evictions > 0, "pool of 6 pages must trigger preemption"
    for (td, _), (tp, _) in zip(od, op):
        assert td == tp
    assert paged.alloc.pages_in_use == 0


def test_paged_lone_request_too_big_raises(params):
    eng = _engine(params, kv_backend="paged", page_size=8, n_pages=2,
                  max_len=64)
    with pytest.raises(MemoryError):
        eng.generate([[65, 66, 67]], max_new=40)


def test_memory_stats_shape(params):
    for eng in (_engine(params),
                _engine(params, kv_backend="paged", page_size=16)):
        st = eng.memory_stats()
        assert {"backend", "pages_total", "pages_in_use", "utilization",
                "evictions"} <= set(st)
        assert st["pages_in_use"] == 0


# ---------------------------------------------------------------------------
# copy-on-write prefix sharing (ensemble fan-out)
# ---------------------------------------------------------------------------

FANOUT_PROMPT = [(i % 100) + 1 for i in range(70)]   # 5 pages at page 16


def test_fanout_bit_identical_to_independent_and_under_06x_peak(params):
    """N-way fan-out from one prefix must produce exactly the tokens and
    logprobs of N independent paged submissions while holding the prefix
    once: peak page usage < 0.6x the unshared peak (prefix >= 4 pages)."""
    N = 4
    unshared = _engine(params, max_batch=N + 1, kv_backend="paged",
                       page_size=16)
    shared = _engine(params, max_batch=N + 1, kv_backend="paged",
                     page_size=16)
    ou = unshared.generate([FANOUT_PROMPT] * N, max_new=8)
    os_ = shared.generate_fanout(FANOUT_PROMPT, [[] for _ in range(N)],
                                 max_new=8)
    for i, ((tu, lu), (ts, ls)) in enumerate(zip(ou, os_)):
        assert tu == ts, f"fork {i}: tokens diverge"
        np.testing.assert_array_equal(np.asarray(lu), np.asarray(ls),
                                      err_msg=f"fork {i}: logprobs diverge")
    peak_u = unshared.memory_stats()["peak_pages"]
    peak_s = shared.memory_stats()["peak_pages"]
    assert len(FANOUT_PROMPT) >= 4 * 16            # prefix >= 4 pages
    assert peak_s < 0.6 * peak_u, (peak_s, peak_u)
    # full drain: every refcount back to zero, no page leaked or double-freed
    assert shared.alloc.pages_in_use == 0
    assert all(c == 0 for c in shared.alloc.refcount)
    assert sorted(shared.alloc.free) == list(range(shared.n_pages))


def test_fanout_sampled_bit_identical(params):
    """Stochastic sampling: same max_batch + same PRNG stream -> the fan-out
    draws exactly what independent submissions would (the prefix parks in
    the LAST slot so forks land on the same batch rows)."""
    sampler = SamplerConfig(temperature=0.8, top_k=16)
    N = 3
    ou = _engine(params, max_batch=N + 1, kv_backend="paged", page_size=16,
                 sampler=sampler).generate([FANOUT_PROMPT] * N, max_new=8)
    os_ = _engine(params, max_batch=N + 1, kv_backend="paged", page_size=16,
                  sampler=sampler).generate_fanout(
        FANOUT_PROMPT, [[] for _ in range(N)], max_new=8)
    assert ou == os_
    # distinct forks actually diverge (they are independent samples)
    assert len({tuple(t) for t, _ in os_}) > 1


def test_fanout_suffixes_and_sharing_telemetry(params):
    """Per-group suffixes are teacher-forced on top of the shared prefix;
    the monitor's windowed telemetry must see the sharing."""
    from repro.core.profiler import RuntimeMonitor
    eng = _engine(params, max_batch=4, kv_backend="paged", page_size=16)
    outs = eng.generate_fanout(FANOUT_PROMPT, [[5, 6, 7], [9], [11, 12]],
                               max_new=6)
    assert len(outs) == 3
    for toks, lps in outs:
        assert 1 <= len(toks) <= 6 and len(lps) == len(toks)
    assert eng.alloc.pages_in_use == 0
    mon = RuntimeMonitor()
    mon.observe_engines([eng])
    assert mon.kv_pages_shared > 0
    assert mon.kv_pages_logical > mon.kv_pages_used
    assert mon.kv_sharing_savings > 0.0
    assert 0.0 < mon.kv_shared_fraction <= 1.0


def test_evicting_a_fork_never_frees_sibling_pages(params):
    """A pool too small for the whole fan-out preempts forks; refcounted
    release must leave sibling (and prefix) pages intact, and the resumed
    forks must still produce the unconstrained results (greedy)."""
    N = 3
    big = _engine(params, max_batch=N + 1, kv_backend="paged", page_size=8)
    ref = big.generate([FANOUT_PROMPT] * N, max_new=12)
    small = _engine(params, max_batch=N + 1, kv_backend="paged", page_size=8,
                    n_pages=12)
    out = small.generate_fanout(FANOUT_PROMPT, [[] for _ in range(N)],
                                max_new=12)
    assert small.evictions > 0, "a 12-page pool must preempt"
    for a, b in zip(ref, out):
        assert a == b
    assert small.alloc.pages_in_use == 0
    assert all(c == 0 for c in small.alloc.refcount)
    assert sorted(small.alloc.free) == list(range(small.n_pages))


def test_fanout_dense_backend_falls_back(params):
    a = _engine(params).generate_fanout([1, 2, 3], [[4], [5]], max_new=4)
    b = _engine(params).generate([[1, 2, 3, 4], [1, 2, 3, 5]], max_new=4)
    assert a == b


def test_release_prefix_frees_parked_pages(params):
    eng = _engine(params, kv_backend="paged", page_size=16)
    slot = eng.prefill_prefix(FANOUT_PROMPT)
    assert eng.slots[slot].parked
    assert slot not in eng.free_slots()
    assert eng.alloc.pages_in_use == 5
    eng.release_prefix(slot)
    assert eng.alloc.pages_in_use == 0
    assert slot in eng.free_slots()


# ---------------------------------------------------------------------------
# priority-aware eviction (SLA class / sketch level ordering)
# ---------------------------------------------------------------------------

def test_eviction_prefers_low_priority_over_youth(params):
    """Victim selection orders by (priority, then youth): a latency-critical
    slot admitted LAST must survive while an older opportunistic one is
    preempted — the pre-priority engine would have evicted the youngest."""
    eng = _engine(params, kv_backend="paged", page_size=16)
    lo = eng.add_request(0, [5, 6, 7], max_new=40, priority=0)
    hi = eng.add_request(1, [8, 9, 10], max_new=40, priority=1)
    assert eng.slots[hi].arrival > eng.slots[lo].arrival
    assert eng._evict_victim(protect=-1)
    assert eng.slots[lo].evicted and not eng.slots[lo].active
    assert eng.slots[hi].active, "high-priority slot must not be evicted"


def test_eviction_equal_priority_falls_back_to_youngest(params):
    eng = _engine(params, kv_backend="paged", page_size=16)
    old = eng.add_request(0, [5, 6, 7], max_new=40)
    young = eng.add_request(1, [8, 9, 10], max_new=40)
    assert eng._evict_victim(protect=-1)
    assert eng.slots[young].evicted
    assert eng.slots[old].active


def test_priority_preserved_across_resume(params):
    """A preempted request resumes with its priority intact (threaded
    through the resume queue), and still completes correctly."""
    prompts = [[65, 66, 67, 68], [70, 71], [80, 81, 82]]
    ref = _engine(params, max_len=64).generate(prompts, max_new=24)
    eng = _engine(params, kv_backend="paged", page_size=8, n_pages=6,
                  max_len=64)
    out = eng.generate(prompts, max_new=24, priorities=[2, 1, 0])
    assert eng.evictions > 0
    for (td, _), (tp, _) in zip(ref, out):
        assert td == tp
    assert eng.alloc.pages_in_use == 0


# ---------------------------------------------------------------------------
# serving-layer bug sweep
# ---------------------------------------------------------------------------

def test_dense_consume_peak_is_windowed(params):
    """Dense fleets drain to zero active slots between synchronous requests;
    consume_peak must report the window's high-water mark, not ~0."""
    eng = _engine(params)                          # dense, max_batch 3
    eng.generate([[1, 2, 3], [4, 5], [6]], max_new=4)
    assert sum(1 for s in eng.slots if s.active) == 0      # drained
    assert eng.consume_peak() == 3
    assert eng.consume_peak() == 0                 # window reset


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_inactive_slot_lengths_do_not_drift(params, backend):
    """Freed slots must stop advancing their cache lengths: before the fix
    they drifted past max_len and kept issuing clipped writes."""
    kw = {"kv_backend": "paged", "page_size": 16} if backend == "paged" else {}
    eng = _engine(params, max_batch=2, max_len=64, **kw)
    s1 = eng.add_request(1, [8, 9, 10], max_new=40)        # long-running
    s0 = eng.add_request(0, [5, 6, 7], max_new=1)          # done immediately
    assert s0 != s1 and not eng.slots[s0].active
    frozen = eng.slots[s0].ctx_len
    while eng.slots[s1].active:
        eng.step()
    lens = np.asarray(eng.cache["lengths"])
    assert lens[s0] == frozen, "inactive slot length drifted"
    assert lens[s1] <= eng.max_len


def test_monitor_sees_windowed_peak_after_drain(params):
    """The pipeline observes engines between (synchronous) requests, when
    pools have drained to zero — the monitor must still see the high-water
    mark of the window, or memory pressure would always read 0."""
    from repro.core.profiler import RuntimeMonitor
    eng = _engine(params, kv_backend="paged", page_size=16)
    eng.generate([[65, 66, 67], [70] * 20], max_new=12)
    assert eng.alloc.pages_in_use == 0           # drained
    mon = RuntimeMonitor()
    mon.observe_engines([eng])
    assert mon.kv_pages_used > 0
    assert mon.kv_utilization > 0.0
    # window resets: a second observation with no traffic reads current (0)
    mon.observe_engines([eng])
    assert mon.kv_pages_used == 0

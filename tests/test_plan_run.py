"""Plan/run engine step: batched ragged ingest vs the serial fallback vs
monolithic prefill (three-way agreement: identical tokens, logprobs within
float32 rounding), the one-table-push-per-step contract, admission-stamp
pruning under churn, surfaced prompt truncation, and the bounded score
buffer."""
import jax
import numpy as np
import pytest

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serving.engine import InferenceEngine
from repro.serving.sampler import SamplerConfig

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                   max_seq_len=512, dtype="float32", remat=False)

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


# The three schedulers batch different rows into each call (one chunk per
# step, R rows per step, or one monolithic prefill), and XLA picks kernels
# and reduction orders by shape, so logprobs agree to float32 rounding, not
# bit for bit: on XLA:CPU under JAX 0.9.0 they differ by at most 1.9e-6.
# 1e-5 is five times that and far below any change of the chosen token.
# Tokens stay exactly equal.
LOGPROB_ATOL = 1e-5


def _assert_same(a, b):
    for i, ((ta, la), (tb, lb)) in enumerate(zip(a, b)):
        assert ta == tb, f"request {i}: tokens diverge"
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=0, atol=LOGPROB_ATOL,
                                   err_msg=f"request {i}: logprobs diverge")


def _assert_same_replay(a, b):
    for i, ((ta, la), (tb, lb)) in enumerate(zip(a, b)):
        assert ta == tb, f"request {i}: tokens diverge"
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"request {i}: logprobs diverge")


# ---------------------------------------------------------------------------
# three-way agreement: batched ragged == serial one-chunk == monolithic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [16, 48])
@pytest.mark.parametrize("page", [8, 16])
def test_three_way_greedy(params, chunk, page):
    mono = _engine(params, chunk=0, page_size=page)
    serial = _engine(params, chunk=chunk, page_size=page,
                     ragged_ingest=False)
    batched = _engine(params, chunk=chunk, page_size=page)
    om = mono.generate(PROMPTS, max_new=12)
    os_ = serial.generate(PROMPTS, max_new=12)
    ob = batched.generate(PROMPTS, max_new=12)
    _assert_same(om, os_)
    _assert_same(om, ob)
    assert batched.alloc.pages_in_use == 0
    assert serial.alloc.pages_in_use == 0


def test_three_way_sampled_serialized(params):
    """One slot serializes the PRNG stream position-for-position: all three
    schedulers take identical draws."""
    sampler = SamplerConfig(temperature=0.9, top_k=20)
    outs = [_engine(params, chunk=c, max_batch=1, ragged_ingest=r,
                    sampler=sampler).generate(PROMPTS[:3], max_new=10)
            for c, r in ((0, True), (16, False), (16, True))]
    _assert_same(outs[0], outs[1])
    _assert_same(outs[0], outs[2])


def test_three_way_fork_suffixes(params):
    """Fork fan-out: suffix replay rides the (batched) chunk path; serial
    and batched must agree (identical tokens, LOGPROB_ATOL), and both match
    monolithic up to the documented (1, V)-vs-(B, V) unembed ulp on the
    post-replay logprob."""
    prefix = [(i % 100) + 1 for i in range(70)]
    suffixes = [[5, 6, 7], [9], [11] * 20]
    mono = _engine(params, chunk=0, max_batch=4)
    serial = _engine(params, chunk=16, max_batch=4, ragged_ingest=False)
    batched = _engine(params, chunk=16, max_batch=4)
    om = mono.generate_fanout(prefix, suffixes, max_new=8)
    os_ = serial.generate_fanout(prefix, suffixes, max_new=8)
    ob = batched.generate_fanout(prefix, suffixes, max_new=8)
    _assert_same(os_, ob)
    _assert_same_replay(om, ob)
    assert batched.alloc.pages_in_use == 0


def test_three_way_eviction_resume(params):
    """A starved pool preempts and resumes; every scheduler converges to
    the unconstrained result. Serial and batched may preempt at different
    step boundaries (batched ingest moves the pressure point), so the one
    post-resume logprob carries the documented replay ulp — tokens are
    still bitwise."""
    prompts = [[65, 66, 67, 68], [70, 71], [80, 81, 82]]
    ref = _engine(params, chunk=16, max_len=64,
                  page_size=8).generate(prompts, max_new=24)
    serial = _engine(params, chunk=16, max_len=64, page_size=8, n_pages=6,
                     ragged_ingest=False)
    batched = _engine(params, chunk=16, max_len=64, page_size=8, n_pages=6)
    os_ = serial.generate(prompts, max_new=24)
    ob = batched.generate(prompts, max_new=24)
    assert serial.evictions > 0 and batched.evictions > 0
    _assert_same_replay(os_, ob)
    _assert_same_replay(ref, ob)
    assert batched.alloc.pages_in_use == 0


# ---------------------------------------------------------------------------
# plan/run step contract
# ---------------------------------------------------------------------------

def test_push_table_at_most_once_per_step(params, monkeypatch):
    """The step loop batches all host block-table edits (growth, COW,
    eviction, frees) into at most ONE device push per step."""
    pushes = []
    orig_push = InferenceEngine._push_table
    orig_step = InferenceEngine.step

    def spy_push(self):
        pushes.append("push")
        return orig_push(self)

    def spy_step(self):
        before = len(pushes)
        out = orig_step(self)
        assert len(pushes) - before <= 1, \
            "step() pushed the block table more than once"
        return out

    monkeypatch.setattr(InferenceEngine, "_push_table", spy_push)
    monkeypatch.setattr(InferenceEngine, "step", spy_step)
    # eviction pressure + mixed ingest/decode exercises every table-dirtying
    # path inside the step loop
    eng = _engine(params, chunk=16, max_len=64, page_size=8, n_pages=6)
    eng.generate([[65, 66, 67, 68], [70, 71], [80, 81, 82]], max_new=24)
    assert eng.evictions > 0
    assert pushes, "scenario never pushed the table at all"


def test_step_defers_decode_harvest(params):
    """Dispatch and readback are split across steps: after a decode-only
    step the engine holds an in-flight bundle, and the next step commits
    it before planning."""
    eng = _engine(params, chunk=16)
    eng.add_request(0, [1, 2, 3], max_new=4)
    while eng.slots[0].prefill_toks:
        eng.step()
    n0 = len(eng.slots[0].tokens)       # first token (eager finish draw)
    assert eng.step()                   # dispatches decode, commits nothing
    assert eng._pending_decode is not None
    assert len(eng.slots[0].tokens) == n0
    assert eng.step()                   # harvests the deferred commit
    assert len(eng.slots[0].tokens) >= n0 + 1
    while eng.slots[0].active:
        assert eng.step()
    assert eng._pending_decode is None


def test_warmup_is_state_neutral(params):
    """warmup() precompiles decode/ingest variants without touching the
    PRNG stream or cache contents: a warmed engine's outputs are bitwise a
    cold engine's."""
    sampler = SamplerConfig(temperature=0.8, top_k=16)
    cold = _engine(params, chunk=16, sampler=sampler)
    warm = _engine(params, chunk=16, sampler=sampler)
    key_before = np.asarray(warm.key).copy()
    assert warm.warmup(ingest_rows=(1, warm.max_batch)) > 0
    np.testing.assert_array_equal(np.asarray(warm.key), key_before)
    _assert_same(cold.generate(PROMPTS, max_new=8),
                 warm.generate(PROMPTS, max_new=8))


def test_warmup_refuses_busy_engine(params):
    eng = _engine(params, chunk=16)
    eng.add_request(0, [1, 2, 3], max_new=4)
    with pytest.raises(AssertionError):
        eng.warmup()


# ---------------------------------------------------------------------------
# S1: admission-stamp pruning must not drop live requests' TTFT
# ---------------------------------------------------------------------------

def test_stamp_pruning_spares_live_references(params):
    eng = _engine(params, chunk=16)
    eng._admit_stamp_cap = 2
    eng._t_admit = {i: float(i) for i in range(8)}
    eng.slots[0].active, eng.slots[0].req_id = True, 3
    eng._inflight = {5}
    eng._resume_queue = []
    eng._prune_admit_stamps()
    assert 3 in eng._t_admit and 5 in eng._t_admit
    assert len(eng._t_admit) == 2
    eng.slots[0].active, eng.slots[0].req_id = False, -1
    eng._inflight = set()


def test_ttft_survives_stamp_churn_under_eviction(params):
    """With a tiny stamp cap and eviction churn, every request must still
    get its TTFT recorded (the old cap popped the OLDEST stamp — exactly
    the preempted request still waiting in the resume queue)."""
    prompts = [[65, 66, 67, 68], [70, 71], [80, 81, 82]]
    eng = _engine(params, chunk=16, max_len=64, page_size=8, n_pages=6)
    eng._admit_stamp_cap = 1
    eng.ttft.clear()
    eng.generate(prompts, max_new=24)
    assert eng.evictions > 0
    assert set(eng.ttft) == {0, 1, 2}, \
        f"lost TTFT stamps under churn: {sorted(eng.ttft)}"


# ---------------------------------------------------------------------------
# S2: prompt truncation is surfaced and replayed identically on resume
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [0, 16])
def test_truncation_surfaced(params, chunk):
    long_prompt = [(i % 100) + 1 for i in range(200)]
    eng = _engine(params, chunk=chunk, max_len=64)
    (toks, _), = eng.generate([long_prompt], max_new=4)
    assert eng.truncations[0] == 200 - 64
    short = _engine(params, chunk=chunk, max_len=64)
    short.generate([[1, 2, 3]], max_new=4)
    assert 0 not in short.truncations


def test_truncation_replayed_identically_on_resume(params):
    """A truncated request evicted MID-INGEST must resume with the SAME
    kept tail (the resume queue carries the full prompt; re-admission
    re-truncates deterministically) — outputs bitwise match an
    unconstrained engine's. The grower's decode pressure preempts the
    truncated prompt while its chunks are still streaming in."""
    grower = [(i % 50) + 1 for i in range(30)]      # 4 pages, then grows
    long_p = [(i % 90) + 1 for i in range(150)]     # truncates to 64 = 8 pages
    prompts = [grower, long_p]
    ref_eng = _engine(params, chunk=8, max_len=64, max_batch=2, page_size=8)
    ref = ref_eng.generate(prompts, max_new=40)
    small = _engine(params, chunk=8, max_len=64, max_batch=2, page_size=8,
                    n_pages=12)
    out = small.generate(prompts, max_new=40)
    assert small.evictions > 0, "pool must preempt to test the replay"
    assert 0 not in small.truncations
    assert small.truncations[1] == 150 - 64
    _assert_same_replay(ref, out)


# ---------------------------------------------------------------------------
# S4: score() buffer is clamped to max_len
# ---------------------------------------------------------------------------

def test_score_clamps_to_max_len(params):
    eng = _engine(params, chunk=0, max_len=64)
    seq = [(i % 100) + 1 for i in range(300)]
    mean_long, gold_long = eng.score(seq)
    mean_tail, gold_tail = eng.score(seq[-64:])
    assert gold_long.shape == (63,)
    np.testing.assert_array_equal(gold_long, gold_tail)
    assert mean_long == mean_tail


def test_score_short_sequences_unchanged(params):
    eng = _engine(params, chunk=0, max_len=64)
    mean, gold = eng.score([3, 1, 4, 1, 5])
    assert gold.shape == (4,)
    assert np.isfinite(mean)

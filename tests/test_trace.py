"""The in-program tracer (core/trace.py) and the spans the serving layers
record with it: off it records nothing and builds no annotation; on it
nests spans, tags them with the user request, bounds its buffer, files
compiles under the span that built them, and stamps on the profiler's
clock. The engine, front-end and pipeline record their phases and waits;
the pipeline's user latency is kept apart from its sub-requests'."""
import asyncio
import statistics
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import trace
from repro.core.profiler import LatencyModel
from repro.core.progressive import PICEPipeline
from repro.core.scheduler import EdgeModelInfo
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serving.engine import InferenceEngine
from repro.serving.frontend import EngineFrontend
from repro.serving.requests import Request

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                   max_seq_len=512, dtype="float32", remat=False,
                   prefill_chunk=16)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(TINY, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def tracer_off():
    trace.disable()
    yield
    trace.disable()


def _engine(params, name="tiny", **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("eos_id", -1)     # every request runs to its max_new
    return InferenceEngine(TINY, params, kv_backend="paged", page_size=8,
                           name=name, **kw)


def _by_name(recs, name):
    return [r for r in recs if r.name == name]


class _Refused:
    def __init__(self, *a, **k):
        raise AssertionError("an annotation was built with the tracer off")


def test_off_records_nothing_and_builds_no_annotation(params, monkeypatch):
    trace.enable()
    with trace.span("left.over"):
        pass
    trace.disable()
    before = trace.records()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)
    with trace.span("x", engine="e") as sp:
        assert sp is None
    assert trace.interval("y", 0, 1) is None and trace.new_id() is None
    eng = _engine(params, max_batch=3, n_pages=10)
    eng.generate([[5, 6, 7] * 9, [8, 9]], max_new=6)
    eng.generate_fanout([3, 4, 5] * 7, [[6], [7, 8], [9]], max_new=5)
    asyncio.run(EngineFrontend(_engine(params)).generate_fanout_async(
        [1, 2, 3, 4], [[5], [6]], max_new=4))
    assert trace.records() == before


def test_on_nests_parents_and_tags_the_request(monkeypatch):
    built = []

    class Ann:
        def __init__(self, name):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    trace.enable()
    token = trace.REQUEST.set(41)
    with trace.span("outer", engine="e1", n=2) as a:
        with trace.span("inner") as b:
            b.attrs["rows"] = [(0, 4)]
        kid = trace.new_id()
        trace.interval("wait", 5, 9, parent=a.id, span_id=kid, k=1)
    trace.REQUEST.reset(token)
    with trace.span("alone"):
        pass
    recs = {r.name: r for r in trace.records()}
    assert built == ["outer.e1", "inner", "alone"]
    assert recs["inner"].parent == recs["outer"].id
    assert recs["outer"].parent is None
    assert recs["wait"].parent == recs["outer"].id and recs["wait"].id == kid
    assert (recs["wait"].start_ns, recs["wait"].end_ns) == (5, 9)
    assert recs["outer"].attrs == {"engine": "e1", "n": 2, "request": 41}
    assert recs["inner"].attrs == {"rows": [(0, 4)], "request": 41}
    assert "request" not in recs["alone"].attrs
    assert recs["outer"].start_ns <= recs["inner"].start_ns \
        <= recs["inner"].end_ns <= recs["outer"].end_ns


def test_buffer_stays_bounded(monkeypatch):
    monkeypatch.setattr(trace, "CAPACITY", 10)
    trace.enable()
    for i in range(50):
        with trace.span("s", i=i):
            pass
    recs = trace.records()
    assert len(recs) == 10
    assert [r.attrs["i"] for r in recs] == list(range(40, 50))
    trace.enable()                      # a fresh buffer each time
    assert trace.records() == []


def test_compiles_are_filed_under_the_open_span():
    trace.enable()
    with trace.span("engine.warm", engine="e") as sp:
        jax.jit(lambda x: jnp.sin(x) * 3.25 + x)(jnp.arange(7.0))
    trace.disable()
    comp = _by_name(trace.records(), "jax.compile")
    assert comp and all(c.parent == sp.id for c in comp)
    assert comp[0].attrs["span"] == "engine.warm"
    assert comp[0].attrs["engine"] == "e" and comp[0].attrs["seconds"] > 0


def test_stamps_sit_on_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData
    from glob import glob
    jax.profiler.start_trace(str(tmp_path))
    trace.enable()
    for i in range(20):
        with trace.span(f"clock.{i}"):
            time.sleep(0.001)
    trace.disable()
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob(str(tmp_path / "**" / "*.xplane.pb"),
                                    recursive=True)[0])
    base = next(v for p in pd.planes for k, v in p.stats
                if k == "profile_start_time")
    seen = {e.name: base + int(e.start_ns)
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events
            if e.name.startswith("clock.")}
    diffs = [abs(seen[r.name] - r.start_ns) for r in trace.records()]
    assert len(diffs) == 20
    assert statistics.median(diffs) < 50_000


def test_engine_step_phases_and_kernel_rows(params):
    eng = _engine(params, max_batch=3)
    trace.enable()
    eng.generate([[5, 6, 7] * 12, [8, 9, 10]], max_new=5)
    trace.disable()
    recs = trace.records()
    by_id = {r.id: r for r in recs}
    steps = _by_name(recs, "engine.step")
    assert steps and all(s.attrs["engine"] == "tiny"
                         and s.attrs["max_batch"] == 3 for s in steps)
    assert max(s.attrs["active"] for s in steps) == 2
    for name in ("engine.harvest", "engine.plan", "engine.table_push",
                 "engine.ingest", "engine.first_token", "engine.decode"):
        kids = _by_name(recs, name)
        assert kids, name
        assert all(by_id[k.parent].name == "engine.step" for k in kids)
    reads = _by_name(recs, "engine.readback")
    assert {by_id[r.parent].name for r in reads} == {"engine.harvest",
                                                     "engine.first_token"}
    # the 36-token prompt goes in as chunks of 16 at offsets 0, 16, 32
    rows = [row for r in _by_name(recs, "engine.ingest")
            for row in r.attrs["rows"]]
    assert sorted(rows) == [(0, 3), (0, 16), (16, 16), (32, 4)]
    # each decode row's context length, from the step's plan
    ctx = [r.attrs["ctx"] for r in _by_name(recs, "engine.decode")]
    assert ctx[0] == [4] and ctx[-1] == [40]
    assert "busy_s" not in vars(eng)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_decode_spans_say_how_many_pages_a_grid_step_streams(use_pallas):
    """Through the paged kernels, each decode span carries the pages one
    grid step of its read streams at the step's live width (a power of two
    covering each row's context); the oracle read has no grid and records
    none."""
    from repro.kernels.paged_decode_attention import kernel as pda_kernel
    cfg = TINY.with_(use_pallas=use_pallas)
    eng = InferenceEngine(cfg, transformer.init_params(
        cfg, jax.random.PRNGKey(0)), max_batch=2, max_len=64,
        kv_backend="paged", page_size=8, eos_id=-1)
    assert eng.read_path == ("pallas" if use_pallas else "oracle")
    trace.enable()
    eng.generate([list(range(5, 20))], max_new=3)
    trace.disable()
    dec = _by_name(trace.records(), "engine.decode")
    assert dec
    widths = [1 << (-(-max(r.attrs["ctx"]) // 8) - 1).bit_length()
              for r in dec]
    assert widths[0] == 2 and widths[-1] == 4
    want = [pda_kernel.decode_pages(P, latent=False) for P in widths] \
        if use_pallas else []
    assert [r.attrs["pages_per_step"] for r in dec
            if "pages_per_step" in r.attrs] == want


MLA = ModelConfig(
    name="tiny-mla", family="moe", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=96, moe_d_ff=32, vocab_size=256, max_seq_len=4096,
    n_experts=16, experts_per_token=3, moe_dropless=True,
    n_shared_experts=1, first_k_dense=1, norm_topk_prob=False, ep_size=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    rope_theta=10000.0, dtype="float32", remat=False, prefill_chunk=16)


def test_latent_pages_and_expert_rows_on_the_engine_spans():
    """A latent-attention expert engine's ingest and decode spans say how
    many latent pages the call streamed (over rows and layers) and how many
    assignments each held expert computed (over the expert layers): 3 of
    16 experts a token, 4 held here, 2 expert layers."""
    params = transformer.init_params(MLA, jax.random.PRNGKey(1))
    eng = InferenceEngine(MLA, params, max_batch=2, max_len=64,
                          kv_backend="paged", page_size=8, eos_id=-1,
                          name="mla")
    trace.enable()
    eng.generate([[5, 6, 7] * 6, [8, 9, 10]], max_new=4)
    trace.disable()
    recs = trace.records()
    ing = _by_name(recs, "engine.ingest")
    dec = _by_name(recs, "engine.decode")
    assert ing and dec
    for r in ing:
        ends = [o + n for o, n in r.attrs["rows"]]
        assert r.attrs["latent_pages"] == 3 * sum(-(-e // 8) for e in ends)
    for r in dec:
        assert r.attrs["latent_pages"] == 3 * sum(-(-c // 8)
                                                  for c in r.attrs["ctx"])
    for r in ing + dec:
        rows = r.attrs["expert_rows"]
        assert len(rows) == 4 and sum(rows) <= 2 * 3 * 2 * 16
    # two decode rows a step, but the step's 2 slots are all routed: at
    # most 2 tokens x 3 experts x 2 layers, all held ones counted
    assert sum(sum(r.attrs["expert_rows"]) for r in dec) > 0
    # with the tracer off, nothing is read back for it
    eng.generate([[1, 2, 3]], max_new=2)
    assert eng._pending_rows is None


def test_frontend_queue_wait_and_fork_arrival(params):
    fe = EngineFrontend(_engine(params, max_batch=3))
    trace.enable()
    outs = asyncio.run(fe.generate_fanout_async(
        [1, 2, 3, 4, 5], [[6], [7], [8], [9]], max_new=4))
    trace.disable()
    assert len(outs) == 4
    recs = trace.records()
    queued = _by_name(recs, "frontend.queued")
    running = _by_name(recs, "frontend.running")
    assert len(queued) == len(running) == 4
    prefix = _by_name(recs, "engine.prefill_prefix")[0]
    # a fork arrives at the fan-out call, before its prefix is parked
    assert all(q.start_ns <= prefix.start_ns for q in queued)
    # two slots after the parked prefix: two forks wait for a finisher
    waits = sorted(q.end_ns - q.start_ns for q in queued)
    assert waits[-1] > waits[0]
    assert all(q.attrs["role"] == "expansion_primary"
               and q.attrs["engine"] == "tiny" for q in queued)
    assert all(r.attrs["reason"] in ("stop", "length") for r in running)
    assert _by_name(recs, "frontend.admit") and \
        _by_name(recs, "frontend.publish")


def test_admission_stamp_on_the_handle(params):
    fe = EngineFrontend(_engine(params, max_batch=2))
    from repro.serving.frontend import CompletionRequest

    async def main():
        hs = [fe.submit(CompletionRequest(prompt=[3, 4, 5], max_tokens=4))
              for _ in range(3)]
        for h in hs:
            await h.wait()
        return hs
    hs = asyncio.run(main())
    for h in hs:
        assert h.req.arrival_time_s <= h.admitted_s <= h.finish_s
        assert h.queue_wait_s == h.admitted_s - h.req.arrival_time_s
    # two slots: the third request waited for the first to finish
    assert hs[2].queue_wait_s > hs[0].queue_wait_s


def _pipeline(params):
    engines = {n: _engine(params, name=n, max_batch=4, max_len=256,
                          eos_id=0)
               for n in ("cloud", "edge-a", "edge-b")}
    infos = [EdgeModelInfo("edge-a", LatencyModel(0.05, 100.0), 0.5),
             EdgeModelInfo("edge-b", LatencyModel(0.05, 100.0), 0.7)]
    # a slow cloud, so Eq. 2 sends the request down the sketch path
    return PICEPipeline(engines["cloud"],
                        {k: engines[k] for k in ("edge-a", "edge-b")},
                        LatencyModel(t0=0.5, rate=5.0, name="slow"), infos)


def _query():
    return Request(query="Write a short essay about rivers and the sea.",
                   category="writing", max_new_tokens=56)


def test_pipeline_user_latency_apart_from_sub_requests(params):
    pipe = _pipeline(params)
    resp = pipe.handle(_query())
    mon = pipe.monitor
    assert resp.mode == "progressive"
    # one user request: once in its window, and no sub-request there
    assert list(mon.request_latency_window.values()) == [resp.latency_s]
    assert mon.request_latency_percentile(50) == resp.latency_s
    # the sketch and each member's expansions are the front-ends'
    assert len(mon.latency_window) >= 3
    assert len(mon.ttft_window) == len(mon.latency_window)


def test_pipeline_stages_on_the_tracer(params):
    pipe = _pipeline(params)
    trace.enable()
    req = _query()
    resp = pipe.handle(req)
    trace.disable()
    recs = trace.records()
    (whole,) = _by_name(recs, "pipeline.request")
    (sk,) = _by_name(recs, "pipeline.sketch")
    (ex,) = _by_name(recs, "pipeline.expand")
    members = _by_name(recs, "pipeline.member")
    (ens,) = _by_name(recs, "pipeline.ensemble")
    assert whole.attrs["mode"] == resp.mode == "progressive"
    assert whole.attrs["net_delay_s"] == resp.network_s
    assert sk.start_ns == whole.start_ns and sk.end_ns == ex.start_ns
    assert ex.end_ns <= ens.start_ns <= ens.end_ns <= whole.end_ns
    assert {m.attrs["engine"] for m in members} == {"edge-a", "edge-b"}
    assert all(m.parent == ex.id for m in members)
    assert ex.attrs["groups"] == sum(m.attrs["groups"] for m in members)
    # every record made for the request carries its id; the engines'
    # steps serve every request and carry none
    for r in (whole, sk, ex, ens, *members):
        assert r.attrs["request"] == req.req_id
    subs = _by_name(recs, "frontend.queued")
    assert len(subs) == 1 + ex.attrs["groups"]
    assert all(s.attrs["request"] == req.req_id for s in subs)
    assert {s.attrs["role"] for s in subs} == {
        "sketch", "expansion_primary", "expansion_extra"}
    assert all("request" not in s.attrs
               for s in _by_name(recs, "engine.step"))

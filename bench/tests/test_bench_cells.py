"""The harness end to end on the CPU at tiny sizes: the PICE pipeline
cell, the fan-out and chat cells, traced and untraced, each checked
against the plain reference; and with the timed path broken underneath
(a token altered where the engine commits it), `correct` comes out false.
The chip look is skipped: `run_cell` is driven directly."""
import pytest

from bench import run
from bench.tests.helpers import run_tiny, tiny_spec


@pytest.fixture
def progressive(monkeypatch):
    """Make Eq. 2 admit the sketch path on this host: the profiled tiny
    cloud is too fast for it, so the scheduler is handed a slow one."""
    from repro.core.profiler import LatencyModel
    from repro.launch import serve
    build = serve.build_pipeline

    def slow_cloud(*a, **k):
        pipe = build(*a, **k)
        pipe.scheduler.cloud = LatencyModel(t0=0.5, rate=5.0, name="slow")
        return pipe
    monkeypatch.setattr(serve, "build_pipeline", slow_cloud)


def altered_tokens(monkeypatch):
    """Every token the engines commit is replaced by the next id."""
    from repro.serving.engine import InferenceEngine
    commit = InferenceEngine._commit

    def bad(self, slot, tok, lp):
        return commit(self, slot, (tok + 1) % self.cfg.vocab_size or 1, lp)
    monkeypatch.setattr(InferenceEngine, "_commit", bad)


def test_pice_cell_runs_progressive_and_checks_every_engine(progressive):
    r, res = run_tiny("pice.long.steady", trace=True)
    assert res["correct"] is True
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert r["compiles"] == 0           # every shape was warmed in set-up
    modes = {d.mode for d in r["window"].records}
    assert "progressive" in modes
    assert set(res["compared"]) == {"gap.tiny-cloud", "gap.tiny-edge-a",
                                    "gap.tiny-edge-b"}
    m = res["metrics"]
    assert m["pipeline.edge_groups.steady"]["value"] >= 2
    assert m["pipeline.expand_s.steady"]["value"] > 0
    assert 0 < m["engine.occupancy.steady"]["value"] <= 100
    assert list(res)[-1] == "compared"


def test_pice_saturated_cell_reports_tokens_per_s(progressive):
    _, res = run_tiny("pice.long.saturated")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_pice_cell_with_altered_tokens_is_not_correct(progressive,
                                                      monkeypatch):
    altered_tokens(monkeypatch)
    _, res = run_tiny("pice.long.steady")
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["compared"].values())


def test_fanout_cell_end_to_end():
    r, res = run_tiny("edge.fanout.steady", trace=True)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["compared"]) == {"gap.tiny-edge"}
    assert r["probe"].prefill_calls and r["probe"].decode_calls
    assert r["compiles"] == 0
    # with no chip the kernels leave no trace to read; on the chip a listed
    # metric that reads nothing fails the traced run instead of vanishing
    assert "paged_decode_roofline.steady" in r["unread"]
    spec = tiny_spec()
    with pytest.raises(run.Unread, match="paged_decode_roofline.steady"):
        run.report(spec.cell("edge.fanout.steady"), spec, r, True,
                   device=object())
    _, res = run_tiny("edge.fanout.steady", seed=8)
    assert set(res["metrics"]) == {"latency_p50_s", "latency_p90_s",
                                   "setup_s"}
    assert res["metrics"]["latency_p90_s"]["value"] >= \
        res["metrics"]["latency_p50_s"]["value"] > 0


@pytest.mark.parametrize("cell", ["edge.fanout.steady",
                                  "edge.chat.saturated"])
def test_edge_cell_with_altered_tokens_is_not_correct(cell, monkeypatch):
    altered_tokens(monkeypatch)
    _, res = run_tiny(cell)
    assert res["correct"] is False


def test_chat_cell_end_to_end_and_control_separates():
    _, res = run_tiny("edge.chat.saturated")
    assert res["correct"] is True
    c = res["compared"]["gap.tiny-edge"]
    assert c["value"] <= c["limit"]
    # the control (the reference in float8) in the program's place, over
    # the same kind of window: the harness's own verdict must be false
    from bench import control
    out = control.reading(tiny_spec(), tiny_spec().cell("edge.chat.saturated"),
                          7, 2.0)
    assert out["correct"] is False
    c = out["compared"]["gap.tiny-edge"]
    assert c["program"] <= c["limit"] < c["control"] and c["tokens"] > 0

"""The program's own spans in a traced run (bench/spans.py): on the tiny
twins, the tracer's records agree with the harness's probe over the span
the probe counts (steps, active slots, every attention call's rows,
expansion groups and times); the metrics that read them read; idle gaps
go to the innermost span of either kind; and on a context without records
(a program without the tracer) the readers return None."""
import pytest

from bench import layers, spans, tracefile as tf
from bench import spec as spec_lib
from bench.tests.helpers import DATA
from bench.tests.test_bench_cells import progressive  # noqa: F401
from repro.core import trace

NEW = [m["name"] for m in spans.METRICS]


def _spec():
    return spans.Spec(DATA / "BENCHMARK.json", DATA / "traffic")


def _traced(cell, seed=7):
    spec = _spec()
    r, seen = spans.run_traced(spec, spec.cell(cell), seed, 2.0)
    base = seen["base"]
    on, off = seen["on"] - base, seen["off"] - base
    recs = seen["ctx"].records

    def counted(name):
        return sorted((x for x in recs if x.name == name
                       and on <= x.start_ns <= off),
                      key=lambda x: x.start_ns)
    return r, seen, counted


def _matches_probe(probe, counted):
    steps = counted("engine.step")
    assert steps
    for eng, n in probe.steps.items():
        mine = [s for s in steps if s.attrs["engine"] == eng]
        assert len(mine) == n
        assert sum(s.attrs["active"] / s.attrs["max_batch"]
                   for s in mine) == pytest.approx(
            probe.active_share_sum[eng], rel=1e-12, abs=0)
    assert [(d.attrs["engine"], d.attrs["ctx"])
            for d in counted("engine.decode")] == probe.decode_calls
    rows = sorted(
        [(x.start_ns, (x.attrs["engine"], x.attrs["rows"]))
         for x in counted("engine.ingest")]
        + [(x.start_ns, (x.attrs["engine"],
                         [(x.attrs["offset"], x.attrs["length"])]))
           for x in counted("engine.feed_chunk")])
    assert [call for _, call in rows] == probe.prefill_calls


def test_fanout_records_match_the_probe_and_metrics_read():
    r, seen, counted = _traced("edge.fanout.steady")
    assert r["probe"].decode_calls and r["probe"].prefill_calls
    _matches_probe(r["probe"], counted)
    for name in ("frontend.queue_wait_s.steady", "engine.host_ms.steady",
                 "engine.readback_ms.steady"):
        assert name not in r["unread"]
        value, _ = r["per_layer"][name]
        assert value >= 0
    assert r["per_layer"]["engine.readback_ms.steady"][0] > 0
    assert "pipeline.sketch_s.steady" not in r["per_layer"]
    # every shape was warmed in set-up: nothing built in the window
    prog = spans.program_readings(seen)
    assert prog["compiles_in_window"] == []
    assert prog["per_step"]["tiny-edge"]["spans_per_step"] >= 4
    # no device plane on this host: no idle to split (the hand-built trace
    # below checks the split)
    assert "idle_by_span_s" not in prog


def test_pice_records_match_the_probe(progressive):  # noqa: F811
    r, seen, counted = _traced("pice.long.steady")
    probe = r["probe"]
    _matches_probe(probe, counted)
    recs = seen["ctx"].records
    done = sorted((d for d in r["window"].records if d.ok),
                  key=lambda d: d.index)
    whole = sorted((x for x in recs if x.name == "pipeline.request"),
                   key=lambda x: x.attrs["request"])
    assert len(whole) == len(done) >= 2
    expand = {x.attrs["request"]: x for x in recs
              if x.name == "pipeline.expand"}
    for d, req in zip(done, whole):
        rid = req.attrs["request"]
        assert req.attrs["mode"] == d.mode == "progressive"
        assert expand[rid].attrs["groups"] == probe.groups[d.index]
        mine = (expand[rid].end_ns - expand[rid].start_ns) / 1e9
        assert mine == pytest.approx(d.end - probe.sketch_done[d.index],
                                     abs=5e-3)
    for name in NEW:
        assert name not in r["unread"] and name in r["per_layer"]
    st = spans.stages(seen["ctx"])
    assert st["requests"] >= 1 and st["sketch_s"] > 0
    assert r["per_layer"]["pipeline.sketch_s.steady"][0] > 0


# hand-built: ops busy 0-10, 16-18, 30-40 of a 0-50 window; a harness
# step holds the program's step, which holds its phases
OPS = [("fusion.1", 0, 10), ("copy.2", 16, 18), ("fusion.3", 30, 40)]
SPANS = [("bench.span", 0, 50), ("bench.step.edge", 5, 48),
         ("engine.step.edge", 6, 44), ("engine.harvest.edge", 8, 20),
         ("engine.readback.edge", 9, 19), ("engine.plan.edge", 20, 35)]


def test_idle_gaps_go_to_the_innermost_program_span():
    idle = tf.attribute(tf.gaps(OPS, 0, 50), SPANS)
    assert idle == {"engine.readback.edge": 6, "engine.plan.edge": 12,
                    "bench.step.edge": 10}
    # without the program's spans the breakdown is the harness's alone
    assert tf.attribute(tf.gaps(OPS, 0, 50), [
        s for s in SPANS if s[0].startswith("bench.")]) == {
        "bench.step.edge": 28}


def _rec(name, s, e, i, parent=None, **attrs):
    return trace.Record(name, s, e, i, parent, attrs)


def _ctx(records, lo=0, hi=100_000_000):
    return spans.Context(None, None, {}, None, [], lo, hi, [],
                         records=records)


def test_step_split_reads_host_and_readback_per_step():
    ms = 1_000_000
    recs = [
        _rec("engine.readback", 1 * ms, 3 * ms, 3, 2),
        _rec("engine.harvest", 1 * ms, 4 * ms, 2, 1),
        _rec("engine.readback", 5 * ms, 6 * ms, 5, 4),
        _rec("engine.first_token", 5 * ms, 7 * ms, 4, 1),
        _rec("engine.step", 0, 10 * ms, 1, None, engine="e"),
        _rec("engine.step", 90 * ms, 120 * ms, 6, None, engine="e"),
        _rec("frontend.queued", 0, 2 * ms, 7, None),
        _rec("frontend.queued", 0, 4 * ms, 8, None),
    ]
    ctx = _ctx(recs)
    read = {m: spec_lib.reader(m).read(ctx) for m in NEW}
    # the second step ends past the traced span and is left out
    assert read["engine.readback_ms.steady"] == pytest.approx(3.0)
    assert read["engine.host_ms.steady"] == pytest.approx(7.0)
    assert read["frontend.queue_wait_s.steady"] == pytest.approx(0.003)
    assert read["pipeline.sketch_s.steady"] is None


def test_readers_find_nothing_without_the_programs_records():
    plain = layers.Context(None, None, {}, None, [], 0, 10, [])
    for ctx in (plain, _ctx([])):
        for m in NEW:
            assert spec_lib.reader(m).read(ctx) is None


def test_load_program_reads_annotations_on_the_tracers_clock(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    trace.enable()
    with jax.profiler.TraceAnnotation("bench.span"):
        with trace.span("engine.step", engine="edge"):
            with trace.span("engine.plan", engine="edge"):
                pass
    trace.disable()
    jax.profiler.stop_trace()
    base, prog = spans.load_program(tf.find_xplane(str(tmp_path)))
    names = {n: (s, e) for n, s, e in prog}
    assert set(names) == {"engine.step.edge", "engine.plan.edge"}
    step = next(r for r in trace.records() if r.name == "engine.step")
    s, e = names["engine.step.edge"]
    # the same span on both clocks, a few microseconds apart
    assert abs(base + s - step.start_ns) < 1_000_000
    assert abs(base + e - step.end_ns) < 1_000_000

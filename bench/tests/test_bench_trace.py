"""The trace reduction on hand-built traces, and `load` on a trace this
host records."""
import pytest

from bench import tracefile as tf

# device ops (ns): busy 0-10, 5-15 (overlap), 30-40; window 0-50
OPS = [("fusion.1", 0, 10), ("paged_decode_attention.3", 5, 15),
       ("fusion.2", 30, 40), ("copy.7", 60, 70), ("while.2", 0, 12)]
SPANS = [("bench.span", 0, 50), ("bench.step.edge", 14, 25),
         ("bench.await.sketch.cloud", 0, 50),
         ("bench.prefill_prefix.edge", 41, 49),
         ("bench.step.cloud", 20, 24)]


def test_union_busy_and_gaps():
    # the loop event nests the ops of its body
    assert tf.merged(OPS, 0, 50) == [(0, 15), (30, 40)]
    assert tf.busy_ns(OPS, 0, 50) == 25
    assert tf.gaps(OPS, 0, 50) == [(15, 30), (40, 50)]
    # clipping at the window's edges
    assert tf.busy_ns(OPS, 8, 35) == 7 + 5


def test_kernel_sums_and_op_totals():
    assert tf.kernel_ns(OPS, "paged_decode_attention", 0, 50) == (10, 1)
    assert tf.kernel_ns(OPS, "paged_prefill_attention", 0, 50) == (0, 0)
    # containers are left out of the totals: their bodies are listed
    assert tf.op_totals(OPS, 0, 50) == {"fusion": 20,
                                        "paged_decode_attention": 10}


def test_op_names_come_from_the_hlo_instruction():
    text = ("%paged_decode_attention.10 = bf16[8,2,6,128]{3,2,1,0} "
            "custom-call(s32[8,8]{1,0} %p), custom_call_target=\"tpu\"")
    assert tf.op_name(text) == "paged_decode_attention.10"
    assert tf.base_name(tf.op_name(text)) == "paged_decode_attention"


def test_gaps_are_named_by_the_innermost_sync_span():
    idle = tf.attribute(tf.gaps(OPS, 0, 50), SPANS)
    # gap 15-30 (mid 22): inside both steps; the cloud step started later
    # (innermost); gap 40-50 (mid 45): the edge's prefix prefill. Spans
    # held across awaits and the window itself never name a gap.
    assert idle == {"bench.step.cloud": 15, "bench.prefill_prefix.edge": 10}
    assert tf.attribute([(26, 28)], SPANS) == {tf.NO_SPAN: 2}


def test_top_lists_seconds_largest_first():
    assert tf.top({"a": 2_000_000_000, "b": 3_000_000_000}, k=1) == \
        [["b", 3.0]]


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.span"):
        with jax.profiler.TraceAnnotation("bench.step.test"):
            jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    tr = tf.load(tf.find_xplane(str(tmp_path)))
    names = {n for n, _, _ in tr["spans"]}
    assert {"bench.span", "bench.step.test"} <= names
    lo, hi = tf.window_of(tr["spans"], "bench.span")
    (s, e), = [(s, e) for n, s, e in tr["spans"] if n == "bench.step.test"]
    assert lo <= s <= e <= hi
    assert tr["devices"] == {}            # no TPU plane on this host


def test_find_xplane_fails_clearly(tmp_path):
    with pytest.raises(FileNotFoundError):
        tf.find_xplane(str(tmp_path))

"""The program's own spans (src/repro/core/trace.py) in a traced run.

bench/run.py reads its per-layer metrics from the harness's probe and the
profiler trace; the program records nothing unless its tracer is turned
on, and run.py does not turn it on. This module runs a cell through the
same `run.run_cell` with the tracer on, and adds what the program records
about itself:

- `load_program(path)`: the program's annotations in an xplane (the host
  spans not named `bench.*`: `engine.step.<engine>`, its phases, the
  front-end's passes), and the profile's start on the wall clock, which
  puts the tracer's records (wall-clock ns) in the trace's frame.
- `Context`: `bench.layers.Context` plus `records`, the tracer's records
  in the trace's frame. The readers of the program-span metrics
  (`METRICS`, in bench/metrics/) read `records` and find nothing, and
  return None, on a context without it.
- `run_traced(...)`: `run.run_cell` with the tracer on from the window's
  opening to the end of its drain. The trace's program annotations join
  the harness's spans, so each idle gap is named by the innermost span
  of either kind (a phase inside `bench.step.<engine>`); the metrics in
  `METRICS` are read beside the cell's own.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell that way on the chip, as `bench/run.py` would with the same
`--trace` and the same checks: with `--trace 1` the per-layer metrics,
`METRICS` among them, over the traced span; with `--trace 0` the
end-to-end metrics with the tracer on and no profiler, and the program's
readings over the whole window and its drain. After run.py's lines it
prints the program's readings: the per-phase idle seconds (traced), the
programs built in the window with the span that built each, the
pipeline's stages, the engines' steps, and what the tracer costs a span.
The last line is run.py's result line with a `program` key added.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import shutil
import statistics
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import layers, spec as spec_lib  # noqa: E402

# the per-layer metrics that read the program's spans: what a benchmark
# entry for each would say, and the cells where each finds something
_BOTH = ["pice.long.steady", "edge.fanout.steady"]
METRICS = [
    {"name": "frontend.queue_wait_s.steady", "unit": "s", "better": "lower",
     "source": "program_span", "layer": "front-end and engine",
     "moves": "latency_p50_s", "workloads": _BOTH},
    {"name": "pipeline.sketch_s.steady", "unit": "s", "better": "lower",
     "source": "program_span", "layer": "pipeline",
     "moves": "latency_p50_s", "workloads": ["pice.long.steady"]},
    {"name": "engine.host_ms.steady", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "front-end and engine",
     "moves": "latency_p50_s", "workloads": _BOTH},
    {"name": "engine.readback_ms.steady", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "front-end and engine",
     "moves": "latency_p50_s", "workloads": _BOTH},
]
PROGRAM_PREFIXES = ("engine.", "frontend.", "pipeline.")
COMPILES = ("jax.compile", "jax.cache_read")


def load_program(path: str) -> Tuple[int, list]:
    """(profile start in wall-clock ns, the program's annotations as
    (name, start_ns, end_ns) in the trace's frame)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    base = next((int(v) for plane in pd.planes for k, v in plane.stats
                 if k == "profile_start_time"), 0)
    spans = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
             for plane in pd.planes if plane.name.startswith("/host:")
             for ln in plane.lines for e in ln.events
             if e.name.startswith(PROGRAM_PREFIXES)]
    return base, spans


@dataclasses.dataclass
class Context(layers.Context):
    records: list = dataclasses.field(default_factory=list)


def ended(ctx, name: str) -> list:
    """Records of `name` that end inside the traced span [lo, hi]: clear
    of the profiler's stop, which stalls the host and every request in
    flight."""
    return [r for r in getattr(ctx, "records", None) or ()
            if r.name == name and ctx.lo <= r.end_ns <= ctx.hi]


def mean_s(ctx, name: str) -> Optional[float]:
    rs = ended(ctx, name)
    return statistics.fmean((r.end_ns - r.start_ns) / 1e9 for r in rs) \
        if rs else None


def _steps(ctx):
    """The `engine.step` records ending inside the traced span, by id, and
    each record with the id of the step it lies under (itself for a
    step; None outside every such step)."""
    recs = getattr(ctx, "records", None) or ()
    up = {r.id: r.parent for r in recs}
    steps = {r.id: r for r in ended(ctx, "engine.step")}

    def step_of(r):
        p = r.id
        while p is not None and p not in steps:
            p = up.get(p)
        return p
    return steps, [(r, step_of(r)) for r in recs]


def step_split(ctx) -> List[Tuple[object, int]]:
    """(engine.step record, ns of its engine.readback descendants) for
    each step ending inside the traced span."""
    steps, under = _steps(ctx)
    read: Dict[int, int] = defaultdict(int)
    for r, p in under:
        if r.name == "engine.readback" and p is not None:
            read[p] += r.end_ns - r.start_ns
    return [(s, read[i]) for i, s in steps.items()]


def compiles(records, lo: int, hi: int) -> List[dict]:
    """The programs built (or read from the compile cache) in [lo, hi],
    each with the span that built it."""
    return [{"kind": r.name, "span": r.attrs.get("span", ""),
             "engine": r.attrs.get("engine", ""),
             "seconds": r.attrs["seconds"]}
            for r in records
            if r.name in COMPILES and lo <= r.end_ns <= hi]


def stages(ctx) -> Optional[dict]:
    """For the requests whose `pipeline.request` ends inside the traced
    span: mean whole, sketch and expansion seconds of those requests."""
    reqs = {r.attrs.get("request") for r in ended(ctx, "pipeline.request")}
    out = {}
    for key, name in (("request_s", "pipeline.request"),
                      ("sketch_s", "pipeline.sketch"),
                      ("expand_s", "pipeline.expand")):
        xs = [(r.end_ns - r.start_ns) / 1e9 for r in ctx.records
              if r.name == name and r.attrs.get("request") in reqs]
        if not xs:
            return None
        out[key] = statistics.fmean(xs)
    out["requests"] = len(reqs)
    return out


def tracer_cost(n: int = 20000) -> dict:
    """ns per span of the engine's kind (an `engine` attribute, one
    attribute set inside), tracer on, with and without a profiler
    collecting, and off."""
    import jax
    from repro.core import trace
    out = {}
    for label, profiling in (("ns_per_span", False),
                             ("ns_per_span_profiling", True)):
        if profiling:
            jax.profiler.start_trace(str(ROOT / ".bench_out" / "cost"))
        trace.enable()
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with trace.span("engine.cost", engine="e") as sp:
                if sp:
                    sp.attrs["active"] = 1
        out[label] = (time.perf_counter_ns() - t0) / n
        trace.disable()
        if profiling:
            jax.profiler.stop_trace()
            shutil.rmtree(ROOT / ".bench_out" / "cost", ignore_errors=True)
    t0 = time.perf_counter_ns()
    for _ in range(n):
        with trace.span("engine.cost", engine="e") as sp:
            if sp:
                sp.attrs["active"] = 1
    out["ns_per_span_off"] = (time.perf_counter_ns() - t0) / n
    return out


def per_step(ctx) -> Optional[dict]:
    """Spans per engine step (the step and its descendants) and the mean
    step, per engine, over steps ending inside the traced span."""
    steps, under = _steps(ctx)
    if not steps:
        return None
    inside: Dict[int, int] = defaultdict(int)
    for _, p in under:
        if p is not None:
            inside[p] += 1
    out = {}
    for eng in sorted({s.attrs["engine"] for s in steps.values()}):
        mine = [i for i, s in steps.items() if s.attrs["engine"] == eng]
        out[eng] = {
            "steps": len(mine),
            "spans_per_step": statistics.fmean(inside[i] for i in mine),
            "step_ms": statistics.fmean(
                (steps[i].end_ns - steps[i].start_ns) / 1e6 for i in mine)}
    return out


class Spec(spec_lib.Spec):
    """The benchmark's spec with `METRICS` read in the cells they list."""

    def per_layer(self, cell: dict) -> List[dict]:
        return super().per_layer(cell) + [
            m for m in METRICS if cell["name"] in m["workloads"]]


@contextlib.contextmanager
def _tracing(seen: dict):
    """Patch the harness so `run.run_cell` runs with the program's tracer
    on from the window's opening to the end of its drain, loads the
    program's annotations with the trace, and hands its readers a
    `Context` with the tracer's records. `seen` collects the tracer-clock
    stamps of the window and the traced span, and the context."""
    from bench import drivers, tracefile
    from repro.core import trace
    measure, load, context = drivers.measure, tracefile.load, layers.Context

    def traced_measure(system, items, traffic, seconds, probe, on_open,
                       on_close, span=None):
        def opened():
            on_open()
            trace.enable()
            seen["open"] = trace.now()

        def closed():
            # the close stops a trace still running: the counted span ends
            seen["close"] = trace.now()
            seen.setdefault("off", seen["close"])
            on_close()
        if span is not None:
            offset, length, start, stop = span

            def started():
                start()
                seen["on"] = trace.now()

            def stopping():
                seen.setdefault("off", trace.now())
                stop()
            span = (offset, length, started, stopping)
        try:
            return measure(system, items, traffic, seconds, probe, opened,
                           closed, span)
        finally:
            seen["end"] = trace.now()
            trace.disable()

    def traced_load(path):
        tr = load(path)
        seen["base"], program = load_program(path)
        return dict(tr, spans=tr["spans"] + program)

    def make_context(*a, **k):
        ctx = seen["ctx"] = Context(*a, **k)
        # a trace without its start time: the harness's span opened just
        # before the tracer's stamp `on`
        base = seen["base"] = seen.get("base") or seen["on"] - ctx.lo
        ctx.records = [r._replace(start_ns=r.start_ns - base,
                                  end_ns=r.end_ns - base)
                       for r in trace.records()]
        return ctx

    drivers.measure, tracefile.load = traced_measure, traced_load
    layers.Context = make_context
    try:
        yield
    finally:
        drivers.measure, tracefile.load = measure, load
        layers.Context = context


def run_traced(spec, cell, seed: int, seconds: float, trace: bool = True,
               device=None, peaks=None) -> Tuple[dict, dict]:
    """`run.run_cell` with the program's tracer on (see `_tracing`).
    Returns run_cell's result and `seen`: the window's and traced span's
    tracer-clock stamps, and with `trace` the profile's start and the
    context the readers read."""
    from bench import run
    seen: dict = {}
    with _tracing(seen):
        r = run.run_cell(spec, cell, seed, seconds, trace, device=device,
                         peaks=peaks)
    return r, seen


def program_readings(seen: dict) -> dict:
    """What the program's records say beyond the per-layer metrics: over
    the traced span in a traced run, else over the window and its drain."""
    from bench import tracefile
    from repro.core import trace
    ctx = seen.get("ctx") or Context(None, None, {}, None, [], seen["open"],
                                     seen["end"], [], records=trace.records())
    base = seen.get("base", 0)
    out = {"compiles_in_window": compiles(
               ctx.records, seen["open"] - base, seen["close"] - base),
           "per_step": per_step(ctx),
           "stages": stages(ctx),
           "records": len(ctx.records)}
    if ctx.ops:
        idle = tracefile.attribute(
            tracefile.gaps(ctx.ops, ctx.lo, ctx.hi), ctx.spans)
        out["idle_by_span_s"] = {k: v / 1e9 for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])}
    return out


def main(argv=None) -> int:
    from bench import run
    args = run.parse(argv)
    spec = Spec()
    cell = spec.cell(args.workload)
    device, peaks = run.require_chips(cell["chips"])
    run.use_compile_cache()
    r, seen = run_traced(spec, cell, args.seed, args.seconds,
                         bool(args.trace), device=device, peaks=peaks)
    try:
        result = run.report(cell, spec, r, bool(args.trace), device=device)
    except run.Unread as e:
        sys.exit(str(e))
    prog = program_readings(seen)
    prog["tracer_cost"] = tracer_cost()
    for k, v in prog.items():
        run.say(f"program {k}: {json.dumps(v)}")
    result["program"] = prog
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # run as the package's module: the readers import `bench.spans`, and a
    # second copy of this file would subclass the patched `layers.Context`
    from bench import spans
    sys.exit(spans.main())

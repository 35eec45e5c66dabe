"""Run one cell of the benchmark on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in BENCHMARK.json, builds its configuration with weights
from the seed, warms the shapes its traffic uses (all of that is
`setup_s`), drives the traffic for `--seconds`, and then checks the tokens
the window served against the plain reference (bench/check.py). With
`--trace 0` it reports the cell's end-to-end metrics; with `--trace 1` it
traces a few seconds from the middle of the window with the JAX profiler
and reports the per-layer metrics (bench/metrics/) over that span, and a
breakdown of device time and idle gaps.

It refuses to run without a TPU, with fewer chips than the cell asks for,
or on a chip the peaks table (bench/peaks.py) does not list. Compiled
programs go to $JAX_COMPILATION_CACHE_DIR when set, else to `.jax_cache/`
at the checkout's root. The last line of standard output is the JSON
result; the numbers compared for `correct`, each with its limit, end
standard error and close the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec as spec_lib  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
OUT = ROOT / ".bench_out"
# a --trace 1 run traces this many seconds from the middle of its window:
# a TPU trace holds ~100k device ops a second, and collecting it stalls
# the host, so the per-layer metrics read a few seconds of steady load
TRACE_SPAN_S = 6.0


class CompileLog:
    """Programs built (compiled, or read back from the persistent compile
    cache) and of those the cache hits, seen through jax.monitoring."""

    def __init__(self):
        import jax
        self.count = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
        elif event == CACHE_HIT_EVENT:
            self.hits += 1


def require_chips(n: int):
    """The first device, or exit non-zero without a TPU, with fewer than
    `n` chips, or on a chip with no published peaks."""
    import jax
    from bench.peaks import peaks_for
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU found (JAX platform is "
                 f"{devs[0].platform!r}); the benchmark runs only on a TPU")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX found {len(devs)}")
    try:
        return devs[0], peaks_for(devs[0].device_kind)
    except KeyError as e:
        sys.exit(f"bench: {e}")


def use_compile_cache():
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    # keep every program, so a run after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(*a):
    print(*a, flush=True)


def run_cell(spec, cell, seed: int, seconds: float, trace: bool,
             device=None, peaks=None, control: bool = False):
    """Build, warm, measure, free and check one cell. Returns everything
    `report` prints."""
    import jax
    from bench import check, drivers, fleet, loadgen, probe as probe_lib
    from bench import layers, tracefile
    config, traffic = spec.config(cell), spec.traffic(cell)
    log = CompileLog()
    probe = probe_lib.Probe(tracing=trace)
    system = fleet.build(config, traffic, seed, log=lambda s: None,
                         frontend_cls=probe_lib.frontend_class(probe),
                         probe=probe)
    vocab = next(iter(system.members.values())).cfg.vocab_size
    items = loadgen.plan(traffic, seed, seconds, vocab)
    dims = fleet.engine_dims(system)
    setup_s = time.perf_counter() - T_START
    trace_dir = OUT / "trace"
    marks = {}

    def on_open():
        marks["compiles"], marks["hits"] = log.count, log.hits

    def start_trace():
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        marks["span"] = jax.profiler.TraceAnnotation("bench.span")
        marks["span"].__enter__()
        probe.counting = True

    def stop_trace():
        if "stopped" in marks or "span" not in marks:
            return
        probe.counting = False
        marks["stopped"] = True
        jax.block_until_ready([e.cache for e in system.engines.values()])
        marks["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()

    def on_close():
        marks["compiles"] = log.count - marks["compiles"]
        marks["hits"] = log.hits - marks["hits"]
        if trace:
            stop_trace()

    span = None
    if trace:
        length = min(TRACE_SPAN_S, seconds)
        span = ((seconds - length) / 2, length, start_trace, stop_trace)
    window = drivers.measure(system, items, traffic, seconds, probe,
                             on_open, on_close, span)
    out = {"setup_s": setup_s, "window": window, "probe": probe,
           "times": dict(system.times), "compiles": marks["compiles"],
           "cache_hits": marks["hits"],
           "traffic": traffic}
    if device is not None:
        out["memory_peak_bytes"] = device.memory_stats()["peak_bytes_in_use"]
    if trace:
        tr = tracefile.load(tracefile.find_xplane(str(trace_dir)))
        lo, hi = tracefile.window_of(tr["spans"], "bench.span") or (0, 0)
        ops = next(iter(tr["devices"].values()), [])
        ctx = layers.Context(probe, window, dims, peaks, ops, lo, hi,
                             tr["spans"])
        out["per_layer"], out["unread"] = {}, []
        for m in spec.per_layer(cell):
            v = spec_lib.reader(m["name"]).read(ctx)
            if v is None:
                out["unread"].append(m["name"])
            else:
                out["per_layer"][m["name"]] = (v, m["unit"])
        idle = tracefile.attribute(tracefile.gaps(ops, lo, hi), tr["spans"])
        out["breakdown"] = {
            "device_ops": tracefile.top(tracefile.op_totals(ops, lo, hi)),
            "idle_gaps": tracefile.top(idle)}
        out["busy_s"], out["window_s"] = ctx.busy_s, ctx.window_s
        shutil.rmtree(trace_dir, ignore_errors=True)
    # the program's state goes before the reference runs
    system.free()
    gc.collect()
    finished = [d.index for d in window.records if d.ok]
    picked = check.sample(probe.served, finished, seed,
                          traffic["check"]["requests"])
    out["compared"] = check.compare(system, probe.served, picked, seed,
                                    control=control)
    out["checked_requests"] = len(picked)
    return out


def end_to_end(window, names):
    """The end-to-end values this window gives, by metric name."""
    from bench import drivers
    vals = {}
    if "latency_p50_s" in names:
        vals["latency_p50_s"] = drivers.latency_quantile(window.records, 0.5)
    if "latency_p90_s" in names:
        vals["latency_p90_s"] = drivers.latency_quantile(window.records, 0.9)
    if "tokens_per_s" in names:
        vals["tokens_per_s"] = sum(d.answer_tokens for d in window.records
                                   if d.ok) / window.seconds
    return vals


class Unread(RuntimeError):
    """A traced run on the chip read nothing for a per-layer metric that
    BENCHMARK.json lists for its cell."""


def report(cell, spec, r, trace: bool, device=None) -> dict:
    """Print the run's readings and return its result line. On the chip
    (`device` given), a traced run whose listed per-layer metrics did not
    all read raises `Unread`: the metric's source is gone, and leaving it
    out would hide that."""
    from bench import drivers
    w = r["window"]
    recs = w.records
    lat = sorted(d.end - d.due for d in recs if d.ok)
    say(f"window: {w.seconds:.3f}s, {len(recs)} requests "
        f"({'arrived' if w.open_loop else 'completed'} in the window), "
        f"{sum(1 for d in recs if not d.ok)} failed, "
        f"{w.in_flight_at_close} in flight at close, drain {w.drain_s:.3f}s")
    if lat:
        n90 = sum(1 for x in lat if x > drivers.latency_quantile(recs, 0.9))
        say(f"latency from due time: p50 "
            f"{drivers.latency_quantile(recs, 0.5):.4f}s p90 "
            f"{drivers.latency_quantile(recs, 0.9):.4f}s max {lat[-1]:.4f}s "
            f"mean {statistics.fmean(lat):.4f}s ({n90} samples beyond p90)")
    if w.lateness:
        say(f"generator lateness: mean {statistics.fmean(w.lateness):.5f}s "
            f"max {max(w.lateness):.5f}s over {len(w.lateness)} submissions")
    say(f"modes: {json.dumps(drivers.modes(recs), sort_keys=True)}")
    say(f"programs built inside the window: {r['compiles']} "
        f"({r['cache_hits']} of them read from the persistent cache)")
    say(f"setup: {r['setup_s']:.3f}s ({json.dumps(r['times'])})")
    names = {m["name"] for m in spec.end_to_end(cell)}
    metrics = {}
    if trace:
        for name, (v, unit) in r["per_layer"].items():
            metrics[name] = {"value": v, "unit": unit}
        if r["unread"]:
            say(f"per-layer metrics that read nothing: {r['unread']}")
            if device is not None:
                raise Unread(f"bench: {cell['name']} lists per-layer "
                             f"metrics that read nothing in this traced "
                             f"run: {', '.join(r['unread'])}")
    else:
        vals = end_to_end(w, names)
        vals["setup_s"] = r["setup_s"]
        units = {m["name"]: m["unit"] for m in spec.end_to_end(cell)}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in vals.items()
                   if k in names}
    for k, v in metrics.items():
        say(f"metric {k}: {v['value']} {v['unit']}")
    comp = r["compared"]
    ok = bool(comp) and all(c["value"] <= c["limit"] for c in comp.values())
    # a latency that is infinite means a request never answered: say so,
    # keep the line valid JSON, and the run is not correct
    unanswered = [k for k, m in metrics.items()
                  if not math.isfinite(m["value"])]
    for k in unanswered:
        del metrics[k]
    ok = ok and not unanswered
    failed = sum(1 for d in recs if not d.ok)
    result = {"correct": ok, "attempted": len(recs), "failed": failed,
              "metrics": metrics}
    if device is not None:
        import jax
        result["device"] = {"platform": device.platform,
                            "kind": device.device_kind,
                            "count": len(jax.devices()),
                            "memory_peak_bytes": r["memory_peak_bytes"]}
        if trace:
            result["device"].update(busy_s=r["busy_s"],
                                    window_s=r["window_s"])
    if trace:
        result["breakdown"] = r["breakdown"]
        say(f"breakdown: {json.dumps(r['breakdown'])}")
    result["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                          for k, c in comp.items()}
    say(f"checked {r['checked_requests']} requests")
    for k, c in comp.items():
        print(f"compared {k}: {c['value']:.6f} limit {c['limit']} "
              f"({c['tokens']} served tokens)", file=sys.stderr, flush=True)
    if not comp:
        print("compared nothing: no finished request to check",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    spec = spec_lib.Spec()
    cell = spec.cell(args.workload)
    device, peaks = require_chips(cell["chips"])
    use_compile_cache()
    r = run_cell(spec, cell, args.seed, args.seconds, bool(args.trace),
                 device=device, peaks=peaks)
    try:
        result = report(cell, spec, r, bool(args.trace), device=device)
    except Unread as e:
        sys.exit(str(e))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Find a cell's knee: the highest offered rate the system keeps up with.

    python3 bench/sweep.py --workload <open-loop cell> --seed <n>
        --seconds <s> --rates 0.4,0.6,0.8

Builds the cell once, then offers each rate in turn for `--seconds`
(open loop, the cell's own traffic at that rate), drains, and prints the
offered and completed rates, the latencies, and what was still in flight
when each window closed. A rate is sustained when the completed rate
keeps up with the offered one and the backlog at the close stays within
what the system holds at once. No correctness check runs here.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import require_chips, use_compile_cache

from bench import drivers, fleet, loadgen, probe as probe_lib
from bench import spec as spec_lib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = spec_lib.Spec()
    cell = spec.cell(args.workload)
    require_chips(cell["chips"])
    use_compile_cache()
    config, traffic = spec.config(cell), spec.traffic(cell)
    probe = probe_lib.Probe()
    system = fleet.build(config, traffic, args.seed, log=lambda s: None,
                         frontend_cls=probe_lib.frontend_class(probe))
    vocab = next(iter(system.members.values())).cfg.vocab_size
    for k, rate in enumerate(float(x) for x in args.rates.split(",")):
        items = loadgen.plan(traffic, args.seed + k, args.seconds, vocab,
                             rate=rate)
        w = drivers.measure(system, items, traffic, args.seconds, probe,
                            lambda: None, lambda: None)
        done_in = [d for d in w.records if d.ok and d.end <= w.close]
        print(json.dumps({
            "rate_offered": len(w.records) / w.seconds,
            "rate_completed_in_window": len(done_in) / w.seconds,
            "requests": len(w.records),
            "failed": sum(1 for d in w.records if not d.ok),
            "in_flight_at_close": w.in_flight_at_close,
            "drain_s": w.drain_s,
            "p50_s": drivers.latency_quantile(w.records, 0.5),
            "p90_s": drivers.latency_quantile(w.records, 0.9),
            "lateness_max_s": max(w.lateness, default=0.0),
            "modes": drivers.modes(w.records)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings the limits of the correctness check are set from.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 5,6,7

For each seed, in one process: build the cell, serve its traffic for a
short window, free the program's state, then run the check with the
control in the program's place (bench/check.py): the reference itself in
float8, read at the same positions of the same served tokens, against the
same limits. Each seed prints the harness's own verdict, which must be
`"correct": false`, with the control's and the program's readings beside
each limit. A limit must lie above every sound reading of the program and
below every control reading. The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402
from bench import spec as spec_lib  # noqa: E402


def reading(spec, cell, seed: int, seconds: float, device=None,
            peaks=None) -> dict:
    """One seed's control run, judged by the harness's own report."""
    r = run.run_cell(spec, cell, seed, seconds, trace=False, device=device,
                     peaks=peaks, control=True)
    result = run.report(cell, spec, r, trace=False, device=device)
    return {"workload": cell["name"], "seed": seed,
            "correct": result["correct"],
            "checked_requests": r["checked_requests"],
            "compared": {k: {"control": c["value"], "program": c["program"],
                             "limit": c["limit"], "tokens": c["tokens"]}
                         for k, c in r["compared"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = spec_lib.Spec()
    cell = spec.cell(args.workload)
    device, peaks = run.require_chips(cell["chips"])
    run.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(reading(spec, cell, seed, args.seconds, device,
                                 peaks)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

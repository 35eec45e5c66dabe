"""Shared set-up for the benchmark's CPU tests: the tiny twins of the
benchmark's configurations and cells (bench/tests/data), run through the
harness with no chip."""
from pathlib import Path

from bench import spec as spec_lib

DATA = Path(__file__).resolve().parent / "data"


def tiny_spec():
    return spec_lib.Spec(DATA / "BENCHMARK.json", DATA / "traffic")


def run_tiny(cell_name, seed=7, seconds=2.0, trace=False, control=False):
    """One run of a tiny cell through `run_cell` and `report`."""
    from bench import run
    spec = tiny_spec()
    cell = spec.cell(cell_name)
    r = run.run_cell(spec, cell, seed, seconds, trace, control=control)
    return r, run.report(cell, spec, r, trace)

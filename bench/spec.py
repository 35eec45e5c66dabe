"""Names in BENCHMARK.json -> the files that hold them.

A cell names a configuration (`configs[].file`, a JSON file of sizes whose
`reference` key names a module under bench/reference/), a traffic mix
(bench/traffic/<traffic>.json, read by bench/loadgen.py) and, through the
metric entries, per-layer readers (bench/metrics/<metric>.py). A reader
file may serve several metrics: `engine.occupancy.steady` is read by
`engine.occupancy.py` when no file of the full name exists. Adding a
configuration, a mix or a metric adds files and entries; nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Spec:
    def __init__(self, bench_file: Path = ROOT / "BENCHMARK.json",
                 traffic_dir: Path = BENCH / "traffic",
                 root: Path = ROOT):
        self.doc = json.loads(Path(bench_file).read_text())
        self.traffic_dir = Path(traffic_dir)
        self.root = Path(root)

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, cell: dict) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == cell["config"]:
                data = json.loads((self.root / c["file"]).read_text())
                data.setdefault("name", c["name"])
                return data
        raise KeyError(f"no configuration {cell['config']!r}")

    def traffic(self, cell: dict) -> dict:
        data = json.loads(
            (self.traffic_dir / f"{cell['traffic']}.json").read_text())
        data.setdefault("name", cell["traffic"])
        return data

    def end_to_end(self, cell: dict) -> List[dict]:
        return [m for m in self.doc["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def per_layer(self, cell: dict) -> List[dict]:
        moved = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if (cell["name"] in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]


_readers: Dict[str, object] = {}


def reader(metric: str, metrics_dir: Path = BENCH / "metrics"):
    """The module whose `read(ctx)` gives `metric`: metrics_dir/<name>.py,
    else the longest dotted prefix of the name that has a file."""
    parts = metric.split(".")
    for n in range(len(parts), 0, -1):
        path = Path(metrics_dir) / (".".join(parts[:n]) + ".py")
        if path.is_file():
            key = str(path)
            if key not in _readers:
                mod_spec = importlib.util.spec_from_file_location(
                    "bench_metric_" + path.stem.replace(".", "_"), path)
                mod = importlib.util.module_from_spec(mod_spec)
                mod_spec.loader.exec_module(mod)
                _readers[key] = mod
            return _readers[key]
    raise KeyError(f"no reader for metric {metric!r} under {metrics_dir}")


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module (a configuration's reference)."""
    path = BENCH / kind / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod

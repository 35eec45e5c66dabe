"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
finds its files: configurations, traffic mixes, reference modules and
per-layer readers."""
import json
import re
from pathlib import Path

import pytest

from bench import spec as spec_lib

ROOT = Path(__file__).resolve().parents[2]
DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_paths():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["command"] == ["python3", "bench/run.py"]
    assert DOC["paths"] == ["bench"]
    assert 1 <= DOC["run_seconds"] <= 51
    n = len(DOC["workloads"])
    # a full check: 2 + 14 per cell runs, each run_seconds + 60, 180 s of
    # compiling per cell, 1200 s spare, within 43200 s even at 24 cells
    for cells in (n, 24):
        runs = 2 + 14 * cells
        assert runs * (DOC["run_seconds"] + 60) + cells * 180 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_entry_keys():
    names = []
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert len(c["reduced"]) <= 16
        names.append(c["name"])
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    metrics = DOC["end_to_end"] + DOC["per_layer"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len({w["name"] for w in DOC["workloads"]}) == len(DOC["workloads"])
    assert len({(w["config"], w["traffic"]) for w in DOC["workloads"]}) == \
        len(DOC["workloads"])


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in \
        e2e["setup_s"]
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_each_cell_finds_its_files_and_reports_enough(cell):
    spec = spec_lib.Spec()
    w = spec.cell(cell)
    config = spec.config(w)
    assert (ROOT / "bench" / "reference"
            / f"{config['reference']}.py").is_file()
    traffic = spec.traffic(w)
    assert traffic["requests"]["kind"] in ("pice", "fanout", "chat")
    e2e = {m["name"] for m in spec.end_to_end(w)}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = spec.per_layer(w)
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e
        assert spec_lib.reader(m["name"]).read is not None


def test_per_layer_metrics():
    e2e = {m["name"] for m in DOC["end_to_end"]}
    layers = {}
    for m in DOC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], set()).add(m["name"].split(".")[0])
    for m in DOC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_configs_name_their_reductions():
    for c in DOC["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(data["reduced"])
        for m in data["members"]:
            assert m["check"]["logit_gap"] > 0


def test_fleet_configuration_is_the_launchers_pairing():
    from bench import fleet
    config = spec_lib.Spec().config(spec_lib.Spec().cell("pice.long.steady"))
    fleet.check_pairing(config, fleet.members_of(config))
    config["members"][0]["hf"] = dict(config["members"][0]["hf"],
                                      num_hidden_layers=9)
    with pytest.raises(ValueError, match="config"):
        fleet.check_pairing(config, fleet.members_of(config))

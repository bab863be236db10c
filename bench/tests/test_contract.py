"""``BENCHMARK.json`` and the files it names hold together: every cell
has its configuration, traffic, limits and driver, every per-layer metric
its reader, and every name keeps to the allowed characters."""
import json
import os
import re

import pytest

import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    size = os.path.getsize(os.path.join(run.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        spec = run.cell_spec(bench, w["name"])
        assert spec["cfg"]["chips"] == w["chips"]
        entry = spec["cfg"]["entry"]
        assert os.path.isfile(os.path.join(run.HERE, "drivers",
                                           f"{entry}.py"))
        assert spec["limits"] and all("max" in v
                                      for v in spec["limits"].values())
        assert w["config"] in configs
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(configs)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_configs(bench):
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        cfg = run.load_json(os.path.join(run.ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.isfile(os.path.join(run.HERE, "metrics",
                                           f"{m['name']}.py"))
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], m["layer"])
    for cell in cells:
        assert [m for m in run.metrics_for(bench, cell, False)
                if m["name"] != "setup_s"]
        assert run.metrics_for(bench, cell, True)


def test_traffic_files_are_data(bench):
    for w in bench["workloads"]:
        path = os.path.join(run.HERE, "traffic", f"{w['name']}.json")
        with open(path) as f:
            json.load(f)

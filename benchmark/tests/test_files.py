"""Every cell's, configuration's and metric's file parses, and
``BENCHMARK.json`` says what the files say, inside the contract's
limits."""

import importlib
import json
import re

import pytest

from benchmark.lib import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCHMARK = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def metric_modules():
    return {p.stem: importlib.import_module(f"benchmark.metrics.{p.stem}")
            for p in sorted((harness.BENCH / "metrics").glob("*.py"))
            if not p.stem.startswith("_")}


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and \
        "\n" not in s and "\t" not in s


@pytest.mark.parametrize("path", sorted(
    (harness.BENCH / "workloads").glob("*.json")), ids=lambda p: p.stem)
def test_cell_file(path):
    cell, config = harness.load_cell(path.stem)
    assert cell["name"] == path.stem and NAME.match(cell["name"])
    assert cell["chips"] in (1, 4) and line(cell["why"])
    assert (harness.BENCH / "drivers" / f"{cell['driver']}.py").is_file()
    assert cell["driver"] in cell, "the driver's parameters"
    assert config["name"] == cell["config"]
    tiny, _ = harness.load_cell(path.stem, rehearsal=True)
    assert set(tiny[cell["driver"]]) == set(cell[cell["driver"]])


@pytest.mark.parametrize("path", sorted(
    (harness.BENCH / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_config_file(path):
    config = harness.load_json(path)
    assert config["name"] == path.stem and NAME.match(path.stem)
    assert line(config["source"]) and len(config["reduced"]) <= 16
    assert all(NAME.match(key) for key in config["reduced"])
    assert {"assumed", "deployment", "model"} <= set(config)
    harness.build_model(config)


@pytest.mark.parametrize("name", sorted(metric_modules()))
def test_metric_file(name):
    mod = metric_modules()[name]
    assert NAME.match(name) and UNIT.match(mod.UNIT)
    assert mod.KIND in ("end_to_end", "per_layer")
    assert mod.BETTER in ("lower", "higher") and mod.SOURCE in SOURCES
    if mod.KIND == "end_to_end":
        assert mod.SOURCE in ("host_clock", "device_trace")
    else:
        assert line(mod.LAYER)
        assert metric_modules()[mod.MOVES].KIND == "end_to_end"
    assert mod.read({}) is None, "nothing to read -> nothing returned"


def test_benchmark_json_has_the_contracts_keys_and_limits():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"]
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= b["run_seconds"] <= 51
    # 2 + 14 runs per cell at the full 24 cells must fit the check.
    cells = 24
    assert (2 + 14 * cells) * (b["run_seconds"] + 60) + cells * 180 \
        + 1200 <= 43200
    assert 2 <= len(b["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_benchmark_json_agrees_with_the_files():
    b = BENCHMARK
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        cell, _ = harness.load_cell(w["name"])
        assert (w["config"], w["chips"], w["why"]) == (
            cell["config"], cell["chips"], cell["why"])
        assert NAME.match(w["traffic"]) and line(w["why"])
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(b["workloads"])
    used = {w["config"] for w in b["workloads"]}
    assert {c["name"] for c in b["configs"]} == used
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        config = harness.load_json(harness.ROOT / c["file"])
        assert (c["source"], c["reduced"]) == (config["source"],
                                               config["reduced"])
        assert line(c["why"]) and c["file"].startswith("benchmark/")
    mods = metric_modules()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        mod = mods[m["name"]]
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert (m["unit"], m["better"], m["source"]) == (
            mod.UNIT, mod.BETTER, mod.SOURCE) and mod.KIND == "end_to_end"
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= cells
    for m in b["per_layer"]:
        mod = mods[m["name"]]
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (mod.UNIT, mod.BETTER, mod.SOURCE,
                                mod.LAYER, mod.MOVES)
        # A per-layer metric is reported only where the metric it moves is.
        assert set(m.get("workloads", cells)) <= set(
            e2e[m["moves"]].get("workloads", cells))
    for cell in cells:
        here = [m for m in b["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(here) >= 2, "setup_s and one other"
        assert any(cell in m.get("workloads", cells)
                   for m in b["per_layer"])

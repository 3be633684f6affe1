"""Every cell, configuration, traffic mix and metric of BENCHMARK.json
resolves to its files, and a new cell or metric is found by adding files
and entries alone."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench.lib import cell
from bench.tests import tiny

BENCHMARK = cell.load_json(cell.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
PER_LAYER = [m["name"] for m in BENCHMARK["per_layer"]]
READERS = sorted(f[:-3] for f in os.listdir(os.path.join(cell.BENCH_DIR,
                                                         "metrics"))
                 if f.endswith(".py"))


def test_top_level_keys_and_limits():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert len(json.dumps(BENCHMARK)) < 64 * 1024
    names = ([c["name"] for c in BENCHMARK["configs"]] + CELLS
             + [m["name"] for m in BENCHMARK["end_to_end"]] + PER_LAYER)
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    files = cell.resolve(name)
    assert files.entry["chips"] == 1
    config = next(c for c in BENCHMARK["configs"]
                  if c["name"] == files.entry["config"])
    assert config["file"] == f"bench/configs/{config['name']}.json"
    for key in config["reduced"]:
        assert NAME.match(key) and key in files.config["generator"], key
    driver = files.module("drivers", files.traffic["driver"])
    for hook in ("run", "measure", "judge", "control"):
        assert callable(getattr(driver, hook)), hook
    assert files.workload["checks"]
    reported = cell.metrics_of(BENCHMARK, name, "end_to_end")
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    assert cell.metrics_of(BENCHMARK, name, "per_layer")


@pytest.mark.parametrize("name", READERS)
def test_metric_reader_reads_nothing_from_nothing(name):
    assert cell.load_module("metrics", name).read({}) is None
    m = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert m["moves"] in [e["name"] for e in BENCHMARK["end_to_end"]]
    for w in m.get("workloads", []):
        assert w in CELLS
        assert m["moves"] in [e["name"] for e in
                              cell.metrics_of(BENCHMARK, w, "end_to_end")]


def test_every_reader_is_a_benchmark_metric():
    assert set(PER_LAYER) == set(READERS)


def test_new_cell_and_metric_are_found_without_edits(tmp_path):
    root = tiny.make(tmp_path)
    with open(os.path.join(root, "bench", "metrics", "probe_count.py"),
              "w") as f:
        f.write("def read(r):\n    return r.get('solves')\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    benchmark["per_layer"].append(
        {"name": "probe_count", "unit": "solves", "better": "higher",
         "source": "program_counter", "layer": "solver",
         "moves": "solve_s", "workloads": ["tiny-matching-mid.solve"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(benchmark, f)
    files = tiny.files(root, "tiny-matching-mid.solve")
    assert files.config["generator"]["num_sources"] == tiny.SOURCES
    names = [m["name"] for m in
             cell.metrics_of(files.benchmark, files.name, "per_layer")]
    assert "probe_count" in names
    assert files.module("metrics", "probe_count").read({"solves": 3}) == 3

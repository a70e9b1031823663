"""BENCHMARK.json against the data files it names."""
import json
import os
import re

import pytest

from benchmarks.lib import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"runner_clock": "host_clock", "program_counter": "program_counter",
           "node_metrics": "program_counter",
           "sidecar_stats": "program_counter",
           "trace_device_op": "device_trace", "trace_span": "program_span"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))


def test_every_cell_loads_and_reports(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for entry, mfile in cell.per_layer:
            assert entry["moves"] in names, (w["name"], entry["name"])
            assert SOURCES[mfile["source"]] == entry["source"]
            assert mfile["unit"] == entry["unit"]
            assert mfile["layer"] == entry["layer"]
        # the cell's own file agrees with the line in BENCHMARK.json
        own = spec.load_json(os.path.join(
            spec.BENCH_DIR, "workloads", w["name"] + ".json"))
        assert own["why"] == w["why"]


def test_names_units_and_config_files(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in bench["configs"]:
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert cfg["source"] == c["source"] and len(c["source"]) <= 200
        assert cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"] and "assumed" in cfg
    assert len(json.dumps(bench)) < 64 * 1024

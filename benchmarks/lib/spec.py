"""Finds a cell's files by the names ``BENCHMARK.json`` gives: the
configuration's ``file``, ``traffic/<traffic>.json`` (the mix's
parameters and the driver that plays it) and ``metrics/<metric>.json``.
``workloads/<cell>.json`` holds the cell's own note."""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict          # the mix's parameters
    end_to_end: list       # BENCHMARK.json entries this cell reports
    per_layer: list        # (entry, metric file) pairs this cell reports


def _for_cell(metric: dict, cell: str, reporting=None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reporting is None or metric.get("moves") in reporting


def load_cell(cell_name: str, config_files: dict = None) -> Cell:
    """``config_files`` ({configuration: file}) puts another file in a
    configuration's place: the tests' toy sizes, never a measured run."""
    spec_path = os.path.join(ROOT, "BENCHMARK.json")
    spec = load_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"no workload {cell_name!r} in {spec_path}; "
                         f"it has {sorted(cells)}")
    w = cells[cell_name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench_dir = os.path.join(ROOT, spec["paths"][0])
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"] if _for_cell(m, cell_name)]
    reporting = {m["name"] for m in e2e}
    per_layer = []
    for m in spec["per_layer"]:
        if _for_cell(m, cell_name, reporting):
            per_layer.append((m, load_json(os.path.join(
                bench_dir, "metrics", m["name"] + ".json"))))
    return Cell(cell_name, int(w["chips"]),
                load_json(os.path.join(ROOT, (config_files or {}).get(
                    cfg_entry["name"], cfg_entry["file"]))),
                traffic, e2e, per_layer)

#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints the result as the last line of standard output. Everything that
belongs to one cell, configuration or per-layer metric is a data file
found by name (see README.md); this file and ``lib/`` hold what is common.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import readers, report, spec  # noqa: E402


class Context:
    """What a driver gets: the cell, the run's arguments, the clock that
    set-up is counted from, and whether a chip is demanded (only the
    tests under ``tests/`` pass ``require_chip=False``; the command line
    cannot)."""

    def __init__(self, cell, seed, seconds, trace, require_chip):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.require_chip = require_chip
        self.t_start = T_START
        self.root = ROOT

    def check_device(self, device: dict) -> None:
        """Refuse to measure off a TPU or on fewer chips than the cell
        asks for: a number from XLA:CPU never goes under a device
        metric's name."""
        if not self.require_chip:
            return
        if device.get("platform") != "tpu" or \
                int(device.get("count", 0)) < self.cell.chips:
            raise SystemExit(
                f"benchmarks/run.py: cell {self.cell.name} needs "
                f"{self.cell.chips} TPU chip(s) but JAX found "
                f"{device.get('count', 0)} x {device.get('kind')!r} on "
                f"platform {device.get('platform')!r}; not measuring")


def main(argv=None, *, config_files=None, require_chip=True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # a time limit's SIGTERM has to reach the finally blocks that stop the
    # children: nothing may be left holding the chip
    signal.signal(signal.SIGTERM, lambda *_a: sys.exit(143))

    cell = spec.load_cell(args.workload, config_files)
    ctx = Context(cell, args.seed, args.seconds, args.trace, require_chip)
    driver = importlib.import_module(
        "benchmarks.drivers." + cell.traffic["driver"])
    res = driver.run(ctx)     # SystemExit without a chip: no result line

    metrics = {}
    if not ctx.trace:
        for m in cell.end_to_end:
            if m["name"] not in res.end_to_end:
                raise SystemExit(f"driver {cell.traffic['driver']} gave no "
                                 f"{m['name']} in cell {cell.name}")
            metrics[m["name"]] = (res.end_to_end[m["name"]], m["unit"])
    else:
        for entry, mfile in cell.per_layer:
            v = readers.read_metric(mfile, res.readings)
            if v is not None:
                metrics[entry["name"]] = (v, entry["unit"])
    report.emit(res.checks, res.attempted, res.failed, metrics, res.device,
                res.breakdown if ctx.trace else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())

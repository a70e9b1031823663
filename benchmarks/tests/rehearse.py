#!/usr/bin/env python3
"""The CPU rehearsal: one cell of BENCHMARK.json with the tests' toy
configurations (conftest.TINY) in the real ones' place — the same traffic,
drivers and metric files — end to end without a chip. Its numbers say nothing about the device.

    JAX_PLATFORMS=cpu python3 benchmarks/tests/rehearse.py --workload <cell> --seed 1 --seconds 3 --trace 1
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import run  # noqa: E402
from benchmarks.tests.conftest import TINY  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(config_files=TINY, require_chip=False))

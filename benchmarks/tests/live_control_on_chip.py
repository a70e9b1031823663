#!/usr/bin/env python3
"""``valset10k.live-rounds`` at its own size on the chip with one of
test_live_cell.py's plants underneath: one of the three faults let through
the node, or the control (a reference that verifies no signature) in the
reference's place. ``correct`` has to come out false (exit code 0 when it
did). It is replay_control_on_chip.py's ``main`` with this cell's plants.

    python3 benchmarks/tests/live_control_on_chip.py --plant <name> --seed <n> --seconds <s>
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.tests import replay_control_on_chip as control  # noqa: E402
from benchmarks.tests import test_live_cell  # noqa: E402

if __name__ == "__main__":
    control.plants = test_live_cell
    sys.exit(control.main())

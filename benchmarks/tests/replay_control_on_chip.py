#!/usr/bin/env python3
"""``valset175.replay`` at its own size on the chip with one of
test_replay_cell.py's plants underneath: a fault of the three let through
the fused verify, or the control (a reference that leaves a check out) in
the reference's place. ``correct`` has to come out false (exit code 0 when
it did).

    python3 benchmarks/tests/replay_control_on_chip.py --plant <name> --seed <n> --seconds <s>
"""
import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.tests import test_replay_cell as plants  # noqa: E402


class _Patch:
    """pytest's monkeypatch, as far as the plants use it."""

    def setattr(self, obj, name, value):
        setattr(obj, name, value)


def main() -> int:
    at = sys.argv.index("--plant")
    plant = sys.argv[at + 1]
    del sys.argv[at:at + 2]
    plants.PLANTS[plant](_Patch())
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = bench_run.main(["--workload", plants.CELL] + sys.argv[1:]
                            + ["--trace", "0"])
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    failed = sorted(k for k, v in line["checks"].items() if not v["ok"])
    print(f"plant {plant}: correct={line['correct']} failed_checks={failed} "
          f"values={[line['checks'][k]['value'] for k in failed]}",
          flush=True)
    return rc or (0 if line["correct"] is False else 1)


if __name__ == "__main__":
    sys.exit(main())

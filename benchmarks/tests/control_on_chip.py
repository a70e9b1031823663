#!/usr/bin/env python3
"""The control of a cell at the cell's own size, on the chip: the run's
own command with the control in the program's place. ``correct`` has to
come out false (exit code 0 when it did).

    python3 benchmarks/tests/control_on_chip.py --workload <cell> --seed <n> --seconds <s> [--fault _half_left_out]

``--fault`` plants one of test_faults.py's faults of the timed path
instead, at the cell's own size.

``commit_verify`` cells: the plain reference with VerifyCommitLight's
early exit put in the entry's place (one bad signature past the 2/3 point
is accepted). ``served_tx`` cells: the program's own lower path, admission
verify switched off (``[mempool] verify_signatures = false``).
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import spec  # noqa: E402
from benchmarks.tests import test_faults  # noqa: E402


class _Patch:
    """pytest's monkeypatch, as far as the controls use it."""

    def setattr(self, obj, name, value):
        setattr(obj, name, value)

    def setenv(self, name, value):
        os.environ[name] = value


def main() -> int:
    cell = sys.argv[sys.argv.index("--workload") + 1]
    driver = spec.load_cell(cell).traffic["driver"]
    if "--fault" in sys.argv:
        at = sys.argv.index("--fault")
        getattr(test_faults, sys.argv[at + 1])(_Patch())
        del sys.argv[at:at + 2]
    elif driver == "commit_verify":
        test_faults._control(_Patch())
    else:
        test_faults._control_env(_Patch())
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = bench_run.main(sys.argv[1:] + ["--trace", "0"])
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    failed = sorted(k for k, v in line["checks"].items() if not v["ok"])
    print(f"control {cell} seed "
          f"{sys.argv[sys.argv.index('--seed') + 1]}: correct="
          f"{line['correct']} failed_checks={failed} "
          f"values={[line['checks'][k]['value'] for k in failed]}", flush=True)
    return rc or (0 if line["correct"] is False else 1)


if __name__ == "__main__":
    sys.exit(main())

"""Consensus step-latency breakdown: where a localnet block's wall time
goes (reference analogue: the StepDurationSeconds metric added to
consensus/metrics.go in later releases, read through Prometheus).

Runs the 4-node localnet under load for a window, then reports each
round step's observation count, total and mean as the DELTA over the
window (the registry is process-global and cumulative, and the warm-up
contains seconds-scale NewHeight samples from node start that would
skew the means). All four in-process nodes aggregate into the same
registry, so the numbers are per-step means across the net.

Run: python tools/step_breakdown.py [seconds]
"""

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import tests.conftest  # noqa: F401  (forces jax onto CPU devices)

from tmtpu.consensus.types import STEP_NAMES  # noqa: E402
from tmtpu.libs import metrics  # noqa: E402
from tools import localnet_bench  # noqa: E402


def _snapshot():
    return {name: metrics.consensus_step_duration.totals(step=name)
            for name in STEP_NAMES.values()}


def main(duration_s: float = 20.0):
    # localnet_bench._run builds the net, waits for height 2, THEN
    # opens its timing window — but the metric registry keeps counting
    # from node start, so snapshot as late as possible (just before the
    # run) and diff afterwards; the residual warm-up inside _run is a
    # couple of NewHeight samples, not the seconds-scale node boot.
    before = _snapshot()
    bench = localnet_bench._run(duration_s)
    after = _snapshot()
    out = {"localnet": bench, "steps": {}}
    for name in STEP_NAMES.values():
        count = after[name][0] - before[name][0]
        total = after[name][1] - before[name][1]
        if count:
            out["steps"][name] = {
                "count": count,
                "total_s": round(total, 3),
                "mean_ms": round(1e3 * total / count, 2),
            }
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main(float(sys.argv[1]) if len(sys.argv) > 1 else 20.0)

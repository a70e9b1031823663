"""The last lines of a run: each number compared beside its limit on
standard error, and the result's one JSON line on standard output."""

from __future__ import annotations

import json
import sys


class Checks:
    """The numbers that decide ``correct``, each with a limit of its own.
    ``at_most`` limits are upper limits; ``at_least`` lower ones."""

    def __init__(self):
        self.rows = []      # (name, value, limit, kind, ok)

    def at_most(self, name: str, value, limit) -> None:
        self.rows.append((name, value, limit, "max",
                          value is not None and value <= limit))

    def at_least(self, name: str, value, limit) -> None:
        self.rows.append((name, value, limit, "min",
                          value is not None and value >= limit))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[4] for r in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim, "kind": k, "ok": ok}
                for n, v, lim, k, ok in self.rows}

    def print(self, out=sys.stderr) -> None:
        for n, v, lim, k, ok in self.rows:
            print(f"check {n}: value={v} limit={k} {lim} "
                  f"{'ok' if ok else 'FAILED'}", file=out)
        print(f"correct={self.correct}", file=out, flush=True)


def emit(checks: Checks, attempted: int, failed: int, metrics: dict,
         device: dict, breakdown: dict = None) -> None:
    """``metrics`` is {name: (value, unit)}; values go out as measured."""
    line = {"correct": checks.correct, "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {n: {"value": v, "unit": u}
                        for n, (v, u) in metrics.items()},
            "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["checks"] = checks.as_dict()
    sys.stdout.flush()
    checks.print()
    print(json.dumps(line), flush=True)

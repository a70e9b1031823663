#!/usr/bin/env python
"""Thin shim over the unified lint engine (tmtpu/analysis).

These checks now live in tmtpu/analysis/rules/framed.py as the
``sidecar`` rule, running off the shared repo index with the other
rules; suppressions (with reviewed justifications) live in
tools/lint_baseline.json. This CLI is kept so the old entry point
(``python tools/check_sidecar.py``) keeps working — prefer
``python tools/lint.py --rule sidecar`` (one index, every rule).
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

RULE = "sidecar"


def check() -> list:
    """Human-readable NEW findings (baseline-suppressed excluded)."""
    from tmtpu.analysis import run_rule

    return [str(f) for f in run_rule(RULE)]


def main() -> int:
    findings = check()
    for f in findings:
        print(f)
    if findings:
        print(f"{len(findings)} sidecar finding(s)", file=sys.stderr)
        return 1
    print(f"check_sidecar: clean (rule {RULE!r} via tools/lint.py)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

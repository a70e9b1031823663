"""What a driver hands back to ``run.py``."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from benchmarks.lib.readers import Readings
from benchmarks.lib.report import Checks


@dataclass
class RunResult:
    checks: Checks
    attempted: int
    failed: int
    end_to_end: dict                 # {metric name: value}
    device: dict                     # platform, kind, count, memory_peak_bytes
    readings: Readings = field(default_factory=Readings)
    breakdown: Optional[dict] = None

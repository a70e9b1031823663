"""metrics rule: every registered metric is written, every write resolves.

Port of tools/check_metrics.py, made fully static: the metric catalog
comes from ``index.metric_defs()`` (an AST parse of libs/metrics.py's
module-level ``DEFAULT.counter/gauge/histogram`` assignments) instead of
importing the module — so the rule also runs against synthetic fixture
trees.

1. A registered-but-never-written metric renders as a permanent zero on
   /metrics — it looks monitored while measuring nothing.
2. A write to a subsystem-prefixed attribute that is not registered
   raises AttributeError only on the code path that hits it.
3. A Counter/Gauge/Histogram constructed directly (outside the DEFAULT
   registry) accepts writes forever but never renders.
"""

from __future__ import annotations

import re
from typing import List

from tmtpu.analysis.findings import Finding
from tmtpu.analysis.index import METRIC_BIND_RE, METRIC_WRITE_RE, RepoIndex
from tmtpu.analysis.registry import rule

_WRITE_PAT = re.compile(
    r"\b(?:metrics\.|_m\.)?([a-z][a-z0-9_]*)" + METRIC_WRITE_RE)
# a registered metric handed as a value to the code that writes it
_BIND_PAT = re.compile(
    r"\b(?:metrics\.|_m\.)([a-z][a-z0-9_]*)" + METRIC_BIND_RE)

# subsystem prefixes whose writes must resolve against the catalog
_KNOWN_PREFIXES = ("consensus_", "p2p_", "mempool_", "crypto_")

_DIRECT_CTOR = re.compile(
    r"\b(?:metrics\.)?(Counter|Gauge|Histogram)\(\s*[\"']")

_METRICS_MOD = "tmtpu/libs/metrics.py"


@rule("metrics",
      doc="registered metrics have write sites, writes name registered "
          "metrics, and no metric bypasses the DEFAULT registry",
      triggers=("tmtpu", "tools", "tests", "bench.py", "chip_smoke.py"))
def check(index: RepoIndex) -> List[Finding]:
    attrs = index.metric_defs()
    written = set()
    referenced = {}  # attr-like name -> first rel it was written in
    for fi in index.files():
        for m in _WRITE_PAT.finditer(fi.source):
            name = m.group(1)
            if name in attrs:
                written.add(name)
            elif name.startswith(_KNOWN_PREFIXES):
                referenced.setdefault(name, fi.rel)
        written.update(name for name in _BIND_PAT.findall(fi.source)
                       if name in attrs)
    findings = []
    for attr in sorted(set(attrs) - written):
        findings.append(Finding(
            "metrics", _METRICS_MOD,
            f"dead metric: {attr} ({attrs[attr]}) is registered in "
            f"{_METRICS_MOD} but never written anywhere",
            key=f"metrics::dead::{attr}"))
    for name, rel in sorted(referenced.items()):
        findings.append(Finding(
            "metrics", rel,
            f"unknown metric: {name} is written in {rel} but not "
            f"registered in {_METRICS_MOD}",
            key=f"metrics::unknown::{name}"))
    for fi in index.files():
        if fi.rel == _METRICS_MOD or fi.rel.startswith("tests/"):
            continue  # the registry itself; tests build throwaways
        for m in _DIRECT_CTOR.finditer(fi.source):
            findings.append(Finding(
                "metrics", fi.rel,
                f"unrendered metric: {fi.rel} constructs a {m.group(1)} "
                f"directly — it bypasses the DEFAULT registry and never "
                f"appears on /metrics; use DEFAULT.{m.group(1).lower()}"
                f"(...)",
                line=fi.line_of(m.start()),
                key=f"metrics::ctor::{fi.rel}::{m.group(1)}"))
    return findings

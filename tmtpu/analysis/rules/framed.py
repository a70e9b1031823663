"""sidecar and lightserve rules: each framed daemon's wire protocol and
telemetry stay fully covered.

Port of tools/check_sidecar.py, one body for both daemons (the
``sidecar`` and ``lightserve`` rules), each run over its own namespace:

1. Every class in the daemon's ``protocol.MESSAGE_TYPES`` has a
   round-trip sample in its protocol test file's SAMPLES dict (and no
   stale samples linger).
2. Every ``<daemon>_*`` metric carries the ``tendermint_<daemon>_``
   prefix and renders through the DEFAULT registry.
3. Every such metric has a write site somewhere in the tree (or is
   handed as a value to the framed core, which writes it), and every
   write names a registered metric.

Imports the protocol module and metrics registry (the render check needs
the real renderer), hence ``requires_import``.
"""

from __future__ import annotations

import importlib
import re
from typing import List

from tmtpu.analysis.findings import Finding
from tmtpu.analysis.index import METRIC_BIND_RE, METRIC_WRITE_RE, RepoIndex
from tmtpu.analysis.registry import rule

_METRICS_MOD = "tmtpu/libs/metrics.py"

_SAMPLE_RE = re.compile(r"proto\.([A-Za-z_][A-Za-z0-9_]*)\s*:")


def _protocol_findings(name: str, index: RepoIndex) -> List[Finding]:
    proto = importlib.import_module(f"tmtpu.{name}.protocol")
    test_path = f"tests/test_{name}_protocol.py"
    fi = index.get(test_path)
    if fi is None:
        return [Finding(name, test_path,
                        f"missing protocol test file: {test_path}",
                        key=f"{name}::no-test-file")]
    findings = []
    if "SAMPLES" not in fi.source:
        return [Finding(name, test_path,
                        f"{test_path} has no SAMPLES dict — the "
                        f"round-trip coverage this rule asserts is gone",
                        key=f"{name}::no-samples")]
    if "def test_frame_round_trip" not in fi.source:
        findings.append(Finding(
            name, test_path,
            f"{test_path} lost test_frame_round_trip — samples "
            f"exist but nothing round-trips them",
            key=f"{name}::no-round-trip-test"))
    sampled = set(_SAMPLE_RE.findall(fi.source))
    registered = {cls.__name__ for cls in proto.MESSAGE_TYPES.values()}
    for cls in sorted(registered - sampled):
        findings.append(Finding(
            name, f"tmtpu/{name}/protocol.py",
            f"untested wire message: protocol.{cls} is registered in "
            f"MESSAGE_TYPES but has no encode/decode round-trip sample "
            f"in {test_path}",
            key=f"{name}::unsampled::{cls}"))
    for cls in sorted(sampled - registered):
        findings.append(Finding(
            name, test_path,
            f"stale sample: {test_path} samples proto.{cls}, "
            f"which is not in MESSAGE_TYPES",
            key=f"{name}::stale-sample::{cls}"))
    return findings


def _metric_findings(name: str, index: RepoIndex) -> List[Finding]:
    from tmtpu.libs import metrics

    attrs = {
        attr: obj for attr, obj in vars(metrics).items()
        if isinstance(obj, metrics._Metric) and
        attr.startswith(f"{name}_")}
    if not attrs:
        return [Finding(
            name, _METRICS_MOD,
            f"no {name}_* metrics found in tmtpu/libs/metrics.py — the "
            f"{name} metric set was removed or renamed",
            key=f"{name}::no-metrics")]
    findings = []
    rendered = metrics.render_prometheus()
    prefix = f"tendermint_{name}_"
    for attr, obj in sorted(attrs.items()):
        if not obj.name.startswith(prefix):
            findings.append(Finding(
                name, _METRICS_MOD,
                f"misfiled metric: {attr} renders as {obj.name!r}, "
                f"outside the {prefix} subsystem",
                key=f"{name}::misfiled::{attr}"))
        if f"# TYPE {obj.name} " not in rendered:
            findings.append(Finding(
                name, _METRICS_MOD,
                f"unrendered metric: {attr} ({obj.name}) does not "
                f"appear in render_prometheus() — it bypassed the "
                f"DEFAULT registry and neither the daemon /metrics nor "
                f"the node exposition will serve it",
                key=f"{name}::unrendered::{attr}"))
    write = re.compile(
        rf"\b(?:metrics\.|_m\.)?({name}_[a-z0-9_]*)" + METRIC_WRITE_RE)
    bind = re.compile(
        rf"\b(?:metrics\.|_m\.)({name}_[a-z0-9_]*)" + METRIC_BIND_RE)
    written, bound = set(), set()
    for fi in index.files():
        written.update(write.findall(fi.source))
        bound.update(bind.findall(fi.source))
    for attr in sorted(set(attrs) - written - bound):
        findings.append(Finding(
            name, _METRICS_MOD,
            f"dead metric: {attr} ({attrs[attr].name}) is "
            f"registered but never written anywhere in the tree",
            key=f"{name}::dead::{attr}"))
    for metric in sorted(written - set(attrs)):
        findings.append(Finding(
            name, _METRICS_MOD,
            f"unknown metric: {name} metric {metric} is written "
            f"somewhere in the tree but not registered in "
            f"tmtpu/libs/metrics.py",
            key=f"{name}::unknown::{metric}"))
    return findings


def _register(name: str) -> None:
    @rule(name,
          doc=f"every {name} wire message round-trips in a test; every "
              f"{name} metric is prefixed, rendered, and written",
          triggers=(f"tmtpu/{name}", "tmtpu/libs", "tests"),
          requires_import=True)
    def check(index: RepoIndex) -> List[Finding]:
        return _protocol_findings(name, index) + \
            _metric_findings(name, index)


for _daemon in ("sidecar", "lightserve"):
    _register(_daemon)

"""One general reader for every per-layer metric. A metric is a data file
(``metrics/<name>.json``) that names where its terms are read and how
they are reduced; a run hands over its ``Readings``. A reader that finds
nothing to read returns None, and the metric is left out of the line.

Sources (a term's ``source``; the metric's own is the default):

  runner_clock     seconds the harness took with its own clock, by name
                   (a number or a list of them)
  program_counter  the metric registry of the process that holds the chip,
  node_metrics     and the node's: Prometheus-style series, differenced
                   over the window; ``name`` is a regex on the metric's
                   name, ``labels`` one on the series key, ``field`` is
                   ``value`` (counter/gauge), ``sum`` or ``count``
  sidecar_stats    the daemon's ``stats()`` flattened to dotted paths and
                   differenced; ``name`` is a regex on the path
  trace_device_op  device seconds (``field: seconds``) or events
                   (``count``) of the operations whose name matches
  trace_span       the same for host spans

Reductions: ``sum``, ``mean``, ``p50`` (of ``of``); ``ratio`` and
``per_10k_lanes`` (of ``num`` over ``den``; ``complement`` gives 1 - x);
``share_of_window`` (``of`` over the window's seconds, in %);
``roofline`` (``units`` x the operation count of ``opcount`` over the
chip's ``peak``, as a share in % of the device ``seconds``).
"""

from __future__ import annotations

import os
import re
import statistics
from dataclasses import dataclass, field
from typing import Optional

from benchmarks.lib.spec import BENCH_DIR, load_json

COUNTER_SOURCES = ("program_counter", "node_metrics", "sidecar_stats")


@dataclass
class Readings:
    clock: dict = field(default_factory=dict)
    # source -> {metric name: {series key: number | {"count", "sum"}}}
    counters: dict = field(default_factory=dict)
    trace: Optional[dict] = None       # tracered.reduce_trace's result
    window_s: float = 0.0
    device_kind: str = ""


def flatten(obj, prefix: str = "") -> dict:
    """Nested dicts to {"a.b.c": number}; what is not a number goes."""
    out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(flatten(v, f"{prefix}.{k}" if prefix else str(k)))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix] = obj
    return out


def registry_delta(after: dict, before: dict) -> dict:
    """``libs/metrics.summary()`` twice -> what the window added. A gauge
    keeps its later value."""
    out = {}
    for name, m in after.items():
        b = (before.get(name) or {}).get("series", {})
        series = {}
        for key, v in m["series"].items():
            if isinstance(v, dict):
                b0 = b.get(key, {"count": 0, "sum": 0.0})
                series[key] = {"count": v["count"] - b0["count"],
                               "sum": v["sum"] - b0["sum"]}
            elif m.get("kind") == "gauge":
                series[key] = v
            else:
                series[key] = v - b.get(key, 0)
        out[name] = series
    return out


def stats_delta(after: dict, before: dict) -> dict:
    a, b = flatten(after), flatten(before)
    return {k: {"": v - b.get(k, 0)} for k, v in a.items()}


def term_value(term: dict, default_source: str, r: Readings):
    """One term of a metric file -> a number, a list of them, or None
    when there is nothing to read."""
    src = term.get("source", default_source)
    name = term["name"]
    if src == "runner_clock":
        return r.clock.get(name)
    if src in COUNTER_SOURCES:
        table = r.counters.get(src)
        if table is None:
            return None
        hit = [s for n, s in table.items() if re.search(name, n)]
        if not hit:
            return None       # no such counter: nothing to read
        fld = term.get("field", "value")
        lab = term.get("labels")
        total = 0.0
        for series in hit:
            for key, v in series.items():
                if lab and not re.search(lab, key):
                    continue
                total += v[fld] if isinstance(v, dict) else v
        return total
    if src in ("trace_device_op", "trace_span"):
        if r.trace is None:
            return None
        table = r.trace["device_ops" if src == "trace_device_op"
                        else "spans"]
        hit = [v for n, v in table.items() if re.search(name, n)]
        if not hit:
            return None
        i = 0 if term.get("field", "seconds") == "seconds" else 1
        return sum(v[i] for v in hit)
    raise ValueError(f"unknown source {src!r}")


def _as_list(v):
    return list(v) if isinstance(v, (list, tuple)) else [v]


def read_metric(mfile: dict, r: Readings) -> Optional[float]:
    src = mfile["source"]
    red = mfile["reduction"]
    scale = mfile.get("scale", 1)
    read = mfile["read"]
    terms = {k: term_value(t, src, r) for k, t in read.items()}
    if any(v is None for v in terms.values()):
        return None
    if red in ("sum", "mean", "p50"):
        vals = _as_list(terms["of"])
        if not vals:
            return None
        v = {"sum": sum, "mean": statistics.fmean,
             "p50": statistics.median}[red](vals)
        return v * scale
    if red in ("ratio", "per_10k_lanes"):
        num, den = (sum(_as_list(terms[k])) for k in ("num", "den"))
        if not den:
            return None
        x = num / den
        if mfile.get("complement"):
            x = 1 - x
        return x * scale * (10_000 if red == "per_10k_lanes" else 1)
    if red == "share_of_window":
        window = r.trace["window_s"] if r.trace and src.startswith("trace") \
            else r.window_s
        return 100.0 * sum(_as_list(terms["of"])) / window if window else None
    if red == "roofline":
        seconds = sum(_as_list(terms["seconds"]))
        units = sum(_as_list(terms["units"]))
        if not seconds or not units:
            return None     # never 0 for a share of a peak
        peaks = load_json(os.path.join(BENCH_DIR, "lib", "peaks.json"))
        if r.device_kind not in peaks:
            raise SystemExit(f"device kind {r.device_kind!r} is not in "
                             f"benchmarks/lib/peaks.json: add it with its "
                             f"source, there is no default")
        ops = load_json(os.path.join(BENCH_DIR, "lib", "opcounts.json"))[
            mfile["opcount"]]
        least_s = units * ops[mfile.get("ops_key", "int_ops")] / \
            peaks[r.device_kind][mfile["peak"]]
        return 100.0 * least_s / seconds
    raise ValueError(f"unknown reduction {red!r}")

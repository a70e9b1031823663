"""From a profiler trace to numbers: device busy and idle seconds, device
seconds per operation, host spans, and each idle gap given to the host
span that was open while the device waited.

The reduction works on a plain form of the trace, so that it can be
checked on a small recorded one (tests/data/):

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

``load_xplane`` makes that form from the ``.xplane.pb`` file the JAX
profiler writes; nothing but JAX is needed to read it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# lines of a device plane that hold whole programs or steps, not the
# operations inside them; counting both would count every second twice
NOT_OP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                "Framework Name Scope", "Source code")
WINDOW_SPAN = "bench.window"
NO_SPAN = "no_span_open__inside_the_program_"


def clean(name: str) -> str:
    """A stable short name: what is not a letter, a digit, ``.``, ``:`` or
    ``-`` becomes ``_`` (``%fusion.3 = ...`` and ``jit(f)`` alike)."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"[^A-Za-z0-9.:\-]+", "_", name)[:80]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str, min_host_event_ns: int = 1_000) -> dict:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not is_dev and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if is_dev or ev.duration_ns >= min_host_event_ns]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _device_planes(trace: dict, emulated: bool) -> List[dict]:
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if planes or not emulated:
        return planes
    # XLA:CPU rehearsal only: the client's worker threads stand in for a
    # device, so that the code below runs here; never reported as one
    return [{"name": "/emulated:CPU", "lines": [
        {"name": "XLA Ops", "events": [
            e for ln in p["lines"] if ln["name"].startswith("tf_XLA")
            for e in ln["events"]
            if e[2] > 0 and not e[0].startswith(("Threadpool", "end: "))]}
        for p in trace["planes"] if p["name"].startswith("/host:")]}]


def _op_lines(plane: dict) -> List[dict]:
    ops = [ln for ln in plane["lines"] if ln["name"] == "XLA Ops"]
    return ops or [ln for ln in plane["lines"]
                   if ln["name"] not in NOT_OP_LINES]


def reduce_trace(trace: dict, emulated: bool = False) -> Optional[dict]:
    """-> {"window_s", "busy_s", "chips", "device_ops": {name: [seconds,
    count]}, "spans": {name: [seconds, count]}, "idle_gaps": {name:
    seconds}} or None when the trace holds no device plane.

    Busy is the union of the intervals in which an operation ran, per
    chip, averaged over the chips. The window is the ``bench.window`` host
    span when the harness wrote one, else first to last device event."""
    host_events = [e for p in trace["planes"] if p["name"].startswith("/host:")
                   for ln in p["lines"] for e in ln["events"]
                   if not e[0].startswith(("Threadpool", "end: "))
                   and not ln["name"].startswith("tf_XLA")]
    window = next(((s, s + d) for n, s, d in host_events
                   if n == WINDOW_SPAN), None)
    planes = _device_planes(trace, emulated)
    if not planes:
        return None
    ops: Dict[str, List[float]] = {}
    busy_ns = 0
    per_chip = []
    for plane in planes:
        ivals = []
        for line in _op_lines(plane):
            for name, start, dur in line["events"]:
                if window and (start + dur <= window[0] or start >= window[1]):
                    continue
                ivals.append((start, start + dur))
                acc = ops.setdefault(clean(name), [0.0, 0])
                acc[0] += dur / 1e9
                acc[1] += 1
        merged = _union(ivals)
        per_chip.append(merged)
        busy_ns += sum(b - a for a, b in merged)
    if window is None:
        edges = [iv for m in per_chip for iv in m]
        if not edges:
            return None
        window = (min(a for a, _ in edges), max(b for _, b in edges))
    spans: Dict[str, List[float]] = {}
    for name, start, dur in host_events:
        if start + dur <= window[0] or start >= window[1]:
            continue
        acc = spans.setdefault(clean(name), [0.0, 0])
        acc[0] += dur / 1e9
        acc[1] += 1
    return {"window_s": (window[1] - window[0]) / 1e9,
            "busy_s": busy_ns / 1e9 / len(planes),
            "chips": len(planes),
            "device_ops": ops, "spans": spans,
            "idle_gaps": _idle_gaps(per_chip[0], window, host_events)}


def _idle_gaps(busy: List[Tuple[int, int]], window: Tuple[int, int],
               host_events: list, min_gap_ns: int = 2_000) -> Dict[str, float]:
    """Idle seconds of the first chip by the innermost host span open at
    the time: of the spans that cover a stretch of a gap, the one that
    started last."""
    gaps = []
    at = window[0]
    for a, b in busy:
        if a - at >= min_gap_ns:
            gaps.append((at, a))
        at = max(at, b)
    if window[1] - at >= min_gap_ns:
        gaps.append((at, window[1]))
    spans = sorted((s, s + d, clean(n)) for n, s, d in host_events
                   if d > 0 and n != WINDOW_SPAN)
    out: Dict[str, float] = {}
    nxt, over = 0, []
    for ga, gb in gaps:     # gaps ascend, so a span that ended stays out
        while nxt < len(spans) and spans[nxt][0] < gb:
            over.append(spans[nxt])
            nxt += 1
        over = [sp for sp in over if sp[1] > ga]
        cuts = sorted({ga, gb} | {min(max(x, ga), gb)
                                  for sp in over for x in sp[:2]})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [sp for sp in over if sp[0] <= mid < sp[1]]
            name = max(cover)[2] if cover else NO_SPAN
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(((n, v[0]) for n, v in red["device_ops"].items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["idle_gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}

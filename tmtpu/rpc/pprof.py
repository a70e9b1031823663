"""Runtime profiling endpoints (reference analogue: the net/http/pprof
server gated by config.RPC.PprofListenAddress, node/node.go:894-900).

Python-native equivalents of the Go pprof profiles:

    /debug/pprof/            index
    /debug/pprof/goroutine   every thread's stack (goroutine profile)
    /debug/pprof/heap        tracemalloc top allocations (heap profile)
    /debug/pprof/profile?seconds=N
                             statistical CPU profile: samples all thread
                             stacks at ~100 Hz for N seconds, returns
                             collapsed stacks (flamegraph.pl format)
    /debug/pprof/cmdline     process argv
    /debug/traces            drain the span ring (libs/trace) as Chrome
                             trace-event JSON; ?format=jsonl for line-
                             delimited spans, ?format=fleet for spans
                             wrapped with node identity + clock anchor
                             (cross-node join input), ?keep=1 to
                             snapshot without draining
    /debug/timeline          per-height round timeline journal
                             (libs/timeline) as JSON; ?height=H for one
                             height, ?last=N for the trailing window
    /debug/txlat             per-tx lifecycle latency snapshot
                             (libs/txlat) as JSON; ?limit=N for the
                             recent-journey window size
    /debug/validators        per-validator consensus forensics ledger
                             (libs/valstats) as JSON — scorecards,
                             vote-lag EWMAs, missed votes/proposals,
                             equivocation/amnesia flags; ?limit=N caps
                             the validator records returned
    /metrics                 Prometheus text exposition (libs/metrics) —
                             the scrape target standard collectors expect
    /healthz                 liveness: 200 when every watchdog check
                             passes, 503 + JSON reasons when stalled
    /readyz                  readiness: 200 when live AND caught up
                             (not block/state syncing), else 503

Started by the node when ``rpc.pprof_laddr`` is set; also used by
`tmtpu debug dump`. The health/ready verdicts come from callables the
node wires in (``health=`` / ``ready=``) — without them the probes
answer 200 with ``{"watchdog": "disabled"}``.
"""

from __future__ import annotations

import collections
import json
import sys
import threading
import time
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from tmtpu.libs import trace


def render_traces(fmt: str = "chrome", keep: bool = False):
    """Body + content-type for /debug/traces: drains the global span ring
    (or snapshots it with ``keep``) in the requested export format.
    ``format=fleet`` wraps the spans with the node identity and a clock
    anchor (wall/perf pair) so a cross-node joiner — tools/critical_path —
    can align this node's monotonic timestamps against its peers'."""
    spans = trace.snapshot() if keep else trace.drain()
    if fmt == "jsonl":
        return trace.to_jsonl(spans), "application/x-ndjson"
    if fmt == "fleet":
        return (json.dumps({
            "clock": trace.clock_anchor(),
            "buffered": len(spans),
            "spans": [sp.to_dict() for sp in spans],
        }), "application/json")
    return (json.dumps(trace.to_chrome_trace(spans)),
            "application/json")


def thread_stacks() -> str:
    """All live threads with their current stacks (goroutine profile)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in sys._current_frames().items():
        out.append(f"thread {tid} [{names.get(tid, '?')}]:")
        out.extend(line.rstrip()
                   for line in traceback.format_stack(frame))
        out.append("")
    return "\n".join(out)


def heap_profile(top: int = 50) -> str:
    """tracemalloc top allocation sites; starts tracing on first call
    (subsequent calls show growth since then)."""
    import tracemalloc

    if not tracemalloc.is_tracing():
        tracemalloc.start()
        return ("tracemalloc started; call again to see allocations "
                "since this point\n")
    snap = tracemalloc.take_snapshot()
    lines = [f"heap profile: top {top} by size"]
    for stat in snap.statistics("lineno")[:top]:
        lines.append(str(stat))
    return "\n".join(lines) + "\n"


def cpu_profile(seconds: float = 5.0, hz: int = 100) -> str:
    """Statistical CPU profile: collapsed stacks, one line per unique
    stack with its sample count (flamegraph.pl input format)."""
    counts: collections.Counter[str] = collections.Counter()
    interval = 1.0 / hz
    deadline = time.monotonic() + seconds
    me = threading.get_ident()
    while time.monotonic() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            frames = []
            f = frame
            while f is not None:
                frames.append(f"{f.f_code.co_name} "
                              f"({f.f_code.co_filename.rsplit('/', 1)[-1]}"
                              f":{f.f_lineno})")
                f = f.f_back
            counts[";".join(reversed(frames))] += 1
        time.sleep(interval)
    return "\n".join(f"{stack} {n}" for stack, n in counts.most_common())


class _Handler(BaseHTTPRequestHandler):
    def log_message(self, *a):  # quiet
        pass

    def _probe(self, source, default_payload):
        """(status, body) for /healthz//readyz: 200 when the wired-in
        verdict callable passes (or none is wired), 503 with the JSON
        reasons otherwise."""
        if source is None:
            return 200, json.dumps(default_payload)
        ok, payload = source()
        return (200 if ok else 503), json.dumps(payload)

    def do_GET(self):
        url = urlparse(self.path)
        q = parse_qs(url.query)
        path = url.path.rstrip("/")
        ctype = "text/plain; charset=utf-8"
        status = 200
        try:
            if path in ("", "/debug/pprof"):
                body = ("pprof endpoints: goroutine, heap, "
                        "profile?seconds=N, cmdline; trace drain at "
                        "/debug/traces[?format=jsonl|fleet][&keep=1]; "
                        "timeline "
                        "at /debug/timeline; tx lifecycle latency at "
                        "/debug/txlat[?limit=N]; validator forensics at "
                        "/debug/validators[?limit=N]; /metrics, /healthz, "
                        "/readyz\n")
            elif path == "/debug/traces":
                body, ctype = render_traces(
                    fmt=q.get("format", ["chrome"])[0],
                    keep=q.get("keep", ["0"])[0] not in ("0", "", "false"),
                )
            elif path == "/debug/timeline":
                from tmtpu.libs import timeline

                h = q.get("height", [None])[0]
                body = json.dumps({
                    "summary": timeline.summary(),
                    "last_event": timeline.last_event(),
                    "heights": timeline.snapshot(
                        height=int(h) if h is not None else None,
                        last=int(q.get("last", ["20"])[0])),
                })
                ctype = "application/json"
            elif path == "/debug/txlat":
                from tmtpu.libs import txlat

                body = json.dumps(txlat.snapshot(
                    limit=int(q.get("limit", ["64"])[0])))
                ctype = "application/json"
            elif path == "/debug/validators":
                from tmtpu.libs import valstats

                body = json.dumps(valstats.snapshot(
                    limit=int(q.get("limit", ["256"])[0])))
                ctype = "application/json"
            elif path == "/metrics":
                from tmtpu.libs import metrics

                body = metrics.render_prometheus()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif path == "/healthz":
                status, body = self._probe(
                    getattr(self.server, "health_source", None),
                    {"healthy": True, "watchdog": "disabled"})
                ctype = "application/json"
            elif path == "/readyz":
                status, body = self._probe(
                    getattr(self.server, "ready_source", None),
                    {"ready": True, "watchdog": "disabled"})
                ctype = "application/json"
            elif path.endswith("/goroutine"):
                body = thread_stacks()
            elif path.endswith("/heap"):
                body = heap_profile()
            elif path.endswith("/profile"):
                secs = float(q.get("seconds", ["5"])[0])
                body = cpu_profile(min(secs, 60.0))
            elif path.endswith("/cmdline"):
                # the whole command line, interpreter first, as Go's
                # os.Args: sys.argv of a ``python -c`` child is ['-c']
                body = "\x00".join(sys.orig_argv)
            else:
                self.send_error(404)
                return
        except Exception as e:
            self.send_error(500, str(e))
            return
        data = body.encode()
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)


class PprofServer:
    def __init__(self, laddr: str, health=None, ready=None):
        """``health``/``ready``: callables returning (ok, json-able
        payload) — back /healthz and /readyz (node/node.py wires the
        watchdog's liveness and the sync-aware readiness here)."""
        host, _, port = laddr.replace("tcp://", "").rpartition(":")
        self.httpd = ThreadingHTTPServer((host or "127.0.0.1", int(port)),
                                         _Handler)
        self.httpd.daemon_threads = True
        self.httpd.health_source = health
        self.httpd.ready_source = ready
        self._thread: threading.Thread | None = None

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def start(self):
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, name="pprof", daemon=True)
        self._thread.start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2.0)

"""Cross-client batch coalescing: many requests, one device dispatch.

This is the piece that turns one TPU into a shared resource: four
localnet nodes each verifying ~100 lanes/block become one daemon
dispatching ~400-lane joint batches. Requests from any number of
connections enter per-curve queues; a single dispatcher thread gathers
them under the adaptive-flush policy (its own
:class:`~tmtpu.crypto.batch.AdaptiveFlushScheduler` instance, fed by
real request arrivals and real dispatch round-trips) and hands ONE
concatenated lane list per curve to the verify engine. Each request
gets back exactly its slice of the joint mask plus the dispatch
metadata (id, total lanes, distinct clients) so clients — and the
two-client coalescing test — can PROVE their lanes shared a dispatch.

Whole-request granularity: a request's lanes never split across
dispatches, so mask slicing is a single contiguous cut and a request
observes exactly one dispatch. ``max_lanes_per_dispatch`` is therefore
a soft cap — gathering stops once adding the next whole request would
exceed it, but a single oversized request still dispatches alone.

Admission control: ``submit`` rejects with :class:`Overloaded` when
accepting the request would push total queued lanes past
``max_queue_lanes``. The daemon answers ``STATUS_OVERLOADED`` —
explicit backpressure the client converts into in-process fallback —
instead of queueing unboundedly and blowing every caller's deadline.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from tmtpu.crypto.batch import AdaptiveFlushScheduler
from tmtpu.libs import metrics as _m
from tmtpu.libs import trace

# verify engine signature: (curve, [(pk, msg, sig, power)], tally)
#   -> (mask, tallied)
VerifyFn = Callable[[str, List[tuple], bool], Tuple[List[bool], int]]


# The dispatcher's wait with nothing queued ends at a submit's notify or
# after this long. A profiler session records only spans that begin and
# end inside it, so the wait is cut short enough that an idle daemon's
# first and last ``sidecar.coalescer.idle`` cost a 6 s profile under 2%.
_IDLE_POLL_S = 0.05


class Overloaded(Exception):
    """Admission control rejected the request; queues are full."""


class PendingRequest:
    """One client's verify request riding toward a joint dispatch."""

    __slots__ = ("client_id", "curve", "items", "tally", "deadline",
                 "enqueued_at", "done", "mask", "tallied", "error",
                 "failure", "dispatch_id", "dispatch_lanes",
                 "dispatch_clients", "trace_ctx", "dispatch_traces")

    def __init__(self, client_id: str, curve: str, items: List[tuple],
                 tally: bool, deadline: Optional[float],
                 trace_ctx=None):
        self.client_id = client_id
        self.curve = curve
        self.items = items
        self.tally = tally
        self.deadline = deadline          # monotonic, None = no deadline
        self.enqueued_at = time.monotonic()
        self.done = threading.Event()
        self.mask: Optional[List[bool]] = None
        self.tallied = 0
        self.error = ""
        self.failure = ""          # "" | "expired" | "engine" | "stopped"
        self.dispatch_id = 0
        self.dispatch_lanes = 0
        self.dispatch_clients = 0
        # distributed-tracing: the request's TraceContext (or None) and,
        # after dispatch, how many traced requests shared the dispatch
        self.trace_ctx = trace_ctx
        self.dispatch_traces = 0

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self.done.wait(timeout)


class Coalescer:
    def __init__(self, verify_fn: VerifyFn, *,
                 max_queue_lanes: int = 65536,
                 max_lanes_per_dispatch: int = 40960,
                 scheduler: Optional[AdaptiveFlushScheduler] = None):
        self._verify_fn = verify_fn
        self._max_queue_lanes = max_queue_lanes
        self._max_lanes_per_dispatch = max_lanes_per_dispatch
        # a PRIVATE scheduler — the daemon's arrival/RTT profile is the
        # aggregate of all clients, distinct from any one node's
        self.scheduler = scheduler or AdaptiveFlushScheduler()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queues: Dict[str, List[PendingRequest]] = {}
        self._queued_lanes = 0
        self._inflight = 0            # batches cut but not yet answered
        self._dispatch_seq = 0
        self._mesh_dispatches = 0
        self._running = False
        self._thread: Optional[threading.Thread] = None

    # --- lifecycle ---

    def start(self) -> None:
        with self._lock:
            if self._running:
                return
            self._running = True
        self._thread = threading.Thread(
            target=self._run, name="sidecar-coalescer", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        # fail whatever never dispatched so no client blocks forever
        with self._lock:
            leftovers = [r for q in self._queues.values() for r in q]
            self._queues.clear()
            self._queued_lanes = 0
        for req in leftovers:
            req.error = "coalescer stopped"
            req.failure = "stopped"
            req.done.set()

    # --- client side ---

    def submit(self, client_id: str, curve: str, items: List[tuple],
               tally: bool, deadline_s: Optional[float] = None,
               trace_ctx=None) -> PendingRequest:
        """Enqueue; returns a waitable :class:`PendingRequest`. Raises
        :class:`Overloaded` when queues are full (never queues partial
        requests). ``trace_ctx`` (a libs.trace.TraceContext or None)
        tags the joint dispatch this request ends up riding."""
        req = PendingRequest(
            client_id, curve, items, tally,
            None if deadline_s is None
            else time.monotonic() + deadline_s,
            trace_ctx=trace_ctx)
        with self._cond:
            if not self._running:
                raise Overloaded("coalescer not running")
            if self._queued_lanes + len(items) > self._max_queue_lanes:
                _m.sidecar_server_overloads_total.inc()
                raise Overloaded(
                    f"queue full: {self._queued_lanes} lanes queued, "
                    f"+{len(items)} exceeds cap {self._max_queue_lanes}")
            self._queues.setdefault(curve, []).append(req)
            self._queued_lanes += len(items)
            _m.sidecar_server_queue_lanes.set(self._queued_lanes)
            self._cond.notify_all()
        self.scheduler.note_arrivals(len(items))
        return req

    def queued_lanes(self) -> int:
        with self._lock:
            return self._queued_lanes

    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until every queued request has dispatched AND every cut
        batch has been answered, or the timeout passes (returns False).
        The dispatcher keeps running — graceful shutdown calls drain()
        first (with admission already closed upstream), then stop()."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._running and (self._queued_lanes > 0
                                     or self._inflight > 0):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                # bounded wait: the dispatcher notifies on completion,
                # but a wedged engine must not turn drain into a hang
                self._cond.wait(timeout=min(remaining, 0.25))
            return self._queued_lanes == 0 and self._inflight == 0

    def snapshot(self) -> Dict:
        with self._lock:
            per_curve = {c: sum(len(r.items) for r in q)
                         for c, q in self._queues.items() if q}
            return {"queued_lanes": self._queued_lanes,
                    "queued_by_curve": per_curve,
                    "inflight_batches": self._inflight,
                    "dispatches": self._dispatch_seq,
                    "mesh_dispatches": self._mesh_dispatches,
                    "scheduler": self.scheduler.snapshot()}

    # --- dispatcher ---

    def _pick_curve_locked(self) -> Optional[str]:
        """Curve whose oldest request has waited longest (FIFO across
        curves so a busy ed25519 stream cannot starve a k1 trickle)."""
        best, best_t = None, None
        for curve, q in self._queues.items():
            if q and (best_t is None or q[0].enqueued_at < best_t):
                best, best_t = curve, q[0].enqueued_at
        return best

    def _run(self) -> None:
        while True:
            batch: List[PendingRequest] = []
            with self._cond:
                while self._running:
                    curve = self._pick_curve_locked()
                    if curve is None:
                        with trace.span("sidecar.coalescer.idle"):
                            self._cond.wait(timeout=_IDLE_POLL_S)
                        continue
                    q = self._queues[curve]
                    lanes = sum(len(r.items) for r in q)
                    # gather: linger only while the adaptive window says
                    # more arrivals are worth the wait AND the oldest
                    # request has slack before its deadline
                    wait = self.scheduler.gather_wait_s(lanes)
                    if lanes >= self._max_lanes_per_dispatch:
                        wait = 0.0
                    now = time.monotonic()
                    elapsed = now - q[0].enqueued_at
                    remaining = wait - elapsed
                    if q[0].deadline is not None:
                        remaining = min(remaining, q[0].deadline - now)
                    if remaining > 1e-4:
                        with trace.span("sidecar.coalescer.linger"):
                            self._cond.wait(timeout=remaining)
                        continue
                    # cut whole requests up to the dispatch cap (always
                    # at least one, even if alone it exceeds the cap)
                    taken_lanes = 0
                    while q and (not batch or taken_lanes + len(q[0].items)
                                 <= self._max_lanes_per_dispatch):
                        r = q.pop(0)
                        batch.append(r)
                        taken_lanes += len(r.items)
                    self._queued_lanes -= taken_lanes
                    self._inflight += 1
                    cut_at = time.monotonic()
                    _m.sidecar_server_queue_lanes.set(self._queued_lanes)
                    break
                if not self._running:
                    return
            if batch:
                # once a request, expired ones too: submit -> cut
                for req in batch:
                    _m.sidecar_server_queue_wait.observe(
                        cut_at - req.enqueued_at, curve=req.curve)
                try:
                    with trace.span("sidecar.coalescer.dispatch",
                                    curve=batch[0].curve,
                                    requests=len(batch)):
                        self._dispatch(batch[0].curve, batch)
                finally:
                    with self._cond:
                        self._inflight -= 1
                        self._cond.notify_all()

    def _dispatch(self, curve: str, batch: List[PendingRequest]) -> None:
        from tmtpu.libs import timeline as _tl

        # expired requests are answered without wasting device lanes
        now = time.monotonic()
        live: List[PendingRequest] = []
        for req in batch:
            if req.deadline is not None and now > req.deadline:
                req.error = "deadline expired before dispatch"
                req.failure = "expired"
                req.done.set()
            else:
                live.append(req)
        if not live:
            return
        with self._lock:
            self._dispatch_seq += 1
            dispatch_id = self._dispatch_seq
        joint: List[tuple] = []
        for req in live:
            joint.extend(req.items)
        clients = len({req.client_id for req in live})
        tally = any(req.tally for req in live)
        from tmtpu.tpu import mesh_dispatch as _mesh

        mesh_before = _mesh.dispatch_count()
        t0 = time.perf_counter()
        try:
            mask, _tallied = self._verify_fn(curve, joint, tally)
        except Exception as exc:  # noqa: BLE001 — engine bug must not
            # wedge clients; they get an error verdict, never a mask
            for req in live:
                req.error = f"verify engine failed: {exc}"
                req.failure = "engine"
                req.done.set()
            return
        dt = time.perf_counter() - t0
        self.scheduler.note_dispatch(len(joint), dt)
        _m.sidecar_server_dispatches_total.inc(curve=curve)
        _m.sidecar_server_dispatch_lanes.observe(len(joint), curve=curve)
        _m.sidecar_server_dispatch_clients.observe(clients)
        # did the engine shard this joint dispatch across the mesh? The
        # verify path (crypto/batch.py → tpu/mesh_dispatch.py) decides;
        # here we account for it: per-chip occupancy in Stats + metrics
        meshed = _mesh.dispatch_count() - mesh_before
        shards = 0
        if meshed:
            snap = _mesh.snapshot()
            shards = snap["devices"]
            with self._lock:
                self._mesh_dispatches += meshed
            _m.sidecar_server_mesh_dispatches.inc(meshed, curve=curve)
            for dev, lanes in snap["occupancy_lanes"].items():
                _m.sidecar_server_mesh_occupancy_lanes.set(
                    lanes, device=dev)
        _tl.record_sidecar(role="server", curve=curve, lanes=len(joint),
                           clients=clients, requests=len(live),
                           mesh_shards=shards,
                           seconds=round(dt, 6))
        # tag the joint dispatch with every context it served: one
        # sidecar.dispatch mark per distinct trace, so a fleet join sees
        # exactly which heights shared this device flush
        traced = [req.trace_ctx for req in live
                  if req.trace_ctx is not None]
        if traced:
            seen_tids = set()
            for ctx in traced:
                if ctx.trace_id in seen_tids:
                    continue
                seen_tids.add(ctx.trace_id)
                trace.mark("sidecar.dispatch", ctx=ctx,
                            dispatch_id=dispatch_id, lanes=len(joint),
                            clients=clients, requests=len(live),
                            seconds=round(dt, 6))
        if len(mask) != len(joint):
            for req in live:
                req.error = (f"verify engine returned {len(mask)} verdicts "
                             f"for {len(joint)} lanes")
                req.failure = "engine"
                req.done.set()
            return
        with trace.span("sidecar.coalescer.reply"):
            off = 0
            for req in live:
                n = len(req.items)
                req.mask = [bool(v) for v in mask[off:off + n]]
                # per-request tally recomputed from ITS slice — the joint
                # tallied sum spans all clients and belongs to nobody;
                # verify-only requests get 0, not a number they didn't
                # ask for
                req.tallied = sum(it[3] for it, ok
                                  in zip(req.items, req.mask)
                                  if ok) if req.tally else 0
                req.dispatch_id = dispatch_id
                req.dispatch_lanes = len(joint)
                req.dispatch_clients = clients
                req.dispatch_traces = len(traced)
                off += n
                req.done.set()

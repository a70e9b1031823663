"""Keyed gather queue: requests from many connections, one joint cut.

Requests enter per-key queues (the sidecar keys by curve, lightserve by
target height); a single dispatcher thread gathers them under the
adaptive-flush policy — a private
:class:`~tmtpu.crypto.batch.AdaptiveFlushScheduler` fed by real arrivals
and real dispatch round-trips — and hands each cut batch to the daemon's
:meth:`GatherQueue._dispatch`. Keys are served FIFO by their oldest
request, so a busy key cannot starve a trickle on another.

Whole-request granularity: a request never splits across cuts. A cut
takes whole requests until the next would push it past
``max_per_cut`` (always at least one, even if alone it exceeds the
cap); a request's ``size`` is what the cap and the admission bound
count — lanes for the sidecar, one per session for lightserve.

Every admitted request is finished exactly once: by the daemon's
dispatch, by a lapsed deadline, by an engine failure, or by ``stop``.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Callable, Dict, List, Optional

from tmtpu.crypto.batch import AdaptiveFlushScheduler
from tmtpu.libs import trace
from tmtpu.libs.framed.protocol import STATUS_OVERLOADED, STATUS_SHUTTING_DOWN

# The dispatcher's wait with nothing queued ends at a submit's notify or
# after this long. A profiler session records only spans that begin and
# end inside it, so the wait is cut short enough that an idle daemon's
# first and last ``<prefix>.coalescer.idle`` cost a 6 s profile under 2%.
_IDLE_POLL_S = 0.05


class Overloaded(Exception):
    """Admission control rejected the request; queues are full."""


def failure_status(failure: str, engine_down: int) -> int:
    """The status a request that never got its answer is refused with:
    a lapsed deadline reads as overload (clients fall back without
    penalty), a stopped queue as shutting down, anything else as the
    namespace's ``engine_down``."""
    return {"expired": STATUS_OVERLOADED,
            "stopped": STATUS_SHUTTING_DOWN}.get(failure, engine_down)


class Pending:
    """One request riding toward a joint dispatch."""

    __slots__ = ("client_id", "size", "deadline", "enqueued_at", "done",
                 "error", "failure", "dispatch_id", "on_done")

    def __init__(self, client_id: str, size: int,
                 deadline_s: Optional[float],
                 on_done: Optional[Callable[["Pending"], None]] = None):
        self.client_id = client_id
        self.size = size
        self.enqueued_at = now = time.monotonic()
        # monotonic, None = no deadline
        self.deadline = None if deadline_s is None else now + deadline_s
        self.done = threading.Event()
        self.error = ""
        self.failure = ""          # "" | "expired" | "engine" | "stopped"
        self.dispatch_id = 0
        # completion hook: invoked exactly once, AFTER done is set, on
        # whichever thread finished the request. Keep it cheap and
        # non-blocking (lightserve hands off to its reply pool).
        self.on_done = on_done

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self.done.wait(timeout)

    def finish(self) -> None:
        """Mark the request complete and fire its completion hook."""
        self.done.set()
        cb, self.on_done = self.on_done, None   # once, ever
        if cb is not None:
            try:
                cb(self)
            except Exception:  # noqa: BLE001 — a reply-path bug must
                pass           # not wedge the dispatcher thread

    def fail(self, failure: str, error: str) -> None:
        self.error = error
        self.failure = failure
        self.finish()


class GatherQueue:
    # the keys snapshot() reports: queued total, queued by key, batches
    # cut but not yet answered, joint dispatches so far
    SNAPSHOT_KEYS = ("queued", "queued_by_key", "inflight", "dispatches")

    def __init__(self, *, prefix: str, unit: str, max_queued: int,
                 overloads, queued_gauge, max_per_cut: float = math.inf,
                 scheduler: Optional[AdaptiveFlushScheduler] = None):
        self._prefix = prefix
        self._unit = unit
        self._max_queued = max_queued
        self._max_per_cut = max_per_cut
        self._overloads = overloads
        self._queued_gauge = queued_gauge
        # a PRIVATE scheduler — the daemon's arrival/RTT profile is the
        # aggregate of all clients, distinct from any one node's
        self.scheduler = scheduler or AdaptiveFlushScheduler()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queues: Dict[object, List[Pending]] = {}
        self._queued = 0
        self._inflight = 0            # batches cut but not yet answered
        self._seq = 0
        self._running = False
        self._thread: Optional[threading.Thread] = None

    def _dispatch(self, key, batch: List[Pending], cut_at: float) -> None:
        """Run one cut batch and finish every request in it."""
        raise NotImplementedError

    # --- lifecycle ---

    def start(self) -> None:
        with self._lock:
            if self._running:
                return
            self._running = True
        self._thread = threading.Thread(
            target=self._run, name=f"{self._prefix}-coalescer",
            daemon=True)
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
            self._queued = 0
        for req in leftovers:
            req.fail("stopped", "coalescer stopped")

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until every queued request has dispatched AND every cut
        batch has been answered, or the timeout passes (returns False).
        The dispatcher keeps running — graceful shutdown calls drain()
        first (with admission already closed upstream), then stop()."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._running and (self._queued > 0
                                     or self._inflight > 0):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                # bounded wait: the dispatcher notifies on completion,
                # but a wedged engine must not turn drain into a hang
                self._cond.wait(timeout=min(remaining, 0.25))
            return self._queued == 0 and self._inflight == 0

    # --- client side ---

    def _admit(self, key, req: Pending) -> Pending:
        """Enqueue ``req`` under ``key``, or raise :class:`Overloaded`
        when it would push the queued total past ``max_queued``: the
        daemon answers ``STATUS_OVERLOADED`` — explicit backpressure —
        instead of queueing unboundedly and blowing every caller's
        deadline. Never queues part of a request."""
        with self._cond:
            if not self._running:
                raise Overloaded("coalescer not running")
            if self._queued + req.size > self._max_queued:
                self._overloads.inc()
                raise Overloaded(
                    f"queue full: {self._queued} {self._unit} queued, "
                    f"+{req.size} exceeds cap {self._max_queued}")
            self._queues.setdefault(key, []).append(req)
            self._queued += req.size
            self._queued_gauge.set(self._queued)
            self._cond.notify_all()
        self.scheduler.note_arrivals(req.size)
        return req

    def queued(self) -> int:
        with self._lock:
            return self._queued

    def snapshot(self) -> Dict:
        queued, by_key, inflight, cuts = self.SNAPSHOT_KEYS
        with self._lock:
            return {queued: self._queued,
                    by_key: {k: sum(r.size for r in q)
                             for k, q in self._queues.items() if q},
                    inflight: self._inflight,
                    cuts: self._seq,
                    "scheduler": self.scheduler.snapshot()}

    # --- dispatcher ---

    def _pick_locked(self):
        """Key whose oldest request has waited longest."""
        best, best_t = None, None
        for key, q in self._queues.items():
            if q and (best_t is None or q[0].enqueued_at < best_t):
                best, best_t = key, q[0].enqueued_at
        return best

    def _run(self) -> None:
        idle_span = f"{self._prefix}.coalescer.idle"
        linger_span = f"{self._prefix}.coalescer.linger"
        while True:
            batch: List[Pending] = []
            with self._cond:
                while self._running:
                    key = self._pick_locked()
                    if key is None:
                        with trace.span(idle_span):
                            self._cond.wait(timeout=_IDLE_POLL_S)
                        continue
                    q = self._queues[key]
                    size = sum(r.size for r in q)
                    # gather: linger only while the adaptive window says
                    # more arrivals are worth the wait AND the oldest
                    # request has slack before its deadline
                    wait = self.scheduler.gather_wait_s(size)
                    if size >= self._max_per_cut:
                        wait = 0.0
                    now = time.monotonic()
                    remaining = wait - (now - q[0].enqueued_at)
                    if q[0].deadline is not None:
                        remaining = min(remaining, q[0].deadline - now)
                    if remaining > 1e-4:
                        with trace.span(linger_span):
                            self._cond.wait(timeout=remaining)
                        continue
                    # cut whole requests up to the cap (always at least
                    # one, even if alone it exceeds the cap)
                    n = taken = 0
                    for r in q:
                        if n and taken + r.size > self._max_per_cut:
                            break
                        n += 1
                        taken += r.size
                    batch = q[:n]
                    del q[:n]
                    if not q:
                        del self._queues[key]
                    self._queued -= taken
                    self._inflight += 1
                    cut_at = time.monotonic()
                    self._queued_gauge.set(self._queued)
                    break
                if not self._running:
                    return
            if batch:
                try:
                    self._dispatch(key, batch, cut_at)
                finally:
                    with self._cond:
                        self._inflight -= 1
                        self._cond.notify_all()

    def _live(self, batch: List[Pending], stage: str) -> List[Pending]:
        """Answer the requests whose deadline passed before ``stage``
        without wasting work on them; returns the rest."""
        now = time.monotonic()
        live = []
        for req in batch:
            if req.deadline is not None and now > req.deadline:
                req.fail("expired", f"deadline expired before {stage}")
            else:
                live.append(req)
        return live

    def _next_id(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

"""Height-keyed session coalescing: many cold clients, one joint resolve.

The serving tier's second line of defense (the fact cache is the
first): when N clients concurrently ask about the SAME uncached target
height, exactly one bisection resolve runs — one set of provider
fetches, one set of device dispatches — and every waiting session gets
its own per-request slice of the outcome (the hop chain from ITS
trusted height, cut from the shared verified path).

The gathering — FIFO across heights, admission control past
``max_queue_sessions`` — is the framed core's
:class:`~tmtpu.libs.framed.gather.GatherQueue`, keyed by target height.
A session IS the unit, so there is no cut cap: every queued session for
the chosen height rides the one resolve.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional

from tmtpu.crypto.batch import AdaptiveFlushScheduler
from tmtpu.libs import metrics as _m
from tmtpu.libs.framed.gather import (  # noqa: F401 — Overloaded re-exported
    GatherQueue,
    Overloaded,
    Pending,
)

# resolve engine: (target_height, now_ns) -> resolution object
# (opaque to the coalescer; the slice function interprets it)
ResolveFn = Callable[[int, int], object]
# per-session outcome: (pending, resolution) -> None, fills the pending
# session's result fields from its own (trusted_height, trusted_hash)
SliceFn = Callable[["PendingSync", object], None]


class PendingSync(Pending):
    """One client session riding toward a joint resolve."""

    __slots__ = ("target_height", "trusted_height", "trusted_hash",
                 "now_ns", "status", "hops", "dispatches", "cache_hit",
                 "coalesced")

    def __init__(self, client_id: str, target_height: int,
                 trusted_height: int, trusted_hash: bytes, now_ns: int,
                 deadline_s: Optional[float],
                 on_done: Optional[Callable[["PendingSync"], None]]
                 = None):
        super().__init__(client_id, 1, deadline_s, on_done)
        self.target_height = target_height
        self.trusted_height = trusted_height
        self.trusted_hash = trusted_hash
        self.now_ns = now_ns
        self.status: Optional[int] = None
        self.hops: Optional[list] = None   # List[Fact], ascending
        self.dispatches = 0
        self.cache_hit = False
        self.coalesced = 0


class SyncCoalescer(GatherQueue):
    SNAPSHOT_KEYS = ("queued_sessions", "queued_by_height",
                     "inflight_resolves", "resolves")

    def __init__(self, resolve_fn: ResolveFn, slice_fn: SliceFn, *,
                 max_queue_sessions: int = 65536,
                 scheduler: Optional[AdaptiveFlushScheduler] = None):
        super().__init__(prefix="lightserve", unit="sessions",
                         max_queued=max_queue_sessions,
                         overloads=_m.lightserve_server_overloads_total,
                         queued_gauge=_m.lightserve_server_backlog,
                         scheduler=scheduler)
        self._resolve_fn = resolve_fn
        self._slice_fn = slice_fn

    def submit(self, client_id: str, target_height: int,
               trusted_height: int, trusted_hash: bytes, now_ns: int,
               deadline_s: Optional[float] = None,
               on_done: Optional[Callable[[PendingSync], None]] = None
               ) -> PendingSync:
        """Enqueue; returns a waitable :class:`PendingSync`. Raises
        :class:`Overloaded` when the session backlog is full. Every
        admitted session's ``on_done`` hook fires exactly once — on
        resolve, slice failure, deadline lapse, or coalescer stop."""
        return self._admit(target_height, PendingSync(
            client_id, target_height, trusted_height, trusted_hash,
            now_ns, deadline_s, on_done))

    def backlog(self) -> int:
        with self._lock:
            return self._queued + self._inflight

    def _dispatch(self, target_height: int, batch: List[PendingSync],
                  cut_at: float) -> None:
        # sessions whose deadline already passed are answered without
        # wasting a resolve slot on them
        live = self._live(batch, "resolve")
        if not live:
            return
        resolve_id = self._next_id()
        # the joint resolve judges expiry at the newest admission
        # stamp. Every now_ns is SERVER-stamped at admission (the
        # server never forwards a client clock here), so the max is
        # simply the most recent server-clock reading in the batch.
        now_ns = max(req.now_ns for req in live)
        t0 = time.perf_counter()
        try:
            resolution = self._resolve_fn(target_height, now_ns)
        except Exception as exc:  # noqa: BLE001 — engine bug must not
            # wedge sessions; they get an error verdict, never a chain
            for req in live:
                req.fail("engine", f"resolve engine failed: {exc}")
            return
        dt = time.perf_counter() - t0
        self.scheduler.note_dispatch(len(live), dt)
        _m.lightserve_server_resolves_total.inc()
        _m.lightserve_server_coalesced_sessions.observe(len(live))
        for req in live:
            req.dispatch_id = resolve_id
            req.coalesced = len(live)
            try:
                self._slice_fn(req, resolution)
            except Exception as exc:  # noqa: BLE001
                req.error = f"slice failed: {exc}"
                req.failure = "engine"
            req.finish()

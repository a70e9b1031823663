"""Cross-client batch coalescing: many requests, one device dispatch.

This is the piece that turns one TPU into a shared resource: four
localnet nodes each verifying ~100 lanes/block become one daemon
dispatching ~400-lane joint batches. Requests from any number of
connections enter per-curve queues of the framed core's
:class:`~tmtpu.libs.framed.gather.GatherQueue` (adaptive gather,
admission control, drain and stop); each cut batch becomes ONE
concatenated lane list for the verify engine. Each request
gets back exactly its slice of the joint mask plus the dispatch
metadata (id, total lanes, distinct clients) so clients — and the
two-client coalescing test — can PROVE their lanes shared a dispatch.

A request's lanes never split across dispatches, so mask slicing is a
single contiguous cut and a request observes exactly one dispatch;
``max_lanes_per_dispatch`` is the cut's soft cap and ``max_queue_lanes``
the admission bound (``STATUS_OVERLOADED`` past it).
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Tuple

from tmtpu.crypto.batch import AdaptiveFlushScheduler
from tmtpu.libs import metrics as _m
from tmtpu.libs import trace
from tmtpu.libs.framed.gather import (  # noqa: F401 — Overloaded re-exported
    GatherQueue,
    Overloaded,
    Pending,
)

# verify engine signature: (curve, [(pk, msg, sig, power)], tally)
#   -> (mask, tallied)
VerifyFn = Callable[[str, List[tuple], bool], Tuple[List[bool], int]]


class PendingRequest(Pending):
    """One client's verify request riding toward a joint dispatch."""

    __slots__ = ("curve", "items", "tally", "mask", "tallied",
                 "dispatch_lanes", "dispatch_clients", "trace_ctx",
                 "dispatch_traces")

    def __init__(self, client_id: str, curve: str, items: List[tuple],
                 tally: bool, deadline_s: Optional[float],
                 trace_ctx=None):
        super().__init__(client_id, len(items), deadline_s)
        self.curve = curve
        self.items = items
        self.tally = tally
        self.mask: Optional[List[bool]] = None
        self.tallied = 0
        self.dispatch_lanes = 0
        self.dispatch_clients = 0
        # distributed-tracing: the request's TraceContext (or None) and,
        # after dispatch, how many traced requests shared the dispatch
        self.trace_ctx = trace_ctx
        self.dispatch_traces = 0


class Coalescer(GatherQueue):
    SNAPSHOT_KEYS = ("queued_lanes", "queued_by_curve", "inflight_batches",
                     "dispatches")

    def __init__(self, verify_fn: VerifyFn, *,
                 max_queue_lanes: int = 65536,
                 max_lanes_per_dispatch: int = 40960,
                 scheduler: Optional[AdaptiveFlushScheduler] = None):
        super().__init__(prefix="sidecar", unit="lanes",
                         max_queued=max_queue_lanes,
                         overloads=_m.sidecar_server_overloads_total,
                         queued_gauge=_m.sidecar_server_queue_lanes,
                         max_per_cut=max_lanes_per_dispatch,
                         scheduler=scheduler)
        self._verify_fn = verify_fn
        self._mesh_dispatches = 0

    def submit(self, client_id: str, curve: str, items: List[tuple],
               tally: bool, deadline_s: Optional[float] = None,
               trace_ctx=None) -> PendingRequest:
        """Enqueue; returns a waitable :class:`PendingRequest`. Raises
        :class:`Overloaded` when queues are full (never queues partial
        requests). ``trace_ctx`` (a libs.trace.TraceContext or None)
        tags the joint dispatch this request ends up riding."""
        return self._admit(curve, PendingRequest(
            client_id, curve, items, tally, deadline_s, trace_ctx))

    queued_lanes = GatherQueue.queued

    def snapshot(self):
        return {**super().snapshot(),
                "mesh_dispatches": self._mesh_dispatches}

    def _dispatch(self, curve: str, batch: List[PendingRequest],
                  cut_at: float) -> None:
        # once a request, expired ones too: submit -> cut
        for req in batch:
            _m.sidecar_server_queue_wait.observe(
                cut_at - req.enqueued_at, curve=curve)
        with trace.span("sidecar.coalescer.dispatch", curve=curve,
                        requests=len(batch)):
            self._verify(curve, batch)

    def _verify(self, curve: str, batch: List[PendingRequest]) -> None:
        from tmtpu.libs import timeline as _tl

        # expired requests are answered without wasting device lanes
        live = self._live(batch, "dispatch")
        if not live:
            return
        dispatch_id = self._next_id()
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
                req.fail("engine", f"verify engine failed: {exc}")
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
                req.fail("engine", f"verify engine returned {len(mask)} "
                                   f"verdicts for {len(joint)} lanes")
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
                req.finish()

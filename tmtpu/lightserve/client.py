"""Lightserve client: one multiplexed connection, many in-flight sessions.

The connection is :class:`tmtpu.libs.framed.client.FramedClient`'s, so
one connection carries thousands of pipelined sessions — the shape the
flood harness uses to hold 10k+ sessions with a handful of sockets.

Failure kinds, for caller policy:

- :class:`LightserveUnavailable` — can't connect, connection died,
  deadline hit, daemon answered upstream_down/shutting_down. Retryable
  against another daemon.
- :class:`LightserveOverloaded` — explicit admission-control
  backpressure; the daemon is healthy. Back off and resubmit.
- :class:`LightserveRefused` — the daemon understood and said no:
  the trusting period lapsed (``expired``), the client's trusted hash
  conflicts with the verified spine (``untrusted`` — treat as possible
  fork evidence!), or the request was malformed. NOT retryable.

The blocking :meth:`sync` wraps the async pair
:meth:`sync_submit`/:meth:`SyncHandle.result`.
"""

from __future__ import annotations

import os
import time
from typing import List, Optional, Tuple

from tmtpu.libs import metrics as _m
from tmtpu.libs.framed.client import (
    DaemonUnavailable,
    FramedClient,
    Waiter,
)
from tmtpu.lightserve import protocol as proto

ENV_ADDR = "TMTPU_LIGHTSERVE_ADDR"


def default_addr(home: str = "") -> str:
    """Explicit config addr wins (caller passes it through), then
    ``TMTPU_LIGHTSERVE_ADDR``, then the per-home unix socket."""
    env = os.environ.get(ENV_ADDR, "")
    if env:
        return env
    if home:
        return f"unix://{os.path.join(home, 'data', 'lightserve.sock')}"
    return ""


class LightserveError(Exception):
    pass


class LightserveUnavailable(LightserveError, DaemonUnavailable):
    """Daemon unreachable / dead connection / deadline / hard error."""


class LightserveOverloaded(LightserveError):
    """Explicit backpressure: daemon healthy but the session queue is
    full."""


class LightserveRefused(LightserveError):
    """A definitive no: expired trust, conflicting trusted hash, or a
    bad request. Resubmitting the same session cannot succeed."""

    def __init__(self, status: int, message: str):
        super().__init__(message or proto.STATUS_NAMES.get(status,
                                                           str(status)))
        self.status = status


class SyncResult:
    """One answered session."""

    __slots__ = ("target_height", "hops", "dispatches", "cache_hit",
                 "dispatch_id", "coalesced")

    def __init__(self, target_height: int,
                 hops: List[Tuple[int, bytes, int]], dispatches: int,
                 cache_hit: bool, dispatch_id: int, coalesced: int):
        self.target_height = target_height
        # ascending (height, header_hash, header_time), ending at target
        self.hops = hops
        self.dispatches = dispatches
        self.cache_hit = cache_hit
        self.dispatch_id = dispatch_id   # 0 = answered inline from cache
        self.coalesced = coalesced


class SyncHandle:
    """An in-flight session: ``wait`` then ``result`` (or just
    ``result``, which waits)."""

    __slots__ = ("_client", "_rid", "_waiter", "submitted_at")

    def __init__(self, client: "LightserveClient", rid: int,
                 waiter: Waiter):
        self._client = client
        self._rid = rid
        self._waiter = waiter
        self.submitted_at = time.perf_counter()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._waiter.event.wait(timeout)

    def result(self, deadline_s: Optional[float] = None) -> SyncResult:
        return self._client._collect(self._rid, self._waiter,
                                     deadline_s, self.submitted_at)


class LightserveClient(FramedClient):
    proto = proto
    prefix = "lightserve"
    unavailable = LightserveUnavailable
    reconnects = _m.lightserve_client_reconnects
    up = _m.lightserve_client_up

    def __init__(self, addr: str, *,
                 client_id: str = "",
                 chain_id: str = "",
                 connect_timeout_s: float = 2.0,
                 request_deadline_s: float = 15.0,
                 retry_backoff_s: float = 1.0,
                 max_frame_bytes: int = proto.DEFAULT_MAX_FRAME_BYTES):
        super().__init__(addr, client_id=client_id,
                         connect_timeout_s=connect_timeout_s,
                         request_deadline_s=request_deadline_s,
                         retry_backoff_s=retry_backoff_s,
                         max_frame_bytes=max_frame_bytes)
        self.chain_id = chain_id       # "" = adopt the server's chain

    def _hello(self, version: int) -> proto.Hello:
        return proto.Hello(version=version, client_id=self.client_id,
                           chain_id=self.chain_id)

    # --- public API ---

    def sync_submit(self, trusted_height: int, trusted_hash: bytes,
                    target_height: int = 0,
                    now_ns: int = 0) -> SyncHandle:
        """Fire one session without blocking; collect it later via the
        handle. Many handles can ride one connection concurrently —
        same-target sessions coalesce server-side."""
        self._ensure_connected()
        rid = next(self._seq)
        w = self._send(rid, proto.SyncRequest(
            request_id=rid, trusted_height=trusted_height,
            trusted_hash=trusted_hash, target_height=target_height,
            now_ns=now_ns))
        return SyncHandle(self, rid, w)

    def _collect(self, rid: int, w: Waiter,
                 deadline_s: Optional[float],
                 submitted_at: float) -> SyncResult:
        try:
            reply = self._await(rid, w,
                                deadline_s or self._request_deadline_s)
        except LightserveUnavailable:
            _m.lightserve_client_requests.inc(status="error")
            raise
        _m.lightserve_client_request_latency.observe(
            time.perf_counter() - submitted_at)
        if not isinstance(reply, proto.SyncResponse):
            _m.lightserve_client_requests.inc(status="error")
            raise LightserveUnavailable(
                f"unexpected reply {type(reply).__name__}")
        status = proto.STATUS_NAMES.get(reply.status,
                                        str(reply.status))
        _m.lightserve_client_requests.inc(status=status)
        if reply.status == proto.STATUS_OVERLOADED:
            raise LightserveOverloaded(reply.error or "overloaded")
        if reply.status in (proto.STATUS_EXPIRED,
                            proto.STATUS_UNTRUSTED,
                            proto.STATUS_BAD_REQUEST):
            raise LightserveRefused(reply.status, reply.error)
        if reply.status != proto.STATUS_OK:
            raise LightserveUnavailable(
                f"lightserve status {status}: {reply.error}")
        hops = [(h.height, bytes(h.header_hash), h.header_time)
                for h in reply.hops]
        if not hops:
            raise LightserveUnavailable("ok response carried no hops")
        return SyncResult(hops[-1][0], hops, reply.dispatches,
                          reply.cache_hit, reply.dispatch_id,
                          reply.coalesced)

    def sync(self, trusted_height: int, trusted_hash: bytes,
             target_height: int = 0, now_ns: int = 0,
             deadline_s: Optional[float] = None) -> SyncResult:
        """One blocking session: prove ``target_height`` (0 = server's
        latest) from ``(trusted_height, trusted_hash)``."""
        handle = self.sync_submit(trusted_height, trusted_hash,
                                  target_height, now_ns)
        return handle.result(deadline_s)

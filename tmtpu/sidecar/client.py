"""Sidecar client: one multiplexed connection, many in-flight requests.

``SidecarClient`` is deliberately dumb about crypto — it moves raw
(pk_bytes, msg, sig, power) lanes over the wire and returns the
daemon's mask. All fallback POLICY (breaker, in-process retry, serial
CPU) lives in :class:`tmtpu.crypto.batch.SidecarBatchVerifier`; the
client only distinguishes the failure KINDS the policy needs:

- :class:`SidecarUnavailable` — can't connect, connection died
  mid-request, per-request deadline hit, or the daemon answered a
  non-OK status other than overload. Counts against the
  ``crypto.sidecar`` breaker.
- :class:`SidecarOverloaded` — explicit admission-control backpressure.
  The daemon is HEALTHY and saying "not now"; the caller verifies this
  batch in-process but does not penalize the breaker for it.

The connection itself — backoff, handshake (with the retry at v1 when
an old daemon refuses a v2 Hello), waiters, deadlines, ``ping`` and
``stats`` — is :class:`tmtpu.libs.framed.client.FramedClient`.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple

from tmtpu.libs import metrics as _m
from tmtpu.libs.framed.client import (
    DaemonUnavailable,
    FramedClient,
)
from tmtpu.sidecar import protocol as proto

ENV_ADDR = "TMTPU_SIDECAR_ADDR"


def default_addr(home: str = "") -> str:
    """Resolution order: explicit config addr (caller passes it through),
    ``TMTPU_SIDECAR_ADDR`` env, then the conventional per-home unix
    socket path."""
    env = os.environ.get(ENV_ADDR, "")
    if env:
        return env
    if home:
        return f"unix://{os.path.join(home, 'data', 'sidecar.sock')}"
    return ""


class SidecarError(Exception):
    pass


class SidecarUnavailable(SidecarError, DaemonUnavailable):
    """Daemon unreachable / dead connection / deadline / hard error."""


class SidecarOverloaded(SidecarError):
    """Explicit backpressure: daemon healthy but queues are full."""


class SidecarClient(FramedClient):
    proto = proto
    prefix = "sidecar"
    unavailable = SidecarUnavailable
    reconnects = _m.sidecar_client_reconnects
    up = _m.sidecar_client_up

    def __init__(self, addr: str, *,
                 client_id: str = "",
                 connect_timeout_s: float = 2.0,
                 request_deadline_s: float = 10.0,
                 retry_backoff_s: float = 1.0,
                 max_frame_bytes: int = proto.DEFAULT_MAX_FRAME_BYTES):
        super().__init__(addr, client_id=client_id,
                         connect_timeout_s=connect_timeout_s,
                         request_deadline_s=request_deadline_s,
                         retry_backoff_s=retry_backoff_s,
                         max_frame_bytes=max_frame_bytes)

    def _hello(self, version: int) -> proto.Hello:
        return proto.Hello(version=version, client_id=self.client_id,
                           features=["verify", "tally"])

    # --- public API ---

    def trace_ctx_supported(self) -> bool:
        """True when the daemon acked a version that knows the v2
        trace-context fields (never attach them to an older daemon)."""
        ack = self.hello_ack
        return ack is not None and \
            ack.version >= proto.TRACE_CTX_MIN_VERSION

    def verify(self, curve: str, lanes: List[Tuple[bytes, bytes, bytes,
                                                   int]],
               tally: bool = False,
               deadline_s: Optional[float] = None) -> Tuple[List[bool],
                                                            int, Dict]:
        """Ship lanes to the daemon; returns (mask, tallied, dispatch
        info). Raises :class:`SidecarOverloaded` on backpressure and
        :class:`SidecarUnavailable` on everything else non-OK.

        When the calling thread has an active trace context
        (libs.trace.activate) and the daemon speaks v2, the context
        rides the request so the daemon's joint dispatch is attributable
        to the height that caused it."""
        from tmtpu.libs import trace as _trace

        deadline_s = deadline_s or self._request_deadline_s
        self._ensure_connected()
        rid = next(self._seq)
        ctx = _trace.current_context()
        ctx_bytes = b""
        if ctx is not None and self.trace_ctx_supported():
            ctx_bytes = ctx.encode()
            _m.trace_context_tx.inc(transport="sidecar")
            _trace.mark("sidecar.verify", ctx=ctx, curve=curve,
                        lanes=len(lanes))
        req = proto.VerifyRequest(
            request_id=rid, curve=curve, tally=tally,
            deadline_ms=int(deadline_s * 1000),
            lanes=[proto.Lane(pub_key=pk, msg=m, sig=s, power=p)
                   for pk, m, s, p in lanes],
            trace_ctx=ctx_bytes)
        t0 = time.perf_counter()
        try:
            reply = self._await(rid, self._send(rid, req), deadline_s)
        except SidecarUnavailable:
            _m.sidecar_client_requests.inc(curve=curve, status="error")
            raise
        _m.sidecar_client_request_latency.observe(
            time.perf_counter() - t0, curve=curve)
        if not isinstance(reply, proto.VerifyResponse):
            _m.sidecar_client_requests.inc(curve=curve, status="error")
            raise SidecarUnavailable(
                f"unexpected reply {type(reply).__name__}")
        status = proto.STATUS_NAMES.get(reply.status,
                                        str(reply.status))
        _m.sidecar_client_requests.inc(curve=curve, status=status)
        if reply.status == proto.STATUS_OVERLOADED:
            raise SidecarOverloaded(reply.error or "overloaded")
        if reply.status != proto.STATUS_OK:
            raise SidecarUnavailable(
                f"sidecar status {status}: {reply.error}")
        if reply.lane_count != len(lanes):
            raise SidecarUnavailable(
                f"sidecar answered {reply.lane_count} lanes "
                f"for {len(lanes)}")
        mask = proto.unpack_mask(reply.mask, reply.lane_count)
        info = {"dispatch_id": reply.dispatch_id,
                "dispatch_lanes": reply.dispatch_lanes,
                "dispatch_clients": reply.dispatch_clients,
                "dispatch_traces": reply.dispatch_traces}
        return mask, reply.tallied, info

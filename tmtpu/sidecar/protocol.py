"""Sidecar wire protocol: the sidecar's namespace of framed messages.

The frame, the handshake and the codes shared with every framed daemon
are :mod:`tmtpu.libs.framed.protocol`'s; this module holds the
sidecar's message classes, its versions and its frame cap. Both sides
enforce ``max_frame_bytes`` (default 8 MiB) — a VerifyRequest for 40960
lanes of (32B pk, ~110B msg, 64B sig) is ~8.5 MB, so real deployments
raise the cap in lockstep with ``max_lanes_per_dispatch``; the default
covers the 10k-validator north-star with headroom.

``PROTOCOL_VERSION`` bumps on any wire change; since v2 the daemon keeps
serving v1 clients (version-skew tolerance: an old client on a new
daemon just never sees the v2-only optional fields), and a v2 client
that gets ``ERR_VERSION`` from a v1 daemon retries the handshake at
version 1.

Version history:
- v1: Hello/HelloAck/Verify/Ping/Stats base protocol.
- v2: optional distributed-tracing context — ``VerifyRequest.trace_ctx``
  (libs/trace.py wire form) and ``VerifyResponse.dispatch_traces``
  (how many traced requests the joint dispatch coalesced). Both fields
  are additive; a v1 peer skips them as unknown fields.

Verify masks travel bit-packed (:func:`pack_mask`/:func:`unpack_mask`):
lane i's verdict is bit ``i & 7`` of byte ``i >> 3``, LSB-first —
40960 lanes fit in 5 KiB instead of a 40960-element repeated bool.
"""

from __future__ import annotations

from typing import Dict, List, Type

from tmtpu.libs.framed.protocol import (  # noqa: F401 — the shared codes
    ERR_PROTOCOL,
    ERR_VERSION,
    STATUS_BAD_REQUEST,
    STATUS_NAMES as _SHARED_NAMES,
    STATUS_OK,
    STATUS_OVERLOADED,
    Codec,
    ErrorReply,
    Ping,
    ProtocolError,
    StatsRequest,
    StatsResponse,
    parse_addr,
)
from tmtpu.libs.protoio import ProtoMessage

PROTOCOL_VERSION = 2
# every version this tree still speaks; the daemon accepts any of them
# and the negotiated version is min(client, server)
SUPPORTED_VERSIONS = (1, 2)
# first version carrying trace-context fields
TRACE_CTX_MIN_VERSION = 2

# Hard ceiling on one frame; configurable per server/client but both
# sides always enforce *some* cap so a corrupt length prefix can't OOM.
DEFAULT_MAX_FRAME_BYTES = 8 * 1024 * 1024

# --- VerifyResponse.status: the shared codes plus ---
STATUS_BACKEND_DOWN = 2    # device breaker open server-side; served serially

STATUS_NAMES = {**_SHARED_NAMES, STATUS_BACKEND_DOWN: "backend_down"}


class Hello(ProtoMessage):
    FIELDS = [
        (1, "version", "uint32"),
        (2, "client_id", "string"),
        (3, "features", ("rep", "string")),
    ]


class HelloAck(ProtoMessage):
    FIELDS = [
        (1, "version", "uint32"),
        (2, "server_id", "string"),
        (3, "backend", "string"),           # "tpu" | "cpu"
        (4, "max_lanes", "uint32"),          # per-request admission cap
        (5, "max_frame_bytes", "uint64"),
    ]


class Lane(ProtoMessage):
    """One signature to check. ``power`` rides along for fused
    verify+tally; 0 when the request is verify-only."""

    FIELDS = [
        (1, "pub_key", "bytes"),
        (2, "msg", "bytes"),
        (3, "sig", "bytes"),
        (4, "power", "int64"),
    ]


class VerifyRequest(ProtoMessage):
    FIELDS = [
        (1, "request_id", "uint64"),
        (2, "curve", "string"),             # "ed25519" | "sr25519" | "secp256k1"
        (3, "tally", "bool"),
        (4, "deadline_ms", "uint32"),        # 0 = server default
        (5, "lanes", ("rep", ("msg", Lane))),
        # v2: optional trace context (libs/trace.py wire form; empty =
        # untraced). Clients only attach it when the daemon acked v2.
        (6, "trace_ctx", "bytes"),
    ]


class VerifyResponse(ProtoMessage):
    FIELDS = [
        (1, "request_id", "uint64"),
        (2, "status", "uint32"),
        (3, "mask", "bytes"),                # bit-packed, lane_count bits
        (4, "lane_count", "uint32"),
        (5, "tallied", "int64"),
        (6, "dispatch_id", "uint64"),        # joint-dispatch identity…
        (7, "dispatch_lanes", "uint32"),     # …total lanes it carried
        (8, "dispatch_clients", "uint32"),   # …distinct clients coalesced
        (9, "error", "string"),
        # v2: how many traced requests the joint dispatch served — the
        # coalescer's dispatch span carries the trace ids themselves
        (10, "dispatch_traces", "uint32"),
    ]


class Pong(ProtoMessage):
    FIELDS = [
        (1, "nonce", "uint64"),
        (2, "backend", "string"),
        (3, "uptime_ms", "uint64"),
    ]


# type_byte → message class. Gaps left for future message kinds; numbers
# are wire-visible and MUST never be reused for a different class.
MESSAGE_TYPES: Dict[int, Type[ProtoMessage]] = {
    1: Hello,
    2: HelloAck,
    3: VerifyRequest,
    4: VerifyResponse,
    5: Ping,
    6: Pong,
    7: StatsRequest,
    8: StatsResponse,
    9: ErrorReply,
}

CODEC = Codec(MESSAGE_TYPES, DEFAULT_MAX_FRAME_BYTES)
TYPE_BYTES = CODEC.type_bytes
encode_frame = CODEC.encode_frame
decode_frame = CODEC.decode_frame
write_frame = CODEC.write_frame
FrameReader = CODEC.reader


def pack_mask(mask: List[bool]) -> bytes:
    out = bytearray((len(mask) + 7) // 8)
    for i, ok in enumerate(mask):
        if ok:
            out[i >> 3] |= 1 << (i & 7)
    return bytes(out)


def unpack_mask(packed: bytes, lane_count: int) -> List[bool]:
    if len(packed) < (lane_count + 7) // 8:
        raise ProtocolError(
            f"mask too short: {len(packed)} bytes for {lane_count} lanes")
    return [bool(packed[i >> 3] & (1 << (i & 7))) for i in range(lane_count)]

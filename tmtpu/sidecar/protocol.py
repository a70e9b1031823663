"""Sidecar wire protocol: length-prefixed frames carrying typed messages.

Framing (reuses :mod:`tmtpu.libs.protoio` primitives):

    frame   = uvarint(len(body)) || body
    body    = type_byte || payload
    payload = protobuf encoding of the message class for type_byte

One byte of type tag inside the length prefix keeps the stream
self-describing without a wrapper message, and lets the reader reject
unknown or oversized frames before decoding a single field. Both sides
enforce ``max_frame_bytes`` (default 8 MiB) — a VerifyRequest for 40960
lanes of (32B pk, ~110B msg, 64B sig) is ~8.5 MB, so real deployments
raise the cap in lockstep with ``max_lanes_per_dispatch``; the default
covers the 10k-validator north-star with headroom.

Handshake: client sends :class:`Hello` first; server answers
:class:`HelloAck` carrying the NEGOTIATED version (min of both sides,
``SUPPORTED_VERSIONS`` only) or :class:`ErrorReply` (``ERR_VERSION``)
and closes on an unsupported version. Anything else as a first message
is a protocol error. ``PROTOCOL_VERSION`` bumps on any wire change;
since v2 the daemon keeps serving v1 clients (version-skew tolerance:
an old client on a new daemon just never sees the v2-only optional
fields), and a v2 client that gets ``ERR_VERSION`` from a v1 daemon
retries the handshake at version 1.

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

import io
from typing import Dict, List, Optional, Tuple, Type

from tmtpu.libs.protoio import (
    DelimitedReader,
    ProtoMessage,
    encode_uvarint,
)

PROTOCOL_VERSION = 2
# every version this tree still speaks; the daemon accepts any of them
# and the negotiated version is min(client, server)
SUPPORTED_VERSIONS = (1, 2)
# first version carrying trace-context fields
TRACE_CTX_MIN_VERSION = 2

# Hard ceiling on one frame; configurable per server/client but both
# sides always enforce *some* cap so a corrupt length prefix can't OOM.
DEFAULT_MAX_FRAME_BYTES = 8 * 1024 * 1024

# --- VerifyResponse.status ---
STATUS_OK = 0
STATUS_OVERLOADED = 1      # admission control rejected; retry or fall back
STATUS_BACKEND_DOWN = 2    # device breaker open server-side; served serially
STATUS_BAD_REQUEST = 3     # unknown curve, zero lanes, malformed lane
STATUS_SHUTTING_DOWN = 4   # daemon draining; do not resubmit

STATUS_NAMES = {
    STATUS_OK: "ok",
    STATUS_OVERLOADED: "overloaded",
    STATUS_BACKEND_DOWN: "backend_down",
    STATUS_BAD_REQUEST: "bad_request",
    STATUS_SHUTTING_DOWN: "shutting_down",
}

# --- ErrorReply.code ---
ERR_VERSION = 1        # Hello.version not in SUPPORTED_VERSIONS
ERR_PROTOCOL = 2       # bad frame / unexpected message sequence
ERR_INTERNAL = 3       # server bug; connection stays usable


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


class Ping(ProtoMessage):
    FIELDS = [(1, "nonce", "uint64")]


class Pong(ProtoMessage):
    FIELDS = [
        (1, "nonce", "uint64"),
        (2, "backend", "string"),
        (3, "uptime_ms", "uint64"),
    ]


class StatsRequest(ProtoMessage):
    FIELDS = []


class StatsResponse(ProtoMessage):
    """Introspection snapshot; ``stats_json`` is a JSON object so the
    payload can grow without protocol bumps (it is advisory, not
    consensus-critical)."""

    FIELDS = [(1, "stats_json", "bytes")]


class ErrorReply(ProtoMessage):
    FIELDS = [
        (1, "request_id", "uint64"),         # 0 when not tied to a request
        (2, "code", "uint32"),
        (3, "message", "string"),
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

TYPE_BYTES: Dict[Type[ProtoMessage], int] = {
    cls: tb for tb, cls in MESSAGE_TYPES.items()
}


class ProtocolError(Exception):
    """Raised on malformed frames, unknown types, or bad sequencing."""


def encode_frame(msg: ProtoMessage,
                 type_bytes: Optional[Dict[Type[ProtoMessage], int]] = None
                 ) -> bytes:
    """Encode one frame. ``type_bytes`` defaults to the sidecar registry;
    sibling frame protocols (tmtpu/lightserve) pass their own class→tag
    map to reuse the codec without sharing a wire namespace."""
    tb = (TYPE_BYTES if type_bytes is None else type_bytes).get(type(msg))
    if tb is None:
        raise ProtocolError(f"unregistered message type {type(msg).__name__}")
    body = bytes([tb]) + msg.encode()
    return encode_uvarint(len(body)) + body


def decode_frame(body: bytes,
                 message_types: Optional[Dict[int, Type[ProtoMessage]]] = None
                 ) -> ProtoMessage:
    """Decode one frame *body* (type byte + payload, length prefix already
    stripped). ``message_types`` defaults to the sidecar registry; sibling
    protocols pass their own tag→class map."""
    if not body:
        raise ProtocolError("empty frame")
    cls = (MESSAGE_TYPES if message_types is None else message_types
           ).get(body[0])
    if cls is None:
        raise ProtocolError(f"unknown message type {body[0]}")
    try:
        return cls.decode(body[1:])
    except (EOFError, ValueError, UnicodeDecodeError) as exc:
        raise ProtocolError(
            f"malformed {cls.__name__} payload: {exc}") from exc


class FrameReader:
    """Reads framed messages from a binary stream, enforcing the frame cap.

    Thin veneer over :class:`protoio.DelimitedReader`; EOF mid-frame
    surfaces as ``EOFError`` (peer went away), anything else malformed as
    :class:`ProtocolError` so the connection loop can answer
    ``ERR_PROTOCOL`` before closing. ``message_types`` selects the tag
    registry (defaults to the sidecar's).
    """

    def __init__(self, stream, max_frame_bytes: int = DEFAULT_MAX_FRAME_BYTES,
                 message_types: Optional[Dict[int,
                                              Type[ProtoMessage]]] = None):
        self._rd = DelimitedReader(stream, max_size=max_frame_bytes)
        self._message_types = message_types

    def read_body(self) -> bytes:
        """Block until one whole frame body has arrived."""
        try:
            return self._rd.read_msg()
        except ValueError as exc:  # oversized frame / runaway varint
            raise ProtocolError(str(exc)) from exc

    def decode(self, body: bytes) -> ProtoMessage:
        return decode_frame(body, self._message_types)

    def read_msg(self) -> ProtoMessage:
        return self.decode(self.read_body())


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


def write_frame(stream: io.RawIOBase, msg: ProtoMessage,
                type_bytes: Optional[Dict[Type[ProtoMessage], int]] = None
                ) -> None:
    stream.write(encode_frame(msg, type_bytes))
    flush = getattr(stream, "flush", None)
    if flush is not None:
        flush()


def parse_addr(addr: str) -> Tuple[str, object]:
    """Parse ``unix:///path/to.sock`` or ``tcp://host:port`` into
    ``("unix", path)`` / ``("tcp", (host, port))``."""
    if addr.startswith("unix://"):
        path = addr[len("unix://"):]
        if not path:
            raise ValueError(f"empty unix socket path in {addr!r}")
        return "unix", path
    if addr.startswith("tcp://"):
        hostport = addr[len("tcp://"):]
        host, sep, port = hostport.rpartition(":")
        if not sep or not host:
            raise ValueError(f"tcp address needs host:port: {addr!r}")
        return "tcp", (host, int(port))
    raise ValueError(
        f"sidecar address must be unix:// or tcp://, got {addr!r}")

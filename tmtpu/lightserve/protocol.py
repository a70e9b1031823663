"""Lightserve wire protocol: commit-proof sessions over framed messages.

The frame, the handshake and the shared codes are
:mod:`tmtpu.libs.framed.protocol`'s; this module is the lightserve
namespace: its message classes, its statuses and its frame cap.

A session is one :class:`SyncRequest`: "I trust ``(trusted_height,
trusted_hash)``; prove ``target_height`` to me." The daemon answers
with the chain of verified-header hops (bisection pivots per
arXiv:2010.07031) from at-or-below the client's trusted height up to
the target, plus accounting: how many device dispatches the answer
actually cost (0 = pure cache hit) and how many concurrent sessions
shared the joint resolve. Frames are small — hops are (height, hash,
time) facts, never validator sets — so the default frame cap is 1 MiB,
not the sidecar's 8.

Handshake: client sends :class:`Hello` first (with the chain id it
expects); server answers :class:`HelloAck` carrying its chain id,
trust anchor, and latest verified height — a cold client with no
social-consensus anchor of its own can adopt the server's.
"""

from __future__ import annotations

from typing import Dict, Type

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

PROTOCOL_VERSION = 1
SUPPORTED_VERSIONS = (1,)

# Proof frames carry (height, hash, time) hops, not lanes: 1 MiB covers
# a ~17k-hop chain with room, far past any O(log N) bisection path.
DEFAULT_MAX_FRAME_BYTES = 1 * 1024 * 1024

# --- SyncResponse.status: the shared codes plus ---
STATUS_UPSTREAM_DOWN = 2   # provider unreachable / verification engine failed
STATUS_EXPIRED = 5         # no trusted state fresh enough to prove the target
STATUS_UNTRUSTED = 6       # client's trusted hash conflicts with the spine

STATUS_NAMES = {
    **_SHARED_NAMES,
    STATUS_UPSTREAM_DOWN: "upstream_down",
    STATUS_EXPIRED: "expired",
    STATUS_UNTRUSTED: "untrusted",
}


class Hello(ProtoMessage):
    FIELDS = [
        (1, "version", "uint32"),
        (2, "client_id", "string"),
        (3, "chain_id", "string"),           # "" = accept server's chain
    ]


class HelloAck(ProtoMessage):
    FIELDS = [
        (1, "version", "uint32"),
        (2, "server_id", "string"),
        (3, "chain_id", "string"),
        (4, "anchor_height", "uint64"),      # the daemon's trust anchor…
        (5, "anchor_hash", "bytes"),         # …a cold client can adopt it
        (6, "latest_height", "uint64"),      # top of the verified spine
        (7, "max_frame_bytes", "uint64"),
    ]


class SyncRequest(ProtoMessage):
    FIELDS = [
        (1, "request_id", "uint64"),
        (2, "trusted_height", "uint64"),
        (3, "trusted_hash", "bytes"),
        (4, "target_height", "uint64"),      # 0 = server's latest
        # the client's wall clock, ONLY a skew check: the server
        # refuses bad_request when it strays past max_client_skew_ns,
        # but trust expiry is always judged on the SERVER clock (a
        # client value must never evict shared cache facts). 0 = skip.
        (5, "now_ns", "uint64"),
    ]


class Hop(ProtoMessage):
    """One verified-header fact on the server's bisection path."""

    FIELDS = [
        (1, "height", "uint64"),
        (2, "header_hash", "bytes"),
        (3, "header_time", "int64"),
    ]


class SyncResponse(ProtoMessage):
    FIELDS = [
        (1, "request_id", "uint64"),
        (2, "status", "uint32"),
        (3, "hops", ("rep", ("msg", Hop))),  # ascending, ends at target
        (4, "dispatches", "uint32"),         # device dispatches this answer cost
        (5, "cache_hit", "bool"),            # target served straight from cache
        (6, "dispatch_id", "uint64"),        # joint-resolve identity (0 = inline)
        (7, "coalesced", "uint32"),          # sessions sharing the resolve
        (8, "error", "string"),
    ]


class Pong(ProtoMessage):
    FIELDS = [
        (1, "nonce", "uint64"),
        (2, "latest_height", "uint64"),
        (3, "uptime_ms", "uint64"),
    ]


# type_byte → message class. Wire-visible; never reuse a number.
MESSAGE_TYPES: Dict[int, Type[ProtoMessage]] = {
    1: Hello,
    2: HelloAck,
    3: SyncRequest,
    4: SyncResponse,
    5: Ping,
    6: Pong,
    7: StatsRequest,
    8: StatsResponse,
    9: ErrorReply,
    10: Hop,
}

CODEC = Codec(MESSAGE_TYPES, DEFAULT_MAX_FRAME_BYTES)
TYPE_BYTES = CODEC.type_bytes
encode_frame = CODEC.encode_frame
decode_frame = CODEC.decode_frame
write_frame = CODEC.write_frame
FrameReader = CODEC.reader

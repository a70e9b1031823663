"""The frame every framed daemon speaks, and what its namespaces share.

Framing (reuses :mod:`tmtpu.libs.protoio` primitives):

    frame   = uvarint(len(body)) || body
    body    = type_byte || payload
    payload = protobuf encoding of the message class for type_byte

One byte of type tag inside the length prefix keeps the stream
self-describing without a wrapper message, and lets the reader reject
unknown or oversized frames before decoding a single field. Both sides
enforce a ``max_frame_bytes`` cap, so a corrupt length prefix cannot
OOM either of them.

A namespace is one daemon's ``MESSAGE_TYPES`` table (type byte → class);
its :class:`Codec` encodes and decodes against that table only, so two
daemons share this codec without sharing a wire namespace. Type bytes
1, 2, 5, 6, 7, 8 and 9 mean the same thing in every namespace: Hello,
HelloAck, Ping, Pong, StatsRequest, StatsResponse and ErrorReply (the
handshake is :mod:`tmtpu.libs.framed.server`'s).
"""

from __future__ import annotations

import io
from typing import Dict, Optional, Tuple, Type

from tmtpu.libs.protoio import (  # noqa: F401 — encode_uvarint re-exported
    DelimitedReader,
    ProtoMessage,
    encode_uvarint,
)

# --- response status codes every namespace shares (each adds its own) ---
STATUS_OK = 0
STATUS_OVERLOADED = 1      # admission control rejected; retry or fall back
STATUS_BAD_REQUEST = 3     # malformed or unanswerable request
STATUS_SHUTTING_DOWN = 4   # daemon stopped before the work ran

STATUS_NAMES = {
    STATUS_OK: "ok",
    STATUS_OVERLOADED: "overloaded",
    STATUS_BAD_REQUEST: "bad_request",
    STATUS_SHUTTING_DOWN: "shutting_down",
}

# --- ErrorReply.code ---
ERR_VERSION = 1        # Hello.version not in SUPPORTED_VERSIONS
ERR_PROTOCOL = 2       # bad frame / unexpected message sequence
ERR_INTERNAL = 3       # server bug; connection stays usable


class Ping(ProtoMessage):
    FIELDS = [(1, "nonce", "uint64")]


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


class ProtocolError(Exception):
    """Raised on malformed frames, unknown types, or bad sequencing."""


class FrameReader:
    """Reads framed messages from a binary stream, enforcing the frame cap.

    Thin veneer over :class:`protoio.DelimitedReader`; EOF mid-frame
    surfaces as ``EOFError`` (peer went away), anything else malformed as
    :class:`ProtocolError` so the connection loop can answer
    ``ERR_PROTOCOL`` before closing.
    """

    def __init__(self, stream, codec: "Codec",
                 max_frame_bytes: Optional[int] = None):
        self._rd = DelimitedReader(
            stream, max_size=max_frame_bytes or codec.max_frame_bytes)
        self.decode = codec.decode_frame

    def read_body(self) -> bytes:
        """Block until one whole frame body has arrived."""
        try:
            return self._rd.read_msg()
        except ValueError as exc:  # oversized frame / runaway varint
            raise ProtocolError(str(exc)) from exc

    def read_msg(self) -> ProtoMessage:
        return self.decode(self.read_body())


class Codec:
    """One namespace's frame codec: its type-byte table and frame cap."""

    def __init__(self, message_types: Dict[int, Type[ProtoMessage]],
                 max_frame_bytes: int):
        self.message_types = message_types
        self.type_bytes: Dict[Type[ProtoMessage], int] = {
            cls: tb for tb, cls in message_types.items()}
        self.max_frame_bytes = max_frame_bytes

    def encode_frame(self, msg: ProtoMessage) -> bytes:
        tb = self.type_bytes.get(type(msg))
        if tb is None:
            raise ProtocolError(
                f"unregistered message type {type(msg).__name__}")
        body = bytes([tb]) + msg.encode()
        return encode_uvarint(len(body)) + body

    def decode_frame(self, body: bytes) -> ProtoMessage:
        """Decode one frame *body* (type byte + payload, length prefix
        already stripped)."""
        if not body:
            raise ProtocolError("empty frame")
        cls = self.message_types.get(body[0])
        if cls is None:
            raise ProtocolError(f"unknown message type {body[0]}")
        try:
            return cls.decode(body[1:])
        except (EOFError, ValueError, UnicodeDecodeError) as exc:
            raise ProtocolError(
                f"malformed {cls.__name__} payload: {exc}") from exc

    def write_frame(self, stream: io.RawIOBase, msg: ProtoMessage) -> None:
        stream.write(self.encode_frame(msg))
        flush = getattr(stream, "flush", None)
        if flush is not None:
            flush()

    def reader(self, stream,
               max_frame_bytes: Optional[int] = None) -> FrameReader:
        """A :class:`FrameReader` bound to this namespace; the cap
        defaults to the namespace's own."""
        return FrameReader(stream, self, max_frame_bytes)


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
        f"daemon address must be unix:// or tcp://, got {addr!r}")

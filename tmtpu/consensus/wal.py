"""Write-ahead log (reference: consensus/wal.go).

Every consensus message is appended (fsync'd for our own messages) BEFORE
processing, so a crashed node replays to exactly where it left off. Record
format: crc32(payload) | uvarint len | payload, where payload is a
WALMessage proto envelope. #ENDHEIGHT markers (EndHeightMessage) delimit
heights for SearchForEndHeight (:231), like the reference.

Crash hardening (docs/RESILIENCE.md): a crash mid-append leaves a *torn*
record — one whose header or payload extends past EOF. That is the
expected signature of power loss, never evidence of bad data, so opening
a WAL auto-truncates a torn tail (``repair_torn_tail``, counted in
``tendermint_wal_torn_tail_truncated_total``) and iteration stops there
silently even in strict mode. *Corruption* — a COMPLETE record whose CRC
mismatches, whose payload fails to decode, or whose declared length is
absurd — can only come from bit rot or a software bug; strict mode
(the replay path) raises ``CorruptedWALError`` for it, and non-strict
iteration stops and reports the skip through the ``status`` dict
(bytes counted in ``tendermint_wal_replay_skipped_bytes_total``).
"""

from __future__ import annotations

import os
import struct
import threading
import time
import zlib
from typing import Callable, Iterator, Optional, Sequence

from tmtpu.libs import faultinject, protoio
from tmtpu.libs import metrics as _m
from tmtpu.types import pb
from tmtpu.types.block import BlockID
from tmtpu.types.vote import Vote

# chaos site on the append path: an injected crash here models power
# loss mid-write, the exact scenario repair_torn_tail exists for
_FAULT_WAL_WRITE = faultinject.register("wal.write")

# a declared payload length beyond this is corruption, not a big record
# (the WAL rotates at 10 MB, so no legitimate record approaches it)
_MAX_RECORD_BYTES = 10 * 1024 * 1024


class TimeoutInfoPB(pb.ProtoMessage):
    FIELDS = [
        (1, "duration_ns", "int64"),
        (2, "height", "int64"),
        (3, "round", "int32"),
        (4, "step", "int32"),
    ]


class MsgInfoPB(pb.ProtoMessage):
    """A peer/internal consensus message: exactly one payload set."""

    FIELDS = [
        (1, "peer_id", "string"),
        (2, "proposal", ("msg", pb.Proposal)),
        (3, "block_part_height", "int64"),
        (4, "block_part_round", "int32"),
        (5, "block_part", ("msg", pb.Part)),
        (6, "vote", ("msg", pb.Vote)),
    ]


class EndHeightPB(pb.ProtoMessage):
    FIELDS = [(1, "height", "int64")]


class EventRoundStatePB(pb.ProtoMessage):
    FIELDS = [(1, "height", "int64"), (2, "round", "int32"),
              (3, "step", "string")]


class WALMessagePB(pb.ProtoMessage):
    FIELDS = [
        (1, "time", ("msg!", pb.Timestamp)),
        (2, "end_height", ("msg", EndHeightPB)),
        (3, "msg_info", ("msg", MsgInfoPB)),
        (4, "timeout", ("msg", TimeoutInfoPB)),
        (5, "event_round_state", ("msg", EventRoundStatePB)),
    ]


class CorruptedWALError(Exception):
    pass


_CRC = struct.Struct(">I")
_TAG_TIME = protoio.tag(1, protoio.WIRE_BYTES)          # WALMessagePB
_TAG_MSG_INFO = protoio.tag(3, protoio.WIRE_BYTES)
_TAG_VOTE = protoio.tag(6, protoio.WIRE_BYTES)          # MsgInfoPB
_TAG_SECONDS = protoio.tag(1, protoio.WIRE_VARINT)      # pb.Timestamp
_TAG_NANOS = protoio.tag(2, protoio.WIRE_VARINT)
_TAG_ADDRESS = protoio.tag(6, protoio.WIRE_BYTES)       # pb.Vote
_TAG_INDEX = protoio.tag(7, protoio.WIRE_VARINT)
_TAG_SIGNATURE = protoio.tag(8, protoio.WIRE_BYTES)


def _uvarint(n: int) -> bytes:
    """``protoio.encode_uvarint``, the values of up to five bytes (a vote
    record's length prefixes, a validator's index, a time's seconds and
    nanos) without its loop."""
    if n < 0x80:
        if n >= 0:
            return bytes((n,))
    elif n < 0x4000:
        return bytes((n & 0x7F | 0x80, n >> 7))
    elif n < 0x800000000:
        if n >= 0x10000000:
            return bytes((n & 0x7F | 0x80, n >> 7 & 0x7F | 0x80,
                          n >> 14 & 0x7F | 0x80, n >> 21 & 0x7F | 0x80,
                          n >> 28))
        if n >= 0x200000:
            return bytes((n & 0x7F | 0x80, n >> 7 & 0x7F | 0x80,
                          n >> 14 & 0x7F | 0x80, n >> 21))
        return bytes((n & 0x7F | 0x80, n >> 7 & 0x7F | 0x80, n >> 14))
    return protoio.encode_uvarint(n)


def _timestamp_body(ns: int) -> bytes:
    """``pb.Timestamp.from_unix_nanos(ns).encode()``: seconds and nanos
    split as it splits them, each left out when zero."""
    seconds, nanos = divmod(ns, 1_000_000_000)
    body = b""
    if seconds:
        body = _TAG_SECONDS + (_uvarint(seconds) if seconds > 0
                               else protoio.encode_varint(seconds))
    if nanos:
        body += _TAG_NANOS + _uvarint(nanos)
    return body


def vote_record_template(peer_id: str, type: int, height: int, round: int,
                         block_id: BlockID) -> Callable[[int, Vote], bytes]:
    """The WAL payload of every vote that shares (peer_id, type, height,
    round, block_id), as a function of the record's time (unix nanos) and
    the vote: byte for byte ``WALMessagePB(time=..., msg_info=MsgInfoPB(
    peer_id=..., vote=vote.to_proto())).encode()`` of such a vote, without
    the seven nested messages built and walked per vote.

    What the group shares -- the peer id, the vote's fields 1-4 up to its
    timestamp's tag -- is encoded once, by the reflective encoder, so what
    proto3 leaves off the wire (an empty peer id, round 0, a nil block id)
    stays its business. Per vote only what varies is written by hand, from
    the decoded ``Vote``'s fields and not from the bytes a peer sent (the
    WAL holds the canonical encoding whatever the peer's was): the two
    Timestamp bodies, the validator's address and index and the signature
    (each left out when empty or zero), and the length prefixes that move
    with them."""
    # MsgInfoPB up to the vote's length; pb.Vote up to its timestamp's
    # length (an empty ``msg!`` timestamp is written as tag, 0: keep the
    # tag)
    info_head = MsgInfoPB(peer_id=peer_id).encode() + _TAG_VOTE
    vote_head = pb.Vote(type=type, height=height, round=round,
                        block_id=block_id.to_proto()).encode()[:-1]
    join = b"".join

    def payload(now_ns: int, vote: Vote) -> bytes:
        ts = _timestamp_body(vote.timestamp)
        parts = [vote_head, _uvarint(len(ts)), ts]
        if vote.validator_address:
            parts += (_TAG_ADDRESS, _uvarint(len(vote.validator_address)),
                      vote.validator_address)
        index = vote.validator_index
        if index:
            parts += (_TAG_INDEX, _uvarint(index) if index > 0
                      else protoio.encode_varint(index))
        if vote.signature:
            parts += (_TAG_SIGNATURE, _uvarint(len(vote.signature)),
                      vote.signature)
        body = join(parts)
        n_body = _uvarint(len(body))
        now = _timestamp_body(now_ns)
        return join((
            _TAG_TIME, _uvarint(len(now)), now, _TAG_MSG_INFO,
            _uvarint(len(info_head) + len(n_body) + len(body)),
            info_head, n_body, body))

    return payload


class WAL:
    """consensus/wal.go:58 WAL interface: Write / WriteSync /
    FlushAndSync / SearchForEndHeight.

    Rotation (libs/autofile/group.go): when the head file exceeds
    ``head_size_limit`` it is renamed to ``<path>.NNN`` and a fresh head
    opened; at most ``max_group_files`` rotated files are kept (oldest
    pruned), bounding disk usage for long-running nodes. Readers iterate
    the rotated files in order, then the head.
    """

    # autofile/group.go defaultHeadSizeLimit = 10MB; we keep ~1GB total
    DEFAULT_HEAD_SIZE_LIMIT = 10 * 1024 * 1024
    DEFAULT_MAX_GROUP_FILES = 100

    def __init__(self, path: str,
                 head_size_limit: int = DEFAULT_HEAD_SIZE_LIMIT,
                 max_group_files: int = DEFAULT_MAX_GROUP_FILES):
        self.path = path
        self.head_size_limit = head_size_limit
        self.max_group_files = max_group_files
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # a crash mid-append leaves a torn trailing record; appending
        # after it would bury the tear mid-file where it reads as
        # corruption, so the tail is repaired BEFORE reopening for append
        self.repair_torn_tail(path)
        self._f = open(path, "ab")
        self._lock = threading.Lock()

    @staticmethod
    def repair_torn_tail(path: str) -> int:
        """Truncate an incomplete trailing record (crash mid-append).
        Returns the number of bytes dropped (0 when the file is clean,
        absent, or ends in real corruption — a COMPLETE record with a
        CRC/decode problem is never touched: strict replay must still be
        able to surface it as CorruptedWALError)."""
        try:
            with open(path, "rb") as f:
                data = f.read()
        except FileNotFoundError:
            return 0
        pos, n, good = 0, len(data), 0
        while pos < n:
            if n - pos < 5:
                break  # torn header
            (crc,) = struct.unpack_from(">I", data, pos)
            hdr = pos + 4
            try:
                length, body = protoio.decode_uvarint(data, hdr)
            except EOFError:
                break  # torn length varint
            except ValueError:
                return 0  # malformed varint: corruption, not a tear
            if length > _MAX_RECORD_BYTES:
                return 0  # corruption (absurd length), not a tear
            if n - body < length:
                break  # torn payload
            if zlib.crc32(data[body:body + length]) != crc:
                return 0  # mid-file corruption: leave for strict replay
            pos = body + length
            good = pos
        dropped = n - good
        if dropped == 0:
            return 0
        with open(path, "r+b") as f:
            f.truncate(good)
        _m.wal_torn_tail_truncated.inc()
        return dropped

    @staticmethod
    def _group_files(path: str):
        """Rotated files (sorted by index) for a WAL path."""
        d = os.path.dirname(path) or "."
        base = os.path.basename(path)
        out = []
        try:
            names = os.listdir(d)
        except FileNotFoundError:
            return []
        for name in names:
            if name.startswith(base + "."):
                suffix = name[len(base) + 1:]
                if suffix.isdigit():
                    out.append((int(suffix), os.path.join(d, name)))
        return [p for _, p in sorted(out)]

    def _maybe_rotate_locked(self) -> None:
        if self._f.tell() < self.head_size_limit:
            return
        self._f.flush()
        os.fsync(self._f.fileno())
        self._f.close()
        group = self._group_files(self.path)
        next_idx = 0
        if group:
            next_idx = int(group[-1].rsplit(".", 1)[1]) + 1
        os.replace(self.path, f"{self.path}.{next_idx:03d}")
        # prune oldest beyond the cap
        group = self._group_files(self.path)
        for p in group[:max(0, len(group) - self.max_group_files)]:
            try:
                os.unlink(p)
            except OSError:
                pass
        self._f = open(self.path, "ab")

    def write(self, msg: WALMessagePB) -> None:
        """One record by the reflective encoder (every kind but a drain's
        votes: state.py ``_wal_write_msgs``)."""
        _m.consensus_wal_records.inc(path="reflective")
        self.write_records((msg.encode(),))

    def write_records(self, payloads: Sequence[bytes]) -> None:
        """A run of records (encoded WALMessagePB payloads), in order: a
        CRC and a length header each, joined, one lock, one file write,
        one rotation check -- so the head rotates at a record boundary and
        may pass ``head_size_limit`` by one run (Go's autofile group checks
        its limit on a ticker, not per record). The chaos site fires once
        a record; a fault at record k leaves the records before k handed
        to the file."""
        chunks = []
        try:
            for payload in payloads:
                faultinject.fire(_FAULT_WAL_WRITE)
                chunks += (_CRC.pack(zlib.crc32(payload)),
                           _uvarint(len(payload)), payload)
        finally:
            if chunks:
                with self._lock:
                    self._f.write(b"".join(chunks))
                    self._maybe_rotate_locked()
                _m.consensus_wal_appends.inc()

    def write_sync(self, msg: WALMessagePB) -> None:
        self.write(msg)
        self.flush_and_sync()

    def flush_and_sync(self) -> None:
        with self._lock:
            self._f.flush()
            os.fsync(self._f.fileno())

    def close(self) -> None:
        with self._lock:
            try:
                self._f.flush()
                os.fsync(self._f.fileno())
            finally:
                self._f.close()

    # -- helpers to build messages -----------------------------------------

    @staticmethod
    def make(now_ns: Optional[int] = None, **kw) -> WALMessagePB:
        return WALMessagePB(
            time=pb.Timestamp.from_unix_nanos(now_ns or time.time_ns()), **kw
        )

    def write_end_height(self, height: int) -> None:
        self.write_sync(self.make(end_height=EndHeightPB(height=height)))

    # -- reading ------------------------------------------------------------

    @classmethod
    def iter_messages(cls, path: str, strict: bool = False,
                      status: Optional[dict] = None
                      ) -> Iterator[WALMessagePB]:
        """Decode records across the whole group (rotated files in order,
        then the head). A torn record in the HEAD terminates iteration
        (crash tolerance); a torn or corrupt record in a ROTATED file
        stops the whole group there — yielding later files would hand
        replay a stream with a silent gap.

        Tear vs corruption: a record extending past EOF is a TEAR (crash
        signature — stop silently, never raise); a complete record with
        a CRC mismatch, undecodable payload, or absurd length is
        CORRUPTION (strict raises CorruptedWALError).

        ``status``, when passed, is filled with the aggregate replay
        report: ``records`` yielded, ``clean`` (no skip anywhere),
        ``skipped_bytes``, and ``skips`` — a list of
        ``{file, offset, reason}`` entries naming exactly where and why
        iteration stopped early."""
        if status is None:
            status = {}
        status.update(records=0, clean=True, skipped_bytes=0, skips=[])
        for p in cls._group_files(path):
            one: dict = {}
            yield from cls._iter_one(p, strict, one, agg=status)
            if not one.get("clean"):
                return
        yield from cls._iter_one(path, strict, agg=status)

    @staticmethod
    def _iter_one(path: str, strict: bool = False, status: dict = None,
                  agg: dict = None) -> Iterator[WALMessagePB]:
        if status is None:
            status = {}

        def skip(offset: int, reason: str, nbytes: int) -> None:
            if agg is not None:
                agg["clean"] = False
                agg["skipped_bytes"] += nbytes
                agg["skips"].append(
                    {"file": path, "offset": offset, "reason": reason})
            if nbytes > 0:
                _m.wal_skipped_bytes.inc(nbytes)

        try:
            f = open(path, "rb")
        except FileNotFoundError:
            status["clean"] = True  # absent file: nothing to miss
            return
        with f:
            data = f.read()
        pos = 0
        n = len(data)
        while pos < n:
            start = pos
            if n - pos < 5:
                skip(start, "torn-header", n - start)
                return  # tear: never strict-raise
            (crc,) = struct.unpack_from(">I", data, pos)
            pos += 4
            try:
                length, pos = protoio.decode_uvarint(data, pos)
            except EOFError:
                skip(start, "torn-length", n - start)
                return  # varint ran off EOF: tear
            except ValueError as e:
                # varint malformed with bytes still available:
                # corruption, not a tear
                skip(start, "bad-length-varint", n - start)
                if strict:
                    raise CorruptedWALError(
                        f"bad length varint at offset {start}") from e
                return
            if length > _MAX_RECORD_BYTES:
                skip(start, "oversize-length", n - start)
                if strict:
                    raise CorruptedWALError(
                        f"absurd record length {length} at offset {start}")
                return
            if n - pos < length:
                skip(start, "torn-payload", n - start)
                return  # tear: the record never finished hitting disk
            payload = data[pos:pos + length]
            pos += length
            if zlib.crc32(payload) != crc:
                skip(start, "crc-mismatch", n - start)
                if strict:
                    raise CorruptedWALError(
                        f"crc mismatch at offset {start}")
                return
            try:
                msg = WALMessagePB.decode(payload)
            except Exception as e:
                skip(start, "decode-error", n - start)
                if strict:
                    raise CorruptedWALError(str(e)) from e
                return
            if agg is not None:
                agg["records"] += 1
            yield msg
        status["clean"] = True

    @classmethod
    def search_for_end_height(cls, path: str, height: int
                              ) -> Optional[int]:
        """wal.go:231 — index (message ordinal) just after #ENDHEIGHT for
        ``height``, or None."""
        found = None
        for i, msg in enumerate(cls.iter_messages(path)):
            if msg.end_height is not None and msg.end_height.height == height:
                found = i + 1
        return found

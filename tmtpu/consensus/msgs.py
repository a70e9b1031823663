"""Consensus wire messages (reference: proto/tendermint/consensus/types.proto
+ consensus/msgs.go) — field numbers match the reference."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from tmtpu.libs import protoio
from tmtpu.libs.protoio import ProtoMessage
from tmtpu.types import pb
from tmtpu.types.block import BlockID
from tmtpu.types.vote import Vote

_decode_varint = protoio.decode_varint
_HEAD_VARINT_TAGS = (0x08, 0x10, 0x18)     # pb.Vote's type, height, round


class NewRoundStepPB(ProtoMessage):
    FIELDS = [
        (1, "height", "int64"),
        (2, "round", "int32"),
        (3, "step", "uint32"),
        (4, "seconds_since_start_time", "int64"),
        (5, "last_commit_round", "int32"),
    ]


class NewValidBlockPB(ProtoMessage):
    FIELDS = [
        (1, "height", "int64"),
        (2, "round", "int32"),
        (3, "block_part_set_header", ("msg!", pb.PartSetHeader)),
        (4, "block_parts", "bytes"),  # LE u32 bit-count + packed u64 words
        (5, "is_commit", "bool"),
    ]


class ProposalPB(ProtoMessage):
    FIELDS = [(1, "proposal", ("msg!", pb.Proposal))]


class ProposalPOLPB(ProtoMessage):
    FIELDS = [
        (1, "height", "int64"),
        (2, "proposal_pol_round", "int32"),
        (3, "proposal_pol", "bytes"),
    ]


class BlockPartPB(ProtoMessage):
    FIELDS = [
        (1, "height", "int64"),
        (2, "round", "int32"),
        (3, "part", ("msg!", pb.Part)),
    ]


class VotePB(ProtoMessage):
    FIELDS = [(1, "vote", ("msg!", pb.Vote))]


class HasVotePB(ProtoMessage):
    FIELDS = [
        (1, "height", "int64"),
        (2, "round", "int32"),
        (3, "type", "enum"),
        (4, "index", "int32"),
    ]


class VoteSetMaj23PB(ProtoMessage):
    FIELDS = [
        (1, "height", "int64"),
        (2, "round", "int32"),
        (3, "type", "enum"),
        (4, "block_id", ("msg!", pb.BlockID)),
    ]


class VoteSetBitsPB(ProtoMessage):
    FIELDS = [
        (1, "height", "int64"),
        (2, "round", "int32"),
        (3, "type", "enum"),
        (4, "block_id", ("msg!", pb.BlockID)),
        (5, "votes", "bytes"),
    ]


class ConsensusMessagePB(ProtoMessage):
    """The channel envelope (oneof)."""

    # field 10 is NOT part of the oneof: an optional piggybacked trace
    # context (libs/trace.py wire form). Old peers skip the unknown
    # field; empty bytes are omitted on encode, so untraced envelopes
    # are byte-identical to pre-tracing builds.
    _ONEOF = [
        (1, "new_round_step", ("msg", NewRoundStepPB)),
        (2, "new_valid_block", ("msg", NewValidBlockPB)),
        (3, "proposal", ("msg", ProposalPB)),
        (4, "proposal_pol", ("msg", ProposalPOLPB)),
        (5, "block_part", ("msg", BlockPartPB)),
        (6, "vote", ("msg", VotePB)),
        (7, "has_vote", ("msg", HasVotePB)),
        (8, "vote_set_maj23", ("msg", VoteSetMaj23PB)),
        (9, "vote_set_bits", ("msg", VoteSetBitsPB)),
    ]
    FIELDS = _ONEOF + [(10, "trace_ctx", "bytes")]

    def which(self) -> str:
        for _, name, _s in self._ONEOF:
            if getattr(self, name) is not None:
                return name
        return ""


class VoteDecoder:
    """The vote channel's one frequent message, decoded by hand: an envelope
    whose field 6 (``VotePB``) holds field 1 (``pb.Vote``) and nothing else,
    the vote's fields in canonical order. ``decode(buf)`` is the ``Vote``
    that ``Vote.from_proto(ConsensusMessagePB.decode(buf).vote.vote)``
    gives, or None for "not mine": another oneof arm, ``trace_ctx``, an
    unknown, repeated or misplaced field, a length that does not cover
    exactly the rest, a truncation, an over-long varint. Never a guess: what
    is not mine is left to the reflective decoder, whose result or
    exception stands, so 20,000 votes a height do not each build fourteen
    ``ProtoMessage`` objects.

    The vote's fields 1-4 up to its timestamp's tag -- type, height, round,
    block id, shared by every vote of a step -- are *the head*. A head is
    decoded once, by the reflective ``pb.Vote.decode`` + ``BlockID.from_proto``
    (what proto3 leaves off the wire, round 0 or a nil block id, stays their
    business), and kept by its bytes in ``heads``, at most ``MAX_HEADS`` of
    them: a peer that sends a new head a message clears the table, it does
    not grow it. The votes of a head share one ``BlockID`` object: it has
    ``__slots__`` and nothing under ``tmtpu/`` assigns to a vote's
    ``block_id.hash``, ``.parts_total`` or ``.parts_hash``. Per vote only
    what varies is parsed: the Timestamp's body, the validator's address and
    index, the signature, each optional, in order, at most once."""

    MAX_HEADS = 32
    __slots__ = ("heads",)

    def __init__(self):
        self.heads: Dict[bytes, Tuple[int, int, int, BlockID]] = {}

    def decode(self, buf: bytes) -> Optional[Vote]:
        try:
            return self._decode(buf)
        except (IndexError, ValueError, EOFError):
            return None     # ran off the end, or a varint protoio refuses

    def _decode(self, buf: bytes) -> Optional[Vote]:
        if type(buf) is not bytes or buf[0] != 0x32:    # envelope field 6
            return None
        n = len(buf)
        # the two length prefixes, one or two bytes each (a vote is some
        # 190 bytes), each covering exactly the rest
        pos = 2
        ln = buf[1]
        if ln & 0x80:
            if buf[2] & 0x80:
                return None
            ln = (ln & 0x7F) | (buf[2] << 7)
            pos = 3
        if pos + ln != n or buf[pos] != 0x0A:           # VotePB field 1
            return None
        ln = buf[pos + 1]
        pos += 2
        if ln & 0x80:
            if buf[pos] & 0x80:
                return None
            ln = (ln & 0x7F) | (buf[pos] << 7)
            pos += 1
        if pos + ln != n:
            return None
        # the head: tags 08 10 18 22, ascending, each at most once, then 2a
        start = pos
        tag = buf[pos]
        for varint_tag in _HEAD_VARINT_TAGS:
            if tag == varint_tag:
                pos += 1
                while buf[pos] & 0x80:
                    pos += 1
                pos += 1
                tag = buf[pos]
        if tag == 0x22:
            ln = buf[pos + 1]
            if ln & 0x80:
                return None
            pos += 2 + ln
            tag = buf[pos]
        if tag != 0x2A:
            return None
        head = buf[start:pos]
        shared = self.heads.get(head)
        if shared is None:
            shared = self._learn(head)
        # the Timestamp: seconds, nanos, either absent
        ln = buf[pos + 1]
        end = pos + 2 + ln
        if ln & 0x80 or end > n:
            return None
        pos += 2
        seconds = nanos = 0
        if pos < end and buf[pos] == 0x08:
            seconds, pos = _decode_varint(buf, pos + 1)
        if pos < end and buf[pos] == 0x10:
            nanos, pos = _decode_varint(buf, pos + 1)
        if pos != end:
            return None
        address = signature = b""
        index = 0
        if pos < n and buf[pos] == 0x32:
            ln = buf[pos + 1]
            end = pos + 2 + ln
            if ln & 0x80 or end > n:
                return None
            address = buf[pos + 2:end]
            pos = end
        if pos < n and buf[pos] == 0x38:
            index, pos = _decode_varint(buf, pos + 1)
        if pos < n and buf[pos] == 0x42:
            ln = buf[pos + 1]
            end = pos + 2 + ln
            if ln & 0x80 or end > n:
                return None
            signature = buf[pos + 2:end]
            pos = end
        if pos != n:
            return None
        return Vote(shared[0], shared[1], shared[2], shared[3],
                    seconds * 1_000_000_000 + nanos, address, index,
                    signature)

    def _learn(self, head: bytes) -> Tuple[int, int, int, BlockID]:
        m = pb.Vote.decode(head)
        shared = (m.type, m.height, m.round, BlockID.from_proto(m.block_id))
        if len(self.heads) >= self.MAX_HEADS:
            self.heads.clear()
        self.heads[head] = shared
        return shared


"""Vote and Proposal (reference: types/vote.go, types/proposal.go,
types/canonical.go).

``sign_bytes`` is the consensus-critical byte string: the uvarint-length-
delimited proto encoding of the CanonicalVote/CanonicalProposal
(types/vote.go:93, types/proposal.go:73).
"""

from __future__ import annotations

from typing import Callable, Optional

from tmtpu.libs import protoio
from tmtpu.types import pb
from tmtpu.types.block import BlockID

PREVOTE = pb.SIGNED_MSG_TYPE_PREVOTE
PRECOMMIT = pb.SIGNED_MSG_TYPE_PRECOMMIT
PROPOSAL_TYPE = pb.SIGNED_MSG_TYPE_PROPOSAL

MAX_VOTES_COUNT = 10000  # types/vote_set.go:18


def is_vote_type_valid(t: int) -> bool:
    return t in (PREVOTE, PRECOMMIT)


def canonicalize_vote(chain_id: str, type: int, height: int, round: int,
                      block_id: BlockID, timestamp: int) -> pb.CanonicalVote:
    """types/canonical.go:56 CanonicalizeVote. round widens to int64
    (sfixed64); nil block ids become a nil field."""
    return pb.CanonicalVote(
        type=type, height=height, round=round,
        block_id=block_id.to_canonical(),
        timestamp=pb.Timestamp.from_unix_nanos(timestamp),
        chain_id=chain_id,
    )


# CanonicalVote's timestamp is a ``msg!`` field: written also when empty, as
# its tag and a zero length (2a 00).
_EMPTY_TIMESTAMP_FIELD = pb.CanonicalVote().encode()


def vote_sign_bytes_template(chain_id: str, type: int, height: int,
                             round: int, block_id: BlockID
                             ) -> Callable[[int], bytes]:
    """The sign bytes of every vote that shares (chain_id, type, height,
    round, block_id), as a function of the one thing left to vary, the
    timestamp (unix nanos): byte for byte ``Vote.sign_bytes`` of such a
    vote, without a CanonicalVote built and encoded per vote. Its callers:
    ``Commit.vote_sign_bytes_for`` (a commit's precommits) and
    ``VoteSet.add_votes`` (a vote set's flush, one template a block id).

    Fields 1-4 (up to the timestamp's tag) and field 6 (chain_id) are
    encoded once, by the encoder above, so what proto3 leaves off the wire
    -- height 0, round 0, a nil block id, an empty chain id -- is its
    business and not repeated here. Per timestamp only the Timestamp body
    is written by hand (seconds and nanos split as
    ``pb.Timestamp.from_unix_nanos`` splits them, each left out when zero),
    then the length prefix, which moves with it."""
    head = canonicalize_vote(chain_id="", type=type, height=height,
                             round=round, block_id=block_id,
                             timestamp=0).encode()
    prefix = head[:-1]  # ends with the empty timestamp: keep its tag
    suffix = pb.CanonicalVote(chain_id=chain_id).encode()[
        len(_EMPTY_TIMESTAMP_FIELD):]
    encode_varint = protoio.encode_varint
    encode_uvarint = protoio.encode_uvarint

    def sign_bytes(timestamp: int) -> bytes:
        seconds, nanos = divmod(timestamp, 1_000_000_000)
        ts = b"\x08" + encode_varint(seconds) if seconds else b""
        if nanos:
            ts += b"\x10" + encode_uvarint(nanos)
        # len(ts) <= 17: one byte
        body = prefix + bytes((len(ts),)) + ts + suffix
        return encode_uvarint(len(body)) + body

    return sign_bytes


class Vote:
    __slots__ = ("type", "height", "round", "block_id", "timestamp",
                 "validator_address", "validator_index", "signature")

    def __init__(self, type: int, height: int, round: int, block_id: BlockID,
                 timestamp: int, validator_address: bytes,
                 validator_index: int, signature: bytes = b""):
        self.type = type
        self.height = int(height)
        self.round = int(round)
        self.block_id = block_id
        self.timestamp = int(timestamp)  # unix nanos
        self.validator_address = bytes(validator_address)
        self.validator_index = int(validator_index)
        self.signature = bytes(signature)

    def sign_bytes(self, chain_id: str) -> bytes:
        """types/vote.go:93 VoteSignBytes."""
        cv = canonicalize_vote(chain_id, self.type, self.height, self.round,
                               self.block_id, self.timestamp)
        return protoio.marshal_delimited(cv.encode())

    def verify(self, chain_id: str, pub_key) -> None:
        """types/vote.go:147 — the serial hot call (the batch path goes
        through crypto.BatchVerifier instead). Cache-aware: a vote the
        batch path already verified costs no crypto here."""
        from tmtpu.crypto import batch as _crypto_batch

        if pub_key.address() != self.validator_address:
            raise VoteError("invalid validator address")
        if not _crypto_batch.verify_one(pub_key, self.sign_bytes(chain_id),
                                        self.signature):
            raise VoteError("invalid signature")

    def validate_basic(self) -> None:
        if not is_vote_type_valid(self.type):
            raise VoteError("invalid Type")
        if self.height < 0:
            raise VoteError("negative Height")
        if self.round < 0:
            raise VoteError("negative Round")
        if not self.block_id.is_zero() and not self.block_id.is_complete():
            raise VoteError("blockID must be either empty or complete")
        if len(self.validator_address) != 20:
            raise VoteError("invalid validator address size")
        if self.validator_index < 0:
            raise VoteError("negative ValidatorIndex")
        if not self.signature:
            raise VoteError("signature is missing")
        if len(self.signature) > 64:
            raise VoteError("signature is too big")

    def is_nil(self) -> bool:
        return self.block_id.is_zero()

    def to_proto(self) -> pb.Vote:
        return pb.Vote(
            type=self.type, height=self.height, round=self.round,
            block_id=self.block_id.to_proto(),
            timestamp=pb.Timestamp.from_unix_nanos(self.timestamp),
            validator_address=self.validator_address,
            validator_index=self.validator_index,
            signature=self.signature,
        )

    @classmethod
    def from_proto(cls, m: pb.Vote) -> "Vote":
        return cls(
            m.type, m.height, m.round, BlockID.from_proto(m.block_id),
            m.timestamp.to_unix_nanos() if m.timestamp else 0,
            bytes(m.validator_address), m.validator_index, bytes(m.signature),
        )

    def __eq__(self, other):
        return (isinstance(other, Vote) and self.type == other.type
                and self.height == other.height and self.round == other.round
                and self.block_id == other.block_id
                and self.timestamp == other.timestamp
                and self.validator_address == other.validator_address
                and self.validator_index == other.validator_index
                and self.signature == other.signature)

    def __repr__(self):
        t = {PREVOTE: "Prevote", PRECOMMIT: "Precommit"}.get(self.type, "?")
        return (f"Vote{{{self.validator_index}:"
                f"{self.validator_address.hex().upper()[:12]} "
                f"{self.height}/{self.round}({t}) "
                f"{self.block_id.hash.hex().upper()[:12]}}}")


class VoteError(Exception):
    pass


class ErrVoteConflictingVotes(VoteError):
    """Equivocation detected while adding a vote (types/vote_set.go:169) —
    carries both votes for the evidence pool."""

    def __init__(self, vote_a: Vote, vote_b: Vote):
        super().__init__("conflicting votes from validator "
                         f"{vote_a.validator_address.hex().upper()}")
        self.vote_a = vote_a
        self.vote_b = vote_b
        # set by VoteSet.add_votes: per-vote added flags for the batch that
        # surfaced the conflict (the batch IS fully processed before raising)
        self.results = None


class Proposal:
    """types/proposal.go — proposed block at (height, round) with POL round
    for re-proposals."""

    __slots__ = ("type", "height", "round", "pol_round", "block_id",
                 "timestamp", "signature")

    def __init__(self, height: int, round: int, pol_round: int,
                 block_id: BlockID, timestamp: int = 0, signature: bytes = b""):
        self.type = PROPOSAL_TYPE
        self.height = int(height)
        self.round = int(round)
        self.pol_round = int(pol_round)
        self.block_id = block_id
        self.timestamp = int(timestamp)
        self.signature = bytes(signature)

    def sign_bytes(self, chain_id: str) -> bytes:
        """types/proposal.go:73 ProposalSignBytes."""
        cp = pb.CanonicalProposal(
            type=self.type, height=self.height, round=self.round,
            pol_round=self.pol_round,
            block_id=self.block_id.to_canonical(),
            timestamp=pb.Timestamp.from_unix_nanos(self.timestamp),
            chain_id=chain_id,
        )
        return protoio.marshal_delimited(cp.encode())

    def validate_basic(self) -> None:
        if self.type != PROPOSAL_TYPE:
            raise VoteError("invalid Type")
        if self.height < 0:
            raise VoteError("negative Height")
        if self.round < 0:
            raise VoteError("negative Round")
        if self.pol_round < -1 or self.pol_round >= self.round:
            raise VoteError("invalid POLRound")
        if not self.block_id.is_complete():
            raise VoteError("expected a complete, non-empty BlockID")
        if not self.signature:
            raise VoteError("signature is missing")
        if len(self.signature) > 64:
            raise VoteError("signature is too big")

    def to_proto(self) -> pb.Proposal:
        return pb.Proposal(
            type=self.type, height=self.height, round=self.round,
            pol_round=self.pol_round, block_id=self.block_id.to_proto(),
            timestamp=pb.Timestamp.from_unix_nanos(self.timestamp),
            signature=self.signature,
        )

    @classmethod
    def from_proto(cls, m: pb.Proposal) -> "Proposal":
        return cls(m.height, m.round, m.pol_round,
                   BlockID.from_proto(m.block_id),
                   m.timestamp.to_unix_nanos() if m.timestamp else 0,
                   bytes(m.signature))

    def __eq__(self, other):
        return (isinstance(other, Proposal) and self.height == other.height
                and self.round == other.round
                and self.pol_round == other.pol_round
                and self.block_id == other.block_id
                and self.timestamp == other.timestamp
                and self.signature == other.signature)

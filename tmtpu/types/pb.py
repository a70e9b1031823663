"""Protobuf wire messages for core types.

Schema mirrors the reference's proto/tendermint/{types,crypto,version}
definitions (proto/tendermint/types/types.proto, canonical.proto,
validator.proto, evidence.proto, params.proto; proto/tendermint/crypto/
keys.proto, proof.proto; proto/tendermint/version/types.proto), encoded with
the deterministic gogo-compatible writer in tmtpu.libs.protoio.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from tmtpu.libs import metrics as _metrics
from tmtpu.libs import protoio
from tmtpu.libs.protoio import ProtoMessage

# --- enums (proto/tendermint/types/types.proto:12-36) ---

BLOCK_ID_FLAG_UNKNOWN = 0
BLOCK_ID_FLAG_ABSENT = 1
BLOCK_ID_FLAG_COMMIT = 2
BLOCK_ID_FLAG_NIL = 3

SIGNED_MSG_TYPE_UNKNOWN = 0
SIGNED_MSG_TYPE_PREVOTE = 1
SIGNED_MSG_TYPE_PRECOMMIT = 2
SIGNED_MSG_TYPE_PROPOSAL = 32

# Go's zero time.Time (0001-01-01T00:00:00Z) in unix seconds.
GO_ZERO_SECONDS = -62135596800
GO_ZERO_NANOS = GO_ZERO_SECONDS * 1_000_000_000


class Timestamp(ProtoMessage):
    """google.protobuf.Timestamp."""

    FIELDS = [(1, "seconds", "int64"), (2, "nanos", "int32")]

    @classmethod
    def from_unix_nanos(cls, ns: int) -> "Timestamp":
        return cls(seconds=ns // 1_000_000_000, nanos=ns % 1_000_000_000)

    def to_unix_nanos(self) -> int:
        return self.seconds * 1_000_000_000 + self.nanos


class Consensus(ProtoMessage):
    """tendermint.version.Consensus."""

    FIELDS = [(1, "block", "uint64"), (2, "app", "uint64")]


class App(ProtoMessage):
    """tendermint.version.App."""

    FIELDS = [(1, "protocol", "uint64"), (2, "software", "string")]


class PublicKey(ProtoMessage):
    """tendermint.crypto.PublicKey (oneof sum: ed25519=1 | secp256k1=2).

    The framework additionally understands sr25519 on field 3 for mixed-curve
    validator sets (an extension; the reference's codec only maps
    ed25519/secp256k1 — crypto/encoding/codec.go:14-63)."""

    FIELDS = [(1, "ed25519", "bytes"), (2, "secp256k1", "bytes"),
              (3, "sr25519", "bytes")]


class Proof(ProtoMessage):
    """tendermint.crypto.Proof."""

    FIELDS = [
        (1, "total", "int64"),
        (2, "index", "int64"),
        (3, "leaf_hash", "bytes"),
        (4, "aunts", ("rep", "bytes")),
    ]


class PartSetHeader(ProtoMessage):
    FIELDS = [(1, "total", "uint32"), (2, "hash", "bytes")]


class BlockID(ProtoMessage):
    FIELDS = [
        (1, "hash", "bytes"),
        (2, "part_set_header", ("msg!", PartSetHeader)),
    ]


class Part(ProtoMessage):
    FIELDS = [
        (1, "index", "uint32"),
        (2, "bytes", "bytes"),
        (3, "proof", ("msg!", Proof)),
    ]


class CanonicalPartSetHeader(ProtoMessage):
    FIELDS = [(1, "total", "uint32"), (2, "hash", "bytes")]


class CanonicalBlockID(ProtoMessage):
    FIELDS = [
        (1, "hash", "bytes"),
        (2, "part_set_header", ("msg!", CanonicalPartSetHeader)),
    ]


class CanonicalVote(ProtoMessage):
    """proto/tendermint/types/canonical.proto:30-38.  height/round are
    sfixed64 for fixed-size canonical encoding; block_id is nullable."""

    FIELDS = [
        (1, "type", "enum"),
        (2, "height", "sfixed64"),
        (3, "round", "sfixed64"),
        (4, "block_id", ("msg", CanonicalBlockID)),
        (5, "timestamp", ("msg!", Timestamp)),
        (6, "chain_id", "string"),
    ]


class CanonicalProposal(ProtoMessage):
    FIELDS = [
        (1, "type", "enum"),
        (2, "height", "sfixed64"),
        (3, "round", "sfixed64"),
        (4, "pol_round", "int64"),
        (5, "block_id", ("msg", CanonicalBlockID)),
        (6, "timestamp", ("msg!", Timestamp)),
        (7, "chain_id", "string"),
    ]


class Vote(ProtoMessage):
    FIELDS = [
        (1, "type", "enum"),
        (2, "height", "int64"),
        (3, "round", "int32"),
        (4, "block_id", ("msg!", BlockID)),
        (5, "timestamp", ("msg!", Timestamp)),
        (6, "validator_address", "bytes"),
        (7, "validator_index", "int32"),
        (8, "signature", "bytes"),
    ]


class Proposal(ProtoMessage):
    FIELDS = [
        (1, "type", "enum"),
        (2, "height", "int64"),
        (3, "round", "int32"),
        (4, "pol_round", "int32"),
        (5, "block_id", ("msg!", BlockID)),
        (6, "timestamp", ("msg!", Timestamp)),
        (7, "signature", "bytes"),
    ]


_count_encode_hand = _metrics.types_commit_codec.bound(
    dir="encode", path="hand")
_count_encode_reflective = _metrics.types_commit_codec.bound(
    dir="encode", path="reflective")
_count_decode_hand = _metrics.types_commit_codec.bound(
    dir="decode", path="hand")
_count_decode_reflective = _metrics.types_commit_codec.bound(
    dir="decode", path="reflective")

_BYTE = [bytes((i,)) for i in range(128)]
_FLAG_FIELD = {0: b"", 1: b"\x08\x01", 2: b"\x08\x02", 3: b"\x08\x03"}


def _uvarint(v: int) -> bytes:
    if v < 0x80:
        return _BYTE[v]
    return protoio.encode_uvarint(v)


def _read_uvarint(buf: bytes, pos: int) -> Tuple[int, int]:
    """A varint at ``pos`` and the position after it; ValueError for one
    that is padded (a last byte of 0) or longer than ten bytes, which the
    reflective decoder reads and the hand decoder leaves to it."""
    if buf[pos] < 0x80:
        return buf[pos], pos + 1
    v, end = protoio.decode_uvarint(buf, pos)
    if buf[end - 1] == 0 or end - pos > 10:
        raise ValueError("not a minimal varint")
    return v, end


def _read_varint(buf: bytes, pos: int) -> Tuple[int, int]:
    """``_read_uvarint`` as int64 reads it (``protoio.decode_varint``)."""
    v, pos = _read_uvarint(buf, pos)
    return (v - (1 << 64) if v >= 1 << 63 else v), pos


class CommitSig(ProtoMessage):
    """Besides the reflective codec, which a ``CommitSig`` object keeps, a
    hand encoder for many at once (``encode_rows``): a Commit's signatures
    and its hash's leaves are written from plain values, with no object
    built per signature."""

    FIELDS = [
        (1, "block_id_flag", "enum"),
        (2, "validator_address", "bytes"),
        (3, "timestamp", ("msg!", Timestamp)),
        (4, "signature", "bytes"),
    ]

    @staticmethod
    def encode_rows(rows) -> List[bytes]:
        """Each row ``(block_id_flag, validator_address, unix nanos,
        signature)`` as the bytes ``encode()`` gives for the CommitSig that
        ``Timestamp.from_unix_nanos`` makes of it: a zero flag and empty
        bytes left out, the Timestamp always written (``1a 00`` when the
        time is 0). A second's varint is made once a call."""
        out = []
        seconds = {}
        for flag, address, ns, signature in rows:
            s, n = divmod(ns, 1_000_000_000)
            ts = seconds.get(s)
            if ts is None:
                ts = seconds[s] = (b"\x08" + protoio.encode_varint(s)
                                   if s else b"")
            if n:
                ts += b"\x10" + _uvarint(n)
            flag_field = _FLAG_FIELD.get(flag)
            if flag_field is None:
                flag_field = b"\x08" + protoio.encode_varint(flag)
            out.append(b"".join((
                flag_field,
                b"\x12" + _uvarint(len(address)) + address
                if address else b"",
                b"\x1a", _BYTE[len(ts)], ts,
                b"\x22" + _uvarint(len(signature)) + signature
                if signature else b"")))
        return out


class Commit(ProtoMessage):
    """A Commit's wire form by hand in both directions, with the reflective
    codec beside it for every other shape.

    ``from_rows`` makes a Commit whose signatures are plain rows
    ``(block_id_flag, validator_address, unix nanos, signature)``;
    ``encode()`` writes those with ``CommitSig.encode_rows``, and the
    ``signatures`` field becomes ``CommitSig`` objects when it is first
    read or set, after which the reflective encoder runs. ``decode`` reads
    the canonical shape into rows -- fields 1 to 4 in order, each scalar
    at most once, every varint minimal, each CommitSig's fields in order
    with its Timestamp present and nanos in [0, 10^9) -- and leaves any
    other input to the reflective decoder, whose result or exception
    stands. Either way the bytes and the fields are the reflective
    codec's. ``types_commit_codec_total{dir,path}`` moves once a Commit."""

    FIELDS = [
        (1, "height", "int64"),
        (2, "round", "int32"),
        (3, "block_id", ("msg!", BlockID)),
        (4, "signatures", ("rep", ("msg!", CommitSig))),
    ]
    _rows = None

    @classmethod
    def from_rows(cls, height: int, round: int, block_id: BlockID,
                  rows: list) -> "Commit":
        m = cls(height=height, round=round, block_id=block_id)
        m._rows = rows
        return m

    @property
    def signatures(self) -> list:
        if self._rows is not None:
            self._signatures = [
                CommitSig(block_id_flag=flag, validator_address=address,
                          timestamp=Timestamp.from_unix_nanos(ns),
                          signature=signature)
                for flag, address, ns, signature in self._rows]
            self._rows = None
        return self._signatures

    @signatures.setter
    def signatures(self, value: list) -> None:
        self._signatures = value
        self._rows = None

    def rows(self) -> list:
        """The signatures as rows, as ``from_rows`` takes them."""
        if self._rows is not None:
            return self._rows
        return [(s.block_id_flag, s.validator_address,
                 s.timestamp.to_unix_nanos() if s.timestamp else 0,
                 s.signature) for s in self._signatures]

    def encode(self) -> bytes:
        if self._rows is None:
            _count_encode_reflective()
            return super().encode()
        _count_encode_hand()
        parts = [b"\x08" + protoio.encode_varint(self.height)
                 if self.height else b"",
                 b"\x10" + protoio.encode_varint(self.round)
                 if self.round else b""]
        block_id = self.block_id.encode() if self.block_id is not None \
            else b""
        parts += (b"\x1a", _uvarint(len(block_id)), block_id)
        for sig in CommitSig.encode_rows(self._rows):
            parts += (b"\x22", _uvarint(len(sig)), sig)
        return b"".join(parts)

    @classmethod
    def decode(cls, buf: bytes) -> "Commit":
        m = None
        if type(buf) is bytes:
            try:
                m = cls._decode_canonical(buf)
            except (IndexError, ValueError, EOFError):
                m = None
        if m is None:
            _count_decode_reflective()
            return super().decode(buf)
        _count_decode_hand()
        return m

    @classmethod
    def _decode_canonical(cls, buf: bytes) -> Optional["Commit"]:
        n = len(buf)
        height = round_ = 0
        pos = 0
        if buf[pos] == 0x08:
            height, pos = _read_varint(buf, pos + 1)
        if pos < n and buf[pos] == 0x10:
            round_, pos = _read_varint(buf, pos + 1)
        if pos >= n or buf[pos] != 0x1A:
            return None
        ln, pos = _read_uvarint(buf, pos + 1)
        end = pos + ln
        if end > n:
            return None
        block_id = BlockID.decode(buf[pos:end])
        pos = end
        rows = []
        append = rows.append
        while pos < n:
            if buf[pos] != 0x22:
                return None
            ln, pos = _read_uvarint(buf, pos + 1)
            end = pos + ln
            if end > n:
                return None
            flag = 0
            address = signature = b""
            if pos < end and buf[pos] == 0x08:
                flag, pos = _read_varint(buf, pos + 1)
            if pos < end and buf[pos] == 0x12:
                ln, pos = _read_uvarint(buf, pos + 1)
                if pos + ln > end:
                    return None
                address = buf[pos:pos + ln]
                pos += ln
            if pos >= end or buf[pos] != 0x1A:
                return None
            ln, pos = _read_uvarint(buf, pos + 1)
            ts_end = pos + ln
            if ts_end > end:
                return None
            seconds = nanos = 0
            if pos < ts_end and buf[pos] == 0x08:
                seconds, pos = _read_varint(buf, pos + 1)
            if pos < ts_end and buf[pos] == 0x10:
                nanos, pos = _read_varint(buf, pos + 1)
            if pos != ts_end or not 0 <= nanos < 1_000_000_000:
                return None
            if pos < end and buf[pos] == 0x22:
                ln, pos = _read_uvarint(buf, pos + 1)
                if pos + ln > end:
                    return None
                signature = buf[pos:pos + ln]
                pos += ln
            if pos != end:
                return None
            append((flag, address, seconds * 1_000_000_000 + nanos,
                    signature))
        return cls.from_rows(height, round_, block_id, rows)


class Header(ProtoMessage):
    FIELDS = [
        (1, "version", ("msg!", Consensus)),
        (2, "chain_id", "string"),
        (3, "height", "int64"),
        (4, "time", ("msg!", Timestamp)),
        (5, "last_block_id", ("msg!", BlockID)),
        (6, "last_commit_hash", "bytes"),
        (7, "data_hash", "bytes"),
        (8, "validators_hash", "bytes"),
        (9, "next_validators_hash", "bytes"),
        (10, "consensus_hash", "bytes"),
        (11, "app_hash", "bytes"),
        (12, "last_results_hash", "bytes"),
        (13, "evidence_hash", "bytes"),
        (14, "proposer_address", "bytes"),
    ]


class Data(ProtoMessage):
    FIELDS = [(1, "txs", ("rep", "bytes"))]


class Validator(ProtoMessage):
    FIELDS = [
        (1, "address", "bytes"),
        (2, "pub_key", ("msg!", PublicKey)),
        (3, "voting_power", "int64"),
        (4, "proposer_priority", "int64"),
    ]


class ValidatorSet(ProtoMessage):
    FIELDS = [
        (1, "validators", ("rep", ("msg!", Validator))),
        (2, "proposer", ("msg", Validator)),
        (3, "total_voting_power", "int64"),
    ]


class SimpleValidator(ProtoMessage):
    """Hash input for ValidatorSet.Hash (types/validator.go:117-133);
    pub_key is nullable here."""

    FIELDS = [
        (1, "pub_key", ("msg", PublicKey)),
        (2, "voting_power", "int64"),
    ]


# --- evidence (proto/tendermint/types/evidence.proto) ---


class LightBlockPB(ProtoMessage):
    FIELDS: list = []  # filled in below (forward refs)


class DuplicateVoteEvidence(ProtoMessage):
    FIELDS = [
        (1, "vote_a", ("msg", Vote)),
        (2, "vote_b", ("msg", Vote)),
        (3, "total_voting_power", "int64"),
        (4, "validator_power", "int64"),
        (5, "timestamp", ("msg!", Timestamp)),
    ]


class SignedHeader(ProtoMessage):
    FIELDS = [
        (1, "header", ("msg", Header)),
        (2, "commit", ("msg", Commit)),
    ]


class LightBlock(ProtoMessage):
    FIELDS = [
        (1, "signed_header", ("msg", SignedHeader)),
        (2, "validator_set", ("msg", ValidatorSet)),
    ]


class LightClientAttackEvidence(ProtoMessage):
    FIELDS = [
        (1, "conflicting_block", ("msg", LightBlock)),
        (2, "common_height", "int64"),
        (3, "byzantine_validators", ("rep", ("msg!", Validator))),
        (4, "total_voting_power", "int64"),
        (5, "timestamp", ("msg!", Timestamp)),
    ]


class Evidence(ProtoMessage):
    """oneof sum: duplicate_vote_evidence=1 | light_client_attack_evidence=2."""

    FIELDS = [
        (1, "duplicate_vote_evidence", ("msg", DuplicateVoteEvidence)),
        (2, "light_client_attack_evidence", ("msg", LightClientAttackEvidence)),
    ]


class EvidenceList(ProtoMessage):
    FIELDS = [(1, "evidence", ("rep", ("msg!", Evidence)))]


class Block(ProtoMessage):
    """proto/tendermint/types/block.proto."""

    FIELDS = [
        (1, "header", ("msg!", Header)),
        (2, "data", ("msg!", Data)),
        (3, "evidence", ("msg!", EvidenceList)),
        (4, "last_commit", ("msg", Commit)),
    ]


# --- consensus params (proto/tendermint/types/params.proto) ---


class BlockParams(ProtoMessage):
    FIELDS = [(1, "max_bytes", "int64"), (2, "max_gas", "int64")]


class Duration(ProtoMessage):
    """google.protobuf.Duration."""

    FIELDS = [(1, "seconds", "int64"), (2, "nanos", "int32")]

    @classmethod
    def from_nanos(cls, ns: int) -> "Duration":
        return cls(seconds=int(ns) // 1_000_000_000, nanos=int(ns) % 1_000_000_000)

    def to_nanos(self) -> int:
        return self.seconds * 1_000_000_000 + self.nanos


class EvidenceParams(ProtoMessage):
    FIELDS = [
        (1, "max_age_num_blocks", "int64"),
        (2, "max_age_duration", ("msg!", Duration)),
        (3, "max_bytes", "int64"),
    ]


class ValidatorParams(ProtoMessage):
    FIELDS = [(1, "pub_key_types", ("rep", "string"))]


class VersionParams(ProtoMessage):
    FIELDS = [(1, "app_version", "uint64")]


class ConsensusParams(ProtoMessage):
    FIELDS = [
        (1, "block", ("msg", BlockParams)),
        (2, "evidence", ("msg", EvidenceParams)),
        (3, "validator", ("msg", ValidatorParams)),
        (4, "version", ("msg", VersionParams)),
    ]


class HashedParams(ProtoMessage):
    """Subset of params hashed into Header.ConsensusHash
    (proto/tendermint/types/params.proto HashedParams)."""

    FIELDS = [(1, "block_max_bytes", "int64"), (2, "block_max_gas", "int64")]

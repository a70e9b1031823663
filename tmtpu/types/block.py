"""Block, Header, Commit, CommitSig, BlockID (reference: types/block.go).

Time is carried as integer unix nanoseconds everywhere (no float drift;
matches the reference's nanosecond-precision time.Time canonicalization).
"""

from __future__ import annotations

from typing import Callable, List, Optional

from tmtpu.crypto import tmhash
from tmtpu.crypto.merkle import hash_from_byte_slices
from tmtpu.libs import protoio
from tmtpu.types import pb

BLOCK_ID_FLAG_ABSENT = pb.BLOCK_ID_FLAG_ABSENT
BLOCK_ID_FLAG_COMMIT = pb.BLOCK_ID_FLAG_COMMIT
BLOCK_ID_FLAG_NIL = pb.BLOCK_ID_FLAG_NIL

MAX_HEADER_BYTES = 626  # types/block.go MaxHeaderBytes


# --- wrapper encodings for header field hashing (types/encoding_helper.go:
# cdcEncode wraps scalars in gogotypes {String,Int64,Bytes}Value) ---


class _StringValue(pb.ProtoMessage):
    FIELDS = [(1, "value", "string")]


class _Int64Value(pb.ProtoMessage):
    FIELDS = [(1, "value", "int64")]


class _BytesValue(pb.ProtoMessage):
    FIELDS = [(1, "value", "bytes")]


def cdc_encode_string(s: str) -> bytes:
    return _StringValue(value=s).encode() if s else b""


def cdc_encode_int64(v: int) -> bytes:
    return _Int64Value(value=v).encode() if v else b""


def cdc_encode_bytes(b: bytes) -> bytes:
    return _BytesValue(value=b).encode() if b else b""


class BlockID:
    __slots__ = ("hash", "parts_total", "parts_hash")

    def __init__(self, hash: bytes = b"", parts_total: int = 0,
                 parts_hash: bytes = b""):
        self.hash = bytes(hash)
        self.parts_total = int(parts_total)
        self.parts_hash = bytes(parts_hash)

    def is_zero(self) -> bool:
        return not self.hash and not self.parts_total and not self.parts_hash

    def is_complete(self) -> bool:
        """types/block.go BlockID.IsComplete."""
        return (len(self.hash) == tmhash.SIZE
                and self.parts_total > 0
                and len(self.parts_hash) == tmhash.SIZE)

    def key(self) -> bytes:
        return self.hash + self.parts_total.to_bytes(4, "big") + self.parts_hash

    def to_proto(self) -> pb.BlockID:
        return pb.BlockID(
            hash=self.hash,
            part_set_header=pb.PartSetHeader(
                total=self.parts_total, hash=self.parts_hash
            ),
        )

    def to_canonical(self) -> Optional[pb.CanonicalBlockID]:
        """types/canonical.go CanonicalizeBlockID — nil for the zero id."""
        if self.is_zero():
            return None
        return pb.CanonicalBlockID(
            hash=self.hash,
            part_set_header=pb.CanonicalPartSetHeader(
                total=self.parts_total, hash=self.parts_hash
            ),
        )

    @classmethod
    def from_proto(cls, m: Optional[pb.BlockID]) -> "BlockID":
        if m is None:
            return cls()
        psh = m.part_set_header or pb.PartSetHeader()
        return cls(bytes(m.hash), psh.total, bytes(psh.hash))

    def __eq__(self, other):
        return (isinstance(other, BlockID) and self.hash == other.hash
                and self.parts_total == other.parts_total
                and self.parts_hash == other.parts_hash)

    def __hash__(self):
        return hash(self.key())

    def __repr__(self):
        return (f"BlockID{{{self.hash.hex().upper()[:12]}:"
                f"{self.parts_total}:{self.parts_hash.hex().upper()[:12]}}}")


class CommitSig:
    """types/block.go:595 — one validator's slot in a Commit."""

    __slots__ = ("block_id_flag", "validator_address", "timestamp", "signature")

    def __init__(self, block_id_flag: int = BLOCK_ID_FLAG_ABSENT,
                 validator_address: bytes = b"", timestamp: int = 0,
                 signature: bytes = b""):
        self.block_id_flag = block_id_flag
        self.validator_address = bytes(validator_address)
        self.timestamp = int(timestamp)  # unix nanos
        self.signature = bytes(signature)

    @classmethod
    def absent(cls) -> "CommitSig":
        return cls(BLOCK_ID_FLAG_ABSENT)

    def for_block(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_COMMIT

    def is_absent(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_ABSENT

    def block_id(self, commit_block_id: BlockID) -> BlockID:
        """The BlockID this sig endorses (block.go CommitSig.BlockID)."""
        if self.block_id_flag == BLOCK_ID_FLAG_COMMIT:
            return commit_block_id
        return BlockID()

    def validate_basic(self) -> None:
        if self.block_id_flag not in (BLOCK_ID_FLAG_ABSENT,
                                      BLOCK_ID_FLAG_COMMIT,
                                      BLOCK_ID_FLAG_NIL):
            raise ValueError(f"unknown BlockIDFlag: {self.block_id_flag}")
        if self.is_absent():
            if self.validator_address or self.timestamp or self.signature:
                raise ValueError("absent CommitSig must be empty")
        else:
            if len(self.validator_address) != 20:
                raise ValueError("CommitSig validator address wrong size")
            if not self.signature:
                raise ValueError("CommitSig missing signature")
            if len(self.signature) > 64:
                raise ValueError("CommitSig signature too big")

    def __eq__(self, other):
        return (isinstance(other, CommitSig)
                and self.block_id_flag == other.block_id_flag
                and self.validator_address == other.validator_address
                and self.timestamp == other.timestamp
                and self.signature == other.signature)


class Commit:
    """types/block.go:737."""

    def __init__(self, height: int, round: int, block_id: BlockID,
                 signatures: List[CommitSig]):
        self.height = int(height)
        self.round = int(round)
        self.block_id = block_id
        self.signatures = signatures
        self._hash: Optional[bytes] = None
        self._bit_array = None

    def size(self) -> int:
        return len(self.signatures)

    def vote_sign_bytes_for(self, chain_id: str
                            ) -> Callable[[CommitSig], bytes]:
        """One call's way from a CommitSig of this commit to its canonical
        precommit sign bytes. Type, height, round, block id and chain id
        are the commit's, so they are encoded once
        (``vote.vote_sign_bytes_template``) and each signature fills in its
        timestamp; votes for the block and nil votes differ in the block id
        only, so there are two templates, the nil one made when the first
        nil vote asks for it.

        Nothing is kept on the Commit or its CommitSigs: a node that
        replays a chain sees each commit once, so a memo would only help a
        caller that verifies the same object again, and a CommitSig edited
        in place must never meet bytes made before the edit. The templates
        live as long as the function returned."""
        from tmtpu.types import vote as vote_mod

        def template(block_id: BlockID) -> Callable[[int], bytes]:
            return vote_mod.vote_sign_bytes_template(
                chain_id, pb.SIGNED_MSG_TYPE_PRECOMMIT, self.height,
                self.round, block_id)

        for_block = template(self.block_id)
        for_nil = None

        def sign_bytes(cs: CommitSig) -> bytes:
            nonlocal for_nil
            if cs.block_id_flag == BLOCK_ID_FLAG_COMMIT:
                return for_block(cs.timestamp)
            if for_nil is None:
                for_nil = template(BlockID())
            return for_nil(cs.timestamp)

        return sign_bytes

    def vote_sign_bytes(self, chain_id: str, val_idx: int) -> bytes:
        """Validator val_idx's canonical precommit sign bytes (block.go:807
        Commit.VoteSignBytes): the single-signature entry to
        ``vote_sign_bytes_for``, which a caller that wants more than one
        should take itself."""
        return self.vote_sign_bytes_for(chain_id)(self.signatures[val_idx])

    def bit_array(self):
        from tmtpu.libs.bits import BitArray

        if self._bit_array is None:
            self._bit_array = BitArray.from_bools(
                [not s.is_absent() for s in self.signatures]
            )
        return self._bit_array

    def validate_basic(self) -> None:
        if self.height < 0:
            raise ValueError("negative height")
        if self.round < 0:
            raise ValueError("negative round")
        if self.height >= 1:
            if self.block_id.is_zero():
                raise ValueError("commit cannot be for nil block")
            if not self.signatures:
                raise ValueError("no signatures in commit")
            for cs in self.signatures:
                cs.validate_basic()

    def _rows(self) -> list:
        """The signatures as ``pb.Commit`` rows, read now: what is encoded
        is what the CommitSigs hold at the call, nothing kept."""
        return [(cs.block_id_flag, cs.validator_address, cs.timestamp,
                 cs.signature) for cs in self.signatures]

    def hash(self) -> bytes:
        if self._hash is None:
            self._hash = hash_from_byte_slices(
                pb.CommitSig.encode_rows(self._rows()))
        return self._hash

    def to_proto(self) -> pb.Commit:
        return pb.Commit.from_rows(self.height, self.round,
                                   self.block_id.to_proto(), self._rows())

    @classmethod
    def from_proto(cls, m: pb.Commit) -> "Commit":
        return cls(m.height, m.round, BlockID.from_proto(m.block_id),
                   [CommitSig(flag, address, ns, signature)
                    for flag, address, ns, signature in m.rows()])

    def __eq__(self, other):
        return (isinstance(other, Commit) and self.height == other.height
                and self.round == other.round
                and self.block_id == other.block_id
                and self.signatures == other.signatures)


class Header:
    FIELDS = ("version_block", "version_app", "chain_id", "height", "time",
              "last_block_id", "last_commit_hash", "data_hash",
              "validators_hash", "next_validators_hash", "consensus_hash",
              "app_hash", "last_results_hash", "evidence_hash",
              "proposer_address")
    __slots__ = FIELDS

    def __init__(self, **kw):
        self.version_block = kw.pop("version_block", 0)
        self.version_app = kw.pop("version_app", 0)
        self.chain_id = kw.pop("chain_id", "")
        self.height = kw.pop("height", 0)
        self.time = kw.pop("time", 0)  # unix nanos
        self.last_block_id = kw.pop("last_block_id", BlockID())
        self.last_commit_hash = kw.pop("last_commit_hash", b"")
        self.data_hash = kw.pop("data_hash", b"")
        self.validators_hash = kw.pop("validators_hash", b"")
        self.next_validators_hash = kw.pop("next_validators_hash", b"")
        self.consensus_hash = kw.pop("consensus_hash", b"")
        self.app_hash = kw.pop("app_hash", b"")
        self.last_results_hash = kw.pop("last_results_hash", b"")
        self.evidence_hash = kw.pop("evidence_hash", b"")
        self.proposer_address = kw.pop("proposer_address", b"")
        if kw:
            raise TypeError(f"unknown Header fields {list(kw)}")

    def hash(self) -> Optional[bytes]:
        """Merkle root over the 14 proto-encoded fields (block.go:441
        Header.Hash); nil until ValidatorsHash is set."""
        if not self.validators_hash:
            return None
        return hash_from_byte_slices([
            pb.Consensus(block=self.version_block, app=self.version_app).encode(),
            cdc_encode_string(self.chain_id),
            cdc_encode_int64(self.height),
            pb.Timestamp.from_unix_nanos(self.time).encode(),
            self.last_block_id.to_proto().encode(),
            cdc_encode_bytes(self.last_commit_hash),
            cdc_encode_bytes(self.data_hash),
            cdc_encode_bytes(self.validators_hash),
            cdc_encode_bytes(self.next_validators_hash),
            cdc_encode_bytes(self.consensus_hash),
            cdc_encode_bytes(self.app_hash),
            cdc_encode_bytes(self.last_results_hash),
            cdc_encode_bytes(self.evidence_hash),
            cdc_encode_bytes(self.proposer_address),
        ])

    def validate_basic(self) -> None:
        if not self.chain_id or len(self.chain_id) > 50:
            raise ValueError("invalid chain id")
        if self.height < 0:
            raise ValueError("negative height")
        for name in ("last_commit_hash", "data_hash", "evidence_hash",
                     "validators_hash", "next_validators_hash",
                     "consensus_hash", "last_results_hash"):
            h = getattr(self, name)
            if h and len(h) != tmhash.SIZE:
                raise ValueError(f"wrong {name}: expected size {tmhash.SIZE}")
        if len(self.proposer_address) != 20:
            raise ValueError("invalid proposer address length")

    def to_proto(self) -> pb.Header:
        return pb.Header(
            version=pb.Consensus(block=self.version_block, app=self.version_app),
            chain_id=self.chain_id,
            height=self.height,
            time=pb.Timestamp.from_unix_nanos(self.time),
            last_block_id=self.last_block_id.to_proto(),
            last_commit_hash=self.last_commit_hash,
            data_hash=self.data_hash,
            validators_hash=self.validators_hash,
            next_validators_hash=self.next_validators_hash,
            consensus_hash=self.consensus_hash,
            app_hash=self.app_hash,
            last_results_hash=self.last_results_hash,
            evidence_hash=self.evidence_hash,
            proposer_address=self.proposer_address,
        )

    @classmethod
    def from_proto(cls, m: pb.Header) -> "Header":
        v = m.version or pb.Consensus()
        return cls(
            version_block=v.block, version_app=v.app, chain_id=m.chain_id,
            height=m.height,
            time=m.time.to_unix_nanos() if m.time else 0,
            last_block_id=BlockID.from_proto(m.last_block_id),
            last_commit_hash=bytes(m.last_commit_hash),
            data_hash=bytes(m.data_hash),
            validators_hash=bytes(m.validators_hash),
            next_validators_hash=bytes(m.next_validators_hash),
            consensus_hash=bytes(m.consensus_hash),
            app_hash=bytes(m.app_hash),
            last_results_hash=bytes(m.last_results_hash),
            evidence_hash=bytes(m.evidence_hash),
            proposer_address=bytes(m.proposer_address),
        )

    def __eq__(self, other):
        return isinstance(other, Header) and all(
            getattr(self, f) == getattr(other, f) for f in self.FIELDS
        )


class Block:
    def __init__(self, header: Header, txs: List[bytes],
                 evidence: Optional[list] = None,
                 last_commit: Optional[Commit] = None):
        self.header = header
        self.txs = [bytes(t) for t in txs]
        self.evidence = evidence or []
        self.last_commit = last_commit
        self._hash: Optional[bytes] = None

    def hash(self) -> Optional[bytes]:
        if self._hash is None:
            self._hash = self.header.hash()
        return self._hash

    def data_hash(self) -> bytes:
        from tmtpu.types.tx import txs_hash

        return txs_hash(self.txs)

    def fill_header(self) -> None:
        """Populate derivable header hashes (block.go fillHeader)."""
        if not self.header.last_commit_hash and self.last_commit:
            self.header.last_commit_hash = self.last_commit.hash()
        if not self.header.data_hash:
            self.header.data_hash = self.data_hash()
        if not self.header.evidence_hash:
            from tmtpu.types.evidence import evidence_list_hash

            self.header.evidence_hash = evidence_list_hash(self.evidence)

    def validate_basic(self) -> None:
        self.header.validate_basic()
        if self.header.height > 1:
            if self.last_commit is None:
                raise ValueError("nil LastCommit")
            self.last_commit.validate_basic()
        if self.last_commit and \
                self.header.last_commit_hash != self.last_commit.hash():
            raise ValueError("wrong LastCommitHash")
        if self.header.data_hash != self.data_hash():
            raise ValueError("wrong DataHash")
        from tmtpu.types.evidence import evidence_list_hash

        if self.header.evidence_hash != evidence_list_hash(self.evidence):
            raise ValueError("wrong EvidenceHash")

    def to_proto(self) -> pb.Block:
        from tmtpu.types.evidence import evidence_to_proto

        return pb.Block(
            header=self.header.to_proto(),
            data=pb.Data(txs=self.txs),
            evidence=pb.EvidenceList(
                evidence=[evidence_to_proto(e) for e in self.evidence]
            ),
            last_commit=self.last_commit.to_proto() if self.last_commit else None,
        )

    @classmethod
    def from_proto(cls, m: pb.Block) -> "Block":
        from tmtpu.types.evidence import evidence_from_proto

        header = Header.from_proto(m.header or pb.Header())
        txs = [bytes(t) for t in (m.data.txs if m.data else [])]
        ev = [evidence_from_proto(e)
              for e in (m.evidence.evidence if m.evidence else [])]
        lc = Commit.from_proto(m.last_commit) if m.last_commit else None
        return cls(header, txs, ev, lc)

    def encode(self) -> bytes:
        return self.to_proto().encode()

    @classmethod
    def decode(cls, buf: bytes) -> "Block":
        return cls.from_proto(pb.Block.decode(buf))

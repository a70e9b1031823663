"""A Tendermint v0.34 chain made from a seed, and the plain serial replay a
late node makes of it (blockchain/v0/reactor.go poolRoutine): for each
height ``VerifyCommitLight`` of the successor's ``LastCommit`` signature by
signature (types/validator_set.go:722), the header checks of
state/validation.go with the full ``VerifyCommit`` of ``LastCommit``
(:667), and the kvstore app. Nothing here imports ``tmtpu``; signing and
verifying go through ``cryptography`` (OpenSSL), every byte string is
encoded here from the protobuf definitions (proto/tendermint/types/
types.proto, blockchain/types.proto) under gogoproto's rules: a zero
scalar is left out, an embedded message marked non-nullable is always
written, fields ascend.

That the program accepts this chain at all checks the fabricator: its
``validate_block`` compares every hash a header carries with its own
state.

Two departures from the Go node, both forced by the program under test
and stated here so that they are not mistaken for the source's:

- an absent CommitSig carries the empty Timestamp (unix 0), which is what
  this program reads and writes for "no time"; Go's zero ``time.Time``
  marshals as seconds -62135596800;
- the kvstore's app hash is the count of txs ever applied as 8 bytes
  big-endian (tmtpu/abci/example/kvstore.py); Go's kvstore.go writes the
  count as a varint into 8 bytes.
"""

from __future__ import annotations

import hashlib
import random
import struct
from dataclasses import dataclass, field, replace
from typing import Dict, Iterable, List, Optional, Tuple

from cryptography.exceptions import InvalidSignature

from benchmarks.reference import commits as rc
from benchmarks.reference import kvstore as rk

PART_SIZE = 65536                       # types/params.go BlockPartSizeBytes

# why a served block is refused, as a caller of the reactor can tell
BAD_SIGNATURE = "bad_signature"
LOW_POWER = "too_little_power"
WRONG_BLOCK_ID = "wrong_block_id"
INVALID_COMMIT = "invalid_commit"       # wrong size or height
INVALID_BLOCK = "invalid_block"         # a check of state/validation.go


# -- protobuf ------------------------------------------------------------

def _uvarint(n: int) -> bytes:
    if n < 0x80:
        return bytes((n,))
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _int(num: int, v: int) -> bytes:
    """A varint field; int64 -1 is ten bytes of two's complement."""
    return _uvarint(num << 3) + _uvarint(v & (2**64 - 1)) if v else b""


def _msg(num: int, body: bytes) -> bytes:
    """A length-delimited field that is always written (a non-nullable
    message, or a repeated element)."""
    return _uvarint(num << 3 | 2) + _uvarint(len(body)) + body


def _bytes(num: int, b: bytes) -> bytes:
    return _msg(num, b) if b else b""


def _timestamp(ns: int) -> bytes:
    secs, nanos = divmod(ns, 10**9)
    return _int(1, secs) + _int(2, nanos)


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def merkle(items: List[bytes]) -> bytes:
    """crypto/merkle/tree.go HashFromByteSlices (RFC 6962)."""
    n = len(items)
    if n == 0:
        return _sha(b"")
    if n == 1:
        return _sha(b"\x00" + items[0])
    k = 1
    while k * 2 < n:
        k *= 2
    return _sha(b"\x01" + merkle(items[:k]) + merkle(items[k:]))


# -- the chain's types ------------------------------------------------------

@dataclass
class ChainParams:
    chain_id: str
    genesis_time_ns: int
    app_version: int = 1                # abci/example/kvstore ProtocolVersion
    block_version: int = 11             # version/version.go BlockProtocol
    block_max_bytes: int = 22020096     # types/params.go DefaultBlockParams
    block_max_gas: int = -1

    def consensus_hash(self) -> bytes:
        """types/params.go HashConsensusParams: HashedParams alone."""
        return _sha(_int(1, self.block_max_bytes)
                    + _int(2, self.block_max_gas))


BlockID = Tuple[bytes, int, bytes]      # hash, parts total, parts hash
ZERO_ID: BlockID = (b"", 0, b"")


def _block_id(bid: BlockID) -> bytes:
    return _bytes(1, bid[0]) + _msg(2, _int(1, bid[1]) + _bytes(2, bid[2]))


def encode_commit_sig(vals: rc.ValSet, idx: int,
                      sig: Tuple[int, int, bytes]) -> bytes:
    flag, ts, signature = sig
    addr = b"" if flag == rc.ABSENT else vals.addrs[idx]
    return _int(1, flag) + _bytes(2, addr) + _msg(3, _timestamp(ts)) \
        + _bytes(4, signature)


def _commit_id(c: rc.CommitData) -> BlockID:
    return (c.block_hash, c.parts_total, c.parts_hash)


def _commit_sigs(vals: rc.ValSet, c: rc.CommitData) -> List[bytes]:
    return [encode_commit_sig(vals, i, s) for i, s in enumerate(c.sigs)]


def encode_commit(vals: rc.ValSet, c: rc.CommitData,
                  sigs: Optional[List[bytes]] = None) -> bytes:
    """``sigs``: the slots' encodings, where the caller has them."""
    return _int(1, c.height) + _int(2, c.round) \
        + _msg(3, _block_id(_commit_id(c))) \
        + b"".join(_msg(4, s) for s in sigs or _commit_sigs(vals, c))


def commit_hash(vals: rc.ValSet, c: rc.CommitData) -> bytes:
    return merkle(_commit_sigs(vals, c))


def validators_hash(vals: rc.ValSet) -> bytes:
    """types/validator_set.go:347: SimpleValidator{PublicKey{ed25519},
    voting power} in the set's order; priorities are not hashed."""
    return merkle([_msg(1, _msg(1, pub)) + _int(2, power)
                   for pub, power in zip(vals.pubs, vals.powers)])


def txs_hash(txs: List[bytes]) -> bytes:
    return merkle([_sha(tx) for tx in txs])


def results_hash(n_txs: int) -> bytes:
    """types/results.go: the kvstore answers every tx with code 0 and no
    data or gas, whose deterministic encoding is empty."""
    return merkle([b""] * n_txs)


def app_hash_of(size: int) -> bytes:
    return struct.pack(">q", size)


@dataclass
class Block:
    """One block as a peer serves it. ``last_commit`` is the commit of the
    block below (none of its slots filled at height 1)."""
    height: int
    time_ns: int
    last_block_id: BlockID
    last_commit: rc.CommitData
    txs: List[bytes]
    validators_hash: bytes
    next_validators_hash: bytes
    consensus_hash: bytes
    app_hash: bytes
    last_results_hash: bytes
    proposer: bytes
    chain_id: str
    app_version: int
    block_version: int
    last_commit_hash: bytes = b""
    data_hash: bytes = b""
    evidence_hash: bytes = b""
    hash: bytes = b""
    wire: bytes = b""
    parts_total: int = 0
    parts_hash: bytes = b""

    @property
    def id(self) -> BlockID:
        return (self.hash, self.parts_total, self.parts_hash)

    def seal(self, vals: rc.ValSet) -> "Block":
        """Fill what follows from the rest (types/block.go fillHeader,
        Header.Hash, MakePartSet), in that order."""
        sigs = _commit_sigs(vals, self.last_commit)
        self.last_commit_hash = merkle(sigs)
        self.data_hash = txs_hash(self.txs)
        self.evidence_hash = merkle([])
        self.hash = header_hash(self)
        self.wire = _msg(1, encode_header(self)) \
            + _msg(2, b"".join(_msg(1, tx) for tx in self.txs)) \
            + _msg(3, b"") \
            + _msg(4, encode_commit(vals, self.last_commit, sigs))
        self.parts_total, self.parts_hash = part_set(self.wire)
        return self


def _version(b: Block) -> bytes:
    return _int(1, b.block_version) + _int(2, b.app_version)


def encode_header(b: Block) -> bytes:
    return _msg(1, _version(b)) + _bytes(2, b.chain_id.encode()) \
        + _int(3, b.height) + _msg(4, _timestamp(b.time_ns)) \
        + _msg(5, _block_id(b.last_block_id)) \
        + _bytes(6, b.last_commit_hash) + _bytes(7, b.data_hash) \
        + _bytes(8, b.validators_hash) + _bytes(9, b.next_validators_hash) \
        + _bytes(10, b.consensus_hash) + _bytes(11, b.app_hash) \
        + _bytes(12, b.last_results_hash) + _bytes(13, b.evidence_hash) \
        + _bytes(14, b.proposer)


def header_hash(b: Block) -> bytes:
    """types/block.go:441: the merkle root of the fourteen fields, scalars
    wrapped as gogotypes values (types/encoding_helper.go cdcEncode)."""
    def wrapped(v: bytes) -> bytes:
        return _bytes(1, v)

    return merkle([
        _version(b), wrapped(b.chain_id.encode()), _int(1, b.height),
        _timestamp(b.time_ns), _block_id(b.last_block_id),
        wrapped(b.last_commit_hash), wrapped(b.data_hash),
        wrapped(b.validators_hash), wrapped(b.next_validators_hash),
        wrapped(b.consensus_hash), wrapped(b.app_hash),
        wrapped(b.last_results_hash), wrapped(b.evidence_hash),
        wrapped(b.proposer)])


def part_set(data: bytes) -> Tuple[int, bytes]:
    """types/part_set.go NewPartSetFromData -> (total, root)."""
    chunks = [data[i:i + PART_SIZE]
              for i in range(0, len(data), PART_SIZE)] or [b""]
    return len(chunks), merkle(chunks)


# -- the blockchain channel's messages (proto/tendermint/blockchain) -------

def block_response(b: Block) -> bytes:
    return _msg(3, _msg(1, b.wire))


def status_response(base: int, height: int) -> bytes:
    return _msg(5, _int(1, height) + _int(2, base))


# -- the fabricator ---------------------------------------------------------

def median_time(vals: rc.ValSet, c: rc.CommitData) -> int:
    """state/state.go:268 MedianTime: the weighted median, by voting
    power, of the timestamps of the slots that are not absent."""
    weighted = sorted((ts, vals.powers[i])
                      for i, (flag, ts, _s) in enumerate(c.sigs)
                      if flag != rc.ABSENT)
    median = sum(w for _t, w in weighted) // 2
    for ts, w in weighted:
        if median <= w:
            return ts
        median -= w
    return 0


def make_txs(seed: int, height: int, n: int, tx_bytes: int) -> List[bytes]:
    """``key=value`` txs of exactly ``tx_bytes`` bytes, no key twice in a
    chain; the value's bytes are the seed's."""
    rng = random.Random(seed * 1_000_003 + height)
    out = []
    for i in range(n):
        head = b"k%d-%d-%d=" % (seed, height, i)
        out.append(head + rng.randbytes(tx_bytes // 2).hex().encode()[
            :tx_bytes - len(head)])
    return out


def sign_commit(vals: rc.ValSet, seed: int, chain_id: str, height: int,
                bid: BlockID, time_ns: int, n_absent: int,
                absent: Optional[Iterable[int]] = None) -> rc.CommitData:
    """The commit of the block ``bid`` at ``height``: ``n_absent`` slots
    absent (WHICH is the seed's, unless ``absent`` names them), none nil,
    every other validator's precommit for the block, each with a
    timestamp of its own after the block's time."""
    n = len(vals.pubs)
    if absent is None:
        absent = random.Random(seed * 1_000_003 + height).sample(
            range(n), n_absent)
    absent = set(absent)
    c = rc.CommitData(chain_id, height, 0, bid[0], bid[1], bid[2], [])
    # the canonical vote but for its timestamp (field 5), once a commit;
    # the replay below verifies against commits.vote_sign_bytes, which
    # encodes each vote whole
    head = _int(1, rc.PRECOMMIT) + _uvarint(2 << 3 | 1) \
        + struct.pack("<q", height) \
        + _msg(4, _bytes(1, bid[0]) + _msg(2, _int(1, bid[1])
                                           + _bytes(2, bid[2])))
    tail = _bytes(6, chain_id.encode())
    base = time_ns + 10**9
    for i in range(n):
        if i in absent:
            c.sigs.append((rc.ABSENT, 0, b""))
            continue
        ts = base + 1000 * i
        body = head + _msg(5, _timestamp(ts)) + tail
        c.sigs.append((rc.COMMIT, ts,
                       vals.privs[i].sign(_uvarint(len(body)) + body)))
    return c


@dataclass
class Tip:
    """What the next block is built on (state/state.go State, as far as
    a chain with one validator set and the kvstore needs it)."""
    height: int = 0
    block_id: BlockID = ZERO_ID
    time_ns: int = 0
    commit: Optional[rc.CommitData] = None      # of the block at ``height``
    app_size: int = 0
    last_results_hash: bytes = b""

    @property
    def app_hash(self) -> bytes:
        return app_hash_of(self.app_size) if self.height else b""


def make_block(vals: rc.ValSet, p: ChainParams, tip: Tip,
               txs: List[bytes]) -> Block:
    """state/state.go MakeBlock on top of ``tip``."""
    h = tip.height + 1
    vh = validators_hash(vals)
    if h == 1:
        last_commit = rc.CommitData(p.chain_id, 0, 0, b"", 0, b"", [])
        time_ns = p.genesis_time_ns
    else:
        last_commit = tip.commit
        time_ns = median_time(vals, last_commit)
    return Block(
        height=h, time_ns=time_ns, last_block_id=tip.block_id,
        last_commit=last_commit, txs=txs, validators_hash=vh,
        next_validators_hash=vh, consensus_hash=p.consensus_hash(),
        app_hash=tip.app_hash, last_results_hash=tip.last_results_hash,
        proposer=vals.addrs[h % len(vals.addrs)], chain_id=p.chain_id,
        app_version=p.app_version, block_version=p.block_version,
    ).seal(vals)


def advance(tip: Tip, b: Block, commit: rc.CommitData) -> Tip:
    return Tip(b.height, b.id, b.time_ns, commit,
               tip.app_size + len(b.txs), results_hash(len(b.txs)))


def make_chain(vals: rc.ValSet, p: ChainParams, seed: int, n_blocks: int,
               txs_per_block: int, tx_bytes: int, n_absent: int
               ) -> Tuple[List[Block], List[Tip]]:
    """-> (blocks 1..n, tips 0..n): ``tips[h]`` is the state after block
    h, with the commit that block h+1 carries as its LastCommit."""
    tips = [Tip(time_ns=p.genesis_time_ns)]
    blocks = []
    for h in range(1, n_blocks + 1):
        b = make_block(vals, p, tips[-1],
                       make_txs(seed, h, txs_per_block, tx_bytes))
        commit = sign_commit(vals, seed, p.chain_id, h, b.id, b.time_ns,
                             n_absent)
        blocks.append(b)
        tips.append(advance(tips[-1], b, commit))
    return blocks, tips


# -- faults: what a lying peer serves in a block's place -------------------

def with_last_commit(vals: rc.ValSet, b: Block, c: rc.CommitData) -> Block:
    """``b`` carrying another LastCommit, sealed again: its hashes are
    right, so only the commit itself can be at fault."""
    return replace(b, last_commit=c).seal(vals)


def tampered_successor(vals: rc.ValSet, nxt: Block, seed: int) -> Block:
    """``nxt`` with one bit flipped in a signature of its LastCommit, in
    a slot the seed draws from those before the 2/3 point: there
    VerifyCommitLight's early exit and a verifier of every signature
    refuse the same block."""
    present = [i for i, s in enumerate(nxt.last_commit.sigs)
               if s[0] == rc.COMMIT]
    at = random.Random(seed ^ 0x7A3).choice(
        present[:len(vals.pubs) * 2 // 3 - 1])
    return with_last_commit(vals, nxt,
                            rc.tamper_signature(nxt.last_commit, at))


def starved_successor(vals: rc.ValSet, nxt: Block, seed: int) -> Block:
    """``nxt`` whose LastCommit has so many slots absent that exactly 2/3
    of the power is left on the block: one signature short."""
    n = len(vals.pubs)
    keep = sum(vals.powers) * 2 // 3          # equal powers of 1: a count
    c = nxt.last_commit
    present = [i for i, s in enumerate(c.sigs) if s[0] == rc.COMMIT]
    drop = set(random.Random(seed ^ 0x51A).sample(
        present, len(present) - keep // vals.powers[0]))
    sigs = [(rc.ABSENT, 0, b"") if i in drop else s
            for i, s in enumerate(c.sigs)]
    return with_last_commit(vals, nxt, rc.CommitData(
        c.chain_id, c.height, c.round, c.block_hash, c.parts_total,
        c.parts_hash, sigs))


def another_block(vals: rc.ValSet, p: ChainParams, tip: Tip, seed: int,
                  txs_per_block: int, tx_bytes: int) -> Block:
    """A well-formed block on ``tip`` with other txs than the chain's:
    the successor's LastCommit then names a block id that is not its."""
    return make_block(vals, p, tip, make_txs(seed ^ 0xB10C, tip.height + 1,
                                             txs_per_block, tx_bytes))


# -- the plain serial replay -------------------------------------------------

class Refused(Exception):
    def __init__(self, reason: str, what: str = ""):
        super().__init__(f"{reason}: {what}" if what else reason)
        self.reason = reason


@dataclass
class Outcome:
    """What a run of served blocks came to: the heights applied, in
    order, and the first height refused with the reason, if any."""
    applied: List[int] = field(default_factory=list)
    refused: Optional[Tuple[int, str]] = None


class Replay:
    """The node's state and the serial loop over served blocks.

    ``skip`` names ONE check to leave out: the CONTROLS, not the
    reference (``signatures``: no signature is verified; ``power``: the
    2/3 tally is not asked for; ``block_id``: a commit may name any
    block). ``verify_at`` limits signature verification to those heights
    (None: every height); the hashes and the tally are checked at all.
    """

    def __init__(self, vals: rc.ValSet, p: ChainParams, skip: str = "",
                 verify_at: Optional[set] = None):
        self.vals = vals
        self.p = p
        self.skip = skip
        self.verify_at = verify_at
        self.tip = Tip(time_ns=p.genesis_time_ns)
        self.state: Dict[bytes, bytes] = {}
        self.block_ids: Dict[int, BlockID] = {}
        self._vh = validators_hash(vals)

    # -- types/validator_set.go ------------------------------------------

    def _sig_ok(self, c: rc.CommitData, idx: int) -> bool:
        if self.skip == "signatures" or (
                self.verify_at is not None and c.height not in self.verify_at):
            return True
        try:
            self.vals.pub_objs[idx].verify(c.sigs[idx][2], c.sign_bytes(idx))
        except (InvalidSignature, ValueError):
            return False
        return True

    def _commit_basics(self, c: rc.CommitData, height: int, bid: BlockID):
        if len(c.sigs) != len(self.vals.pubs):
            raise Refused(INVALID_COMMIT, "wrong set size")
        if c.height != height:
            raise Refused(INVALID_COMMIT, "wrong height")
        if self.skip != "block_id" and _commit_id(c) != bid:
            raise Refused(WRONG_BLOCK_ID)

    def verify_commit_light(self, c: rc.CommitData, height: int,
                            bid: BlockID) -> None:
        """:722 — only votes for the block count and are verified, in slot
        order, until more than 2/3 of the power is tallied."""
        self._commit_basics(c, height, bid)
        needed = self.vals.total_power * 2 // 3
        tallied = 0
        for idx, (flag, _ts, _sig) in enumerate(c.sigs):
            if flag != rc.COMMIT:
                continue
            if not self._sig_ok(c, idx):
                raise Refused(BAD_SIGNATURE, f"slot {idx}")
            tallied += self.vals.powers[idx]
            if tallied > needed:
                return
        if self.skip != "power":
            raise Refused(LOW_POWER, f"{tallied} of more than {needed}")

    def verify_commit(self, c: rc.CommitData, height: int,
                      bid: BlockID) -> None:
        """:667 — every slot that is not absent is verified; votes for
        the block are tallied."""
        self._commit_basics(c, height, bid)
        tallied = 0
        for idx, (flag, _ts, _sig) in enumerate(c.sigs):
            if flag == rc.ABSENT:
                continue
            if not self._sig_ok(c, idx):
                raise Refused(BAD_SIGNATURE, f"slot {idx}")
            if flag == rc.COMMIT:
                tallied += self.vals.powers[idx]
        if self.skip != "power" and \
                tallied <= self.vals.total_power * 2 // 3:
            raise Refused(LOW_POWER)

    # -- state/validation.go ----------------------------------------------

    def validate_block(self, b: Block) -> None:
        t, p = self.tip, self.p
        want = {
            "version": (b.block_version, b.app_version) ==
                       (p.block_version, p.app_version),
            "chain id": b.chain_id == p.chain_id,
            "height": b.height == t.height + 1,
            "LastBlockID": b.last_block_id == t.block_id,
            "AppHash": b.app_hash == t.app_hash,
            "ConsensusHash": b.consensus_hash == p.consensus_hash(),
            "LastResultsHash": b.last_results_hash == t.last_results_hash,
            "ValidatorsHash": b.validators_hash == self._vh,
            "NextValidatorsHash": b.next_validators_hash == self._vh,
            "LastCommitHash": b.last_commit_hash ==
                              commit_hash(self.vals, b.last_commit),
            "DataHash": b.data_hash == txs_hash(b.txs),
            "EvidenceHash": b.evidence_hash == merkle([]),
            "proposer": b.proposer in self.vals.addrs,
        }
        for name, ok in want.items():
            if not ok:
                raise Refused(INVALID_BLOCK, "wrong " + name)
        if b.height == 1:
            if b.last_commit.sigs:
                raise Refused(INVALID_BLOCK, "LastCommit at the first height")
            if b.time_ns != p.genesis_time_ns:
                raise Refused(INVALID_BLOCK, "wrong time")
            return
        try:
            self.verify_commit(b.last_commit, t.height, t.block_id)
        except Refused as e:
            raise Refused(INVALID_BLOCK, str(e)) from e
        if b.time_ns <= t.time_ns or \
                b.time_ns != median_time(self.vals, b.last_commit):
            raise Refused(INVALID_BLOCK, "wrong time")

    # -- blockchain/v0/reactor.go -------------------------------------------

    def apply(self, first: Block, second: Block) -> None:
        """One turn of the loop: ``first`` is verified by ``second``'s
        LastCommit, validated, executed. Raises ``Refused``."""
        first_id = (header_hash(first),) + part_set(first.wire)
        self.verify_commit_light(second.last_commit, first.height, first_id)
        self.validate_block(first)
        self.state.update(rk.final_state(first.txs))
        self.block_ids[first.height] = first_id
        self.tip = advance(self.tip, first, second.last_commit)

    def run(self, served: List[Block]) -> Outcome:
        """Blocks at consecutive heights from the tip's next, as the pool
        holds them: each but the last is applied if its successor
        vouches for it; the first refusal ends the run."""
        out = Outcome()
        for first, second in zip(served, served[1:]):
            try:
                self.apply(first, second)
            except Refused as e:
                out.refused = (first.height, e.reason)
                break
            out.applied.append(first.height)
        return out

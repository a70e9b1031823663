"""Live consensus rounds of a Tendermint v0.34 chain made from a seed, as
ONE validator of it receives them, and the plain protocol that validator
has to follow (consensus/state.go addVote over types/vote_set.go addVote):
for a height, the votes in delivery order, each signature verified one at
a time, a running tally per block id, the point at which more than 2/3 of
the power has precommitted one block, the votes refused and why, the
evidence two conflicting votes of one validator make. Nothing here imports
``tmtpu``; signing and verifying go through ``cryptography`` (OpenSSL),
every byte string is encoded here from the protobuf definitions
(proto/tendermint/types/types.proto and canonical.proto,
proto/tendermint/consensus/types.proto) with ``reference/blocks.py``'s
encoders.

From the seed: the validator set (``reference/commits.py``), the proposer
of every height (``reference/light.py rotate``: one turn of
types/validator_set.go:116 a height), the node (a validator the seed draws
from those that propose no height of the chain), the chain — each block on
the state the one below left, proposed by its height's proposer, carrying as
LastCommit the co-signers' precommits for the block below with the node's
own absent — and, for every height, each co-signer's prevote and precommit
for the block, all for it and none nil, and the wire bytes a gossiping peer
sends: the proposer's signed ``Proposal``, the block's parts with their
merkle proofs, one ``Vote`` message a vote.

A block depends on the signatures of the height below (its LastCommit's
hash), so the chain is made height by height; what is parallel is a
height's 9,999 precommits, and afterwards all the prevotes: ``workers``
processes, each with the set's keys, sign them.
"""

from __future__ import annotations

import multiprocessing
import random
import struct
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from cryptography.exceptions import InvalidSignature

from benchmarks.reference import blocks as rb
from benchmarks.reference import commits as rc
from benchmarks.reference import kvstore as rk
from benchmarks.reference.light import rotate

PREVOTE, PRECOMMIT, PROPOSAL = 1, 2, 32     # SignedMsgType

# why a delivered vote is not added, as a caller of the node can tell
BAD_SIGNATURE = "bad_signature"
DUPLICATE = "duplicate"
CONFLICTING = "conflicting_vote"
WRONG_HEIGHT = "wrong_height"


@dataclass
class RoundsSpec:
    """Everything a worker process needs to sign its share."""
    seed: int
    chain_id: str
    genesis_time_ns: int
    validators: int
    voting_power: int = 1
    txs_per_block: int = 16
    tx_bytes: int = 1024
    app_version: int = 1

    def params(self) -> rb.ChainParams:
        return rb.ChainParams(self.chain_id, self.genesis_time_ns,
                              app_version=self.app_version)

    def valset(self) -> rc.ValSet:
        return rc.make_valset(self.seed, self.validators, self.voting_power)


# -- who proposes, and who the node is ----------------------------------------

def proposers(vals: rc.ValSet, n_heights: int) -> List[int]:
    """-> the set index of the proposer of heights 1..n (round 0): the
    genesis set's priorities are all zero and every height turns the
    rotation once (state/state.go MakeGenesisState, then
    CopyIncrementProposerPriority(1) a block)."""
    priorities = [0] * len(vals.pubs)
    return [rotate(vals, priorities) for _ in range(n_heights)]


def node_index(seed: int, vals: rc.ValSet, n_heights: int) -> int:
    """The node's place in the set: drawn from the seed among the
    validators that propose none of heights 1..n+1, so that every proposal
    of the chain reaches the node from a peer."""
    busy = set(proposers(vals, n_heights + 1))
    free = [i for i in range(len(vals.pubs)) if i not in busy]
    if not free:
        raise ValueError(f"every one of {len(vals.pubs)} validators "
                         f"proposes within {n_heights + 1} heights")
    return random.Random(seed ^ 0x11FE).choice(free)


# -- votes ----------------------------------------------------------------------

@dataclass
class Vote:
    """types/vote.go Vote, as delivered."""
    type: int
    height: int
    round: int
    block_id: rb.BlockID
    timestamp_ns: int
    index: int                  # the validator's place in the set
    signature: bytes


def vote_sign_bytes(chain_id: str, v: Vote) -> bytes:
    """types/vote.go VoteSignBytes: the length-delimited CanonicalVote,
    each field encoded here (proto3: a zero scalar is left out, the
    timestamp always written, a nil vote has no block id)."""
    body = rb._int(1, v.type)
    if v.height:
        body += rb._uvarint(2 << 3 | 1) + struct.pack("<q", v.height)
    if v.round:
        body += rb._uvarint(3 << 3 | 1) + struct.pack("<q", v.round)
    if v.block_id != rb.ZERO_ID:
        body += rb._msg(4, rb._bytes(1, v.block_id[0]) + rb._msg(
            2, rb._int(1, v.block_id[1]) + rb._bytes(2, v.block_id[2])))
    body += rb._msg(5, rb._timestamp(v.timestamp_ns))
    body += rb._bytes(6, chain_id.encode())
    return rb._uvarint(len(body)) + body


def vote_wire(vals: rc.ValSet, v: Vote) -> bytes:
    """The vote channel's message (consensus Message.vote, field 6)."""
    body = rb._int(1, v.type) + rb._int(2, v.height) + rb._int(3, v.round) \
        + rb._msg(4, rb._block_id(v.block_id)) \
        + rb._msg(5, rb._timestamp(v.timestamp_ns)) \
        + rb._bytes(6, vals.addrs[v.index]) + rb._int(7, v.index) \
        + rb._bytes(8, v.signature)
    return rb._msg(6, rb._msg(1, body))


def vote_time(vtype: int, block_time_ns: int, index: int) -> int:
    """A co-signer's vote time: after the block's, a time of its own a
    validator and a step (a precommit's is ``blocks.sign_commit``'s)."""
    return block_time_ns + (10**9 if vtype == PRECOMMIT else 5 * 10**8) \
        + 1000 * index


def sign_vote(vals: rc.ValSet, chain_id: str, vtype: int, height: int,
              bid: rb.BlockID, index: int, timestamp_ns: int) -> Vote:
    v = Vote(vtype, height, 0, bid, timestamp_ns, index, b"")
    v.signature = vals.privs[index].sign(vote_sign_bytes(chain_id, v))
    return v


def tampered(v: Vote) -> Vote:
    """The vote with one bit of its signature flipped."""
    bad = bytearray(v.signature)
    bad[7] ^= 0x10
    return Vote(v.type, v.height, v.round, v.block_id, v.timestamp_ns,
                v.index, bytes(bad))


# -- the workers ----------------------------------------------------------------

_WORKER: dict = {}


def _worker_init(spec: RoundsSpec) -> None:
    _WORKER["spec"] = spec
    _WORKER["vals"] = spec.valset()


def _sign_share(job) -> List[Tuple[int, bytes, bytes]]:
    """(vote type, height, block id, block time, indices) -> [(index,
    signature, wire bytes)] of those validators' votes for the block."""
    vtype, height, bid, time_ns, indices = job
    spec, vals = _WORKER["spec"], _WORKER["vals"]
    out = []
    for i in indices:
        v = sign_vote(vals, spec.chain_id, vtype, height, bid, i,
                      vote_time(vtype, time_ns, i))
        out.append((i, v.signature, vote_wire(vals, v)))
    return out


class Signers:
    """``workers`` processes that hold the set's keys (started afresh:
    they import this module and nothing of the caller's), or this process
    when ``workers`` is 1."""

    def __init__(self, spec: RoundsSpec, vals: rc.ValSet, workers: int):
        self.workers = max(1, workers)
        self.pool = None
        if self.workers > 1:
            self.pool = ProcessPoolExecutor(
                self.workers,
                mp_context=multiprocessing.get_context("spawn"),
                initializer=_worker_init, initargs=(spec,))
        else:
            _WORKER["spec"], _WORKER["vals"] = spec, vals

    def sign(self, jobs: list) -> List[List[Tuple[int, bytes, bytes]]]:
        if self.pool is None:
            return [_sign_share(j) for j in jobs]
        return list(self.pool.map(_sign_share, jobs))

    def shares(self, indices: List[int]) -> List[List[int]]:
        step = -(-len(indices) // self.workers)
        return [indices[k:k + step] for k in range(0, len(indices), step)]

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown()


# -- the chain, as the node receives it -------------------------------------------

def merkle_proofs(items: List[bytes]) -> Tuple[bytes, List[List[bytes]]]:
    """crypto/merkle/proof.go ProofsFromByteSlices -> (root, the aunts of
    every leaf from its sibling up to the root's children)."""
    n = len(items)
    if n == 1:
        return rb._sha(b"\x00" + items[0]), [[]]
    k = 1
    while k * 2 < n:
        k *= 2
    lroot, left = merkle_proofs(items[:k])
    rroot, right = merkle_proofs(items[k:])
    return rb._sha(b"\x01" + lroot + rroot), \
        [a + [rroot] for a in left] + [a + [lroot] for a in right]


def part_wires(b: rb.Block) -> List[bytes]:
    """The block's parts as the data channel carries them (consensus
    Message.block_part, field 5), each with its proof."""
    chunks = [b.wire[i:i + rb.PART_SIZE]
              for i in range(0, len(b.wire), rb.PART_SIZE)] or [b""]
    root, aunts = merkle_proofs(chunks)
    if (len(chunks), root) != (b.parts_total, b.parts_hash):
        raise AssertionError("the part set's root is not the block id's")
    out = []
    for i, chunk in enumerate(chunks):
        proof = rb._int(1, len(chunks)) + rb._int(2, i) \
            + rb._bytes(3, rb._sha(b"\x00" + chunk)) \
            + b"".join(rb._msg(4, a) for a in aunts[i])
        part = rb._int(1, i) + rb._bytes(2, chunk) + rb._msg(3, proof)
        out.append(rb._msg(5, rb._int(1, b.height) + rb._msg(3, part)))
    return out


def proposal_wire(vals: rc.ValSet, chain_id: str, b: rb.Block,
                  proposer: int) -> bytes:
    """types/proposal.go: the proposer's signed Proposal for ``b`` at round
    0 with no proof-of-lock round (consensus Message.proposal, field 3)."""
    ts = b.time_ns + 10**8
    canonical = rb._int(1, PROPOSAL) \
        + rb._uvarint(2 << 3 | 1) + struct.pack("<q", b.height) \
        + rb._int(4, -1) \
        + rb._msg(5, rb._bytes(1, b.hash) + rb._msg(
            2, rb._int(1, b.parts_total) + rb._bytes(2, b.parts_hash))) \
        + rb._msg(6, rb._timestamp(ts)) + rb._bytes(7, chain_id.encode())
    sig = vals.privs[proposer].sign(rb._uvarint(len(canonical)) + canonical)
    body = rb._int(1, PROPOSAL) + rb._int(2, b.height) + rb._int(4, -1) \
        + rb._msg(5, rb._block_id(b.id)) + rb._msg(6, rb._timestamp(ts)) \
        + rb._bytes(7, sig)
    return rb._msg(3, rb._msg(1, body))


@dataclass
class HeightData:
    """One height as the node's peers send it."""
    block: rb.Block
    proposer: int
    proposal: bytes = b""
    parts: List[bytes] = field(default_factory=list)
    prevotes: List[bytes] = field(default_factory=list)     # co-signers', by index
    precommits: List[bytes] = field(default_factory=list)
    commit: Optional[rc.CommitData] = None      # the co-signers' precommits


@dataclass
class Chain:
    spec: RoundsSpec
    vals: rc.ValSet
    node: int                   # the node's place in the set
    co_signers: List[int]
    heights: List[HeightData]   # heights[h - 1]
    tips: List[rb.Tip]          # tips[h]: the state after block h

    def vote(self, vtype: int, height: int, index: int,
             bid: Optional[rb.BlockID] = None) -> Vote:
        """Validator ``index``'s vote of the chain at ``height`` again (or,
        with ``bid``, its signed vote for another block id)."""
        b = self.heights[height - 1].block
        return sign_vote(self.vals, self.spec.chain_id, vtype, height,
                         b.id if bid is None else bid, index,
                         vote_time(vtype, b.time_ns, index))


def make_block(spec: RoundsSpec, vals: rc.ValSet, tip: rb.Tip, proposer: int
               ) -> rb.Block:
    """state/state.go MakeBlock on ``tip`` by ``proposer``."""
    p = spec.params()
    h = tip.height + 1
    vh = rb.validators_hash(vals)
    if h == 1:
        last_commit = rc.CommitData(spec.chain_id, 0, 0, b"", 0, b"", [])
        time_ns = spec.genesis_time_ns
    else:
        last_commit = tip.commit
        time_ns = rb.median_time(vals, last_commit)
    return rb.Block(
        height=h, time_ns=time_ns, last_block_id=tip.block_id,
        last_commit=last_commit,
        txs=rb.make_txs(spec.seed, h, spec.txs_per_block, spec.tx_bytes),
        validators_hash=vh, next_validators_hash=vh,
        consensus_hash=p.consensus_hash(), app_hash=tip.app_hash,
        last_results_hash=tip.last_results_hash,
        proposer=vals.addrs[proposer], chain_id=spec.chain_id,
        app_version=p.app_version, block_version=p.block_version,
    ).seal(vals)


def make_chain(spec: RoundsSpec, n_heights: int, workers: int = 1) -> Chain:
    vals = spec.valset()
    node = node_index(spec.seed, vals, n_heights)
    who = proposers(vals, n_heights)
    co = [i for i in range(len(vals.pubs)) if i != node]
    signers = Signers(spec, vals, workers)
    shares = signers.shares(co)
    try:
        tips = [rb.Tip(time_ns=spec.genesis_time_ns)]
        heights: List[HeightData] = []
        for h in range(1, n_heights + 1):
            b = make_block(spec, vals, tips[-1], who[h - 1])
            signed = signers.sign([(PRECOMMIT, h, b.id, b.time_ns, share)
                                   for share in shares])
            sigs = [(rc.ABSENT, 0, b"")] * len(vals.pubs)
            wires = {}
            for part in signed:
                for i, sig, wire in part:
                    sigs[i] = (rc.COMMIT,
                               vote_time(PRECOMMIT, b.time_ns, i), sig)
                    wires[i] = wire
            commit = rc.CommitData(spec.chain_id, h, 0, b.id[0], b.id[1],
                                   b.id[2], sigs)
            heights.append(HeightData(
                b, who[h - 1], proposal_wire(vals, spec.chain_id, b,
                                             who[h - 1]),
                part_wires(b), [], [wires[i] for i in co], commit))
            tips.append(rb.advance(tips[-1], b, commit))
        # the prevotes depend on nothing but the block ids: all at once
        signed = signers.sign([(PREVOTE, hd.block.height, hd.block.id,
                                hd.block.time_ns, share)
                               for hd in heights for share in shares])
        for k, hd in enumerate(heights):
            wires = {i: wire
                     for part in signed[k * len(shares):(k + 1) * len(shares)]
                     for i, _sig, wire in part}
            hd.prevotes = [wires[i] for i in co]
    finally:
        signers.close()
    return Chain(spec, vals, node, co, heights, tips)


def final_state(chain: Chain, height: int) -> Dict[bytes, bytes]:
    """The kvstore's state after blocks 1..height."""
    return rk.final_state(tx for hd in chain.heights[:height]
                          for tx in hd.block.txs)


# -- the plain protocol, one height of one node -----------------------------------

@dataclass
class Evidence:
    """types/evidence.go NewDuplicateVoteEvidence: the two votes ordered by
    their block ids' keys, the powers of the set at that height, the time of
    the block at that height."""
    vote_a: Vote
    vote_b: Vote
    total_voting_power: int
    validator_power: int
    timestamp_ns: int


def _block_key(bid: rb.BlockID) -> bytes:
    """types/block.go BlockID.Key: the hash, then the part set header's
    protobuf encoding."""
    return bid[0] + rb._int(1, bid[1]) + rb._bytes(2, bid[2])


class Height:
    """A node's vote sets of one height, round 0, fed one vote at a time.

    ``skip`` names ONE check to leave out: the CONTROL, not the reference
    (``signatures``: no signature is verified).
    """

    def __init__(self, vals: rc.ValSet, chain_id: str, height: int,
                 block_time_ns: int = 0, skip: str = ""):
        self.vals = vals
        self.chain_id = chain_id
        self.height = height
        self.block_time_ns = block_time_ns
        self.skip = skip
        self.votes: Dict[Tuple[int, int], Vote] = {}      # (type, index)
        self.power: Dict[Tuple[int, rb.BlockID], int] = {}
        self.added = {PREVOTE: 0, PRECOMMIT: 0}
        self.refused: List[Tuple[int, int, str]] = []     # (type, index, why)
        self.evidence: List[Evidence] = []
        self.committed: Optional[rb.BlockID] = None
        self.commit_at: Optional[int] = None    # precommits delivered by then
        self._precommits_seen = 0

    @property
    def needed(self) -> int:
        return self.vals.total_power * 2 // 3

    def _sig_ok(self, v: Vote) -> bool:
        if self.skip == "signatures":
            return True
        try:
            self.vals.pub_objs[v.index].verify(
                v.signature, vote_sign_bytes(self.chain_id, v))
        except (InvalidSignature, ValueError):
            return False
        return True

    def own(self, vtype: int, bid: rb.BlockID, index: int) -> None:
        """The node's own vote: signed by itself, so counted unverified."""
        self._count(Vote(vtype, self.height, 0, bid, 0, index, b"own"))

    def deliver(self, v: Vote) -> Optional[str]:
        """types/vote_set.go addVote -> None when added, else why not."""
        if v.type == PRECOMMIT:
            self._precommits_seen += 1
        why = None
        if v.height != self.height or v.round != 0:
            why = WRONG_HEIGHT
        else:
            have = self.votes.get((v.type, v.index))
            if have is not None and have.block_id == v.block_id:
                why = DUPLICATE
            elif not self._sig_ok(v):
                why = BAD_SIGNATURE
            elif have is not None:
                # two signed votes of one validator for two blocks
                a, b = sorted((have, v), key=lambda x: _block_key(x.block_id))
                self.evidence.append(Evidence(
                    a, b, self.vals.total_power, self.vals.powers[v.index],
                    self.block_time_ns))
                why = CONFLICTING
        if why is not None:
            self.refused.append((v.type, v.index, why))
            return why
        self._count(v)
        self.added[v.type] += 1
        return None

    def _count(self, v: Vote) -> None:
        self.votes[(v.type, v.index)] = v
        key = (v.type, v.block_id)
        self.power[key] = self.power.get(key, 0) + self.vals.powers[v.index]
        if v.type == PRECOMMIT and self.committed is None and \
                v.block_id != rb.ZERO_ID and self.power[key] > self.needed:
            self.committed = v.block_id
            self.commit_at = self._precommits_seen

    def polka(self) -> Optional[rb.BlockID]:
        """The block more than 2/3 of the power has prevoted, if any."""
        return next((bid for (t, bid), p in self.power.items()
                     if t == PREVOTE and p > self.needed), None)

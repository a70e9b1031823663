"""The light blocks of a Tendermint v0.34 chain made from a seed, and the
plain serial sync a light client makes of them in sequential mode
(light/client.go:613 verifySequential over light/verifier.go:93
VerifyAdjacent, then light/detector.go:28): per header the adjacent checks,
the tally of more than 2/3 of its own set's power, and each for-block
signature of its commit verified one at a time. Nothing here imports
``tmtpu``; signing and verifying go through ``cryptography`` (OpenSSL), the
bytes are encoded from the protobuf definitions (proto/tendermint/types/
types.proto LightBlock, validator.proto) with ``reference/blocks.py``'s
encoders.

A light client sees headers, commits and validator sets, never a block: so
the chain has no transactions and no parts, and what a header carries of a
block's body (LastCommitHash, DataHash, AppHash, LastResultsHash, the part
set's hash in its id) is 32 bytes drawn from the seed. No light client
checks them, and with them out of the way no header depends on a signature:
the headers are made first, serially and cheaply, and the commits are
signed in worker processes.

Stricter than VerifyCommitLight, never weaker, as the program is: every
for-block signature of a commit is verified, not only those before the 2/3
point.
"""

from __future__ import annotations

import hashlib
import multiprocessing
import random
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Tuple

from cryptography.exceptions import InvalidSignature

from benchmarks.reference import blocks as rb
from benchmarks.reference import commits as rc

# why a session is refused, as a caller of the client can tell
BAD_SIGNATURE = "bad_signature"
LOW_POWER = "too_little_power"
BROKEN_LINK = "validators_hash_breaks_the_link"
INVALID_HEADER = "invalid_header"       # another adjacent check
EXPIRED = "trusted_header_expired"
CONFLICTING_WITNESS = "witness_proves_another_header"


@dataclass
class ChainSpec:
    """Everything a worker process needs to make its share of a chain."""
    seed: int
    chain_id: str
    genesis_time_ns: int
    validators: int
    voting_power: int = 1
    absent_per_commit: int = 0
    block_interval_ns: int = 10**9
    app_version: int = 1
    valset_seed: Optional[int] = None   # a liar's other set

    def params(self) -> rb.ChainParams:
        return rb.ChainParams(self.chain_id, self.genesis_time_ns,
                              app_version=self.app_version)

    def valset(self) -> rc.ValSet:
        return rc.make_valset(self.seed if self.valset_seed is None
                              else self.valset_seed, self.validators,
                              self.voting_power)


@dataclass
class LightBlock:
    """types/light.go LightBlock: a header (``reference/blocks.py``'s
    record of its fields), the commit for it, the set that signed it with
    each validator's proposer priority at that height."""
    header: rb.Block
    commit: rc.CommitData
    vals: rc.ValSet
    priorities: List[int]
    proposer: int                       # index into the set
    wire: bytes = b""

    @property
    def height(self) -> int:
        return self.header.height

    def seal(self) -> "LightBlock":
        self.wire = rb._msg(1, rb._msg(1, rb.encode_header(self.header))
                            + rb._msg(2, rb.encode_commit(self.vals,
                                                          self.commit))) \
            + rb._msg(2, encode_validator_set(self.vals, self.priorities,
                                              self.proposer))
        return self


def _validator(vals: rc.ValSet, i: int, priority: int) -> bytes:
    return rb._bytes(1, vals.addrs[i]) + rb._msg(2, rb._msg(1, vals.pubs[i])) \
        + rb._int(3, vals.powers[i]) + rb._int(4, priority)


def encode_validator_set(vals: rc.ValSet, priorities: List[int],
                         proposer: int) -> bytes:
    return b"".join(rb._msg(1, _validator(vals, i, pr))
                    for i, pr in enumerate(priorities)) \
        + rb._msg(2, _validator(vals, proposer, priorities[proposer])) \
        + rb._int(3, vals.total_power)


def rotate(vals: rc.ValSet, priorities: List[int]) -> int:
    """One turn of types/validator_set.go:116 IncrementProposerPriority in
    place -> the proposer's index: centre on the average, give every
    validator its power, take the total from the one that leads (the lower
    address on a tie). The rescale never triggers at equal powers."""
    avg = sum(priorities) // len(priorities)
    lead = 0
    for i, power in enumerate(vals.powers):
        priorities[i] += power - avg
        if priorities[i] > priorities[lead]:
            lead = i
    priorities[lead] -= vals.total_power
    return lead


def _drawn(spec: ChainSpec, what: bytes, height: int) -> bytes:
    return hashlib.sha256(b"light-%s-%d-%d" % (what, spec.seed,
                                               height)).digest()


def headers(spec: ChainSpec, vals: rc.ValSet, n_blocks: int, start=None):
    """Yields (header, priorities, proposer index) for heights 1..n on top
    of ``start`` (the header below the first, None at the chain's foot).
    No header depends on a signature (module docstring)."""
    p = spec.params()
    vh = rb.validators_hash(vals)
    priorities = [0] * len(vals.pubs)
    last_id, first = rb.ZERO_ID, 1
    if start is not None:
        # the set's rotation is the chain's; a fork shares its past
        for _ in range(start.height):
            rotate(vals, priorities)
        last_id, first = start.id, start.height + 1
    for h in range(first, first + n_blocks):
        lead = rotate(vals, priorities)
        b = rb.Block(
            height=h, time_ns=spec.genesis_time_ns
            + (h - 1) * spec.block_interval_ns,
            last_block_id=last_id, last_commit=None, txs=[],
            validators_hash=vh, next_validators_hash=vh,
            consensus_hash=p.consensus_hash(),
            app_hash=_drawn(spec, b"app", h)[:8],
            last_results_hash=_drawn(spec, b"results", h),
            proposer=vals.addrs[lead], chain_id=spec.chain_id,
            app_version=p.app_version, block_version=p.block_version,
            last_commit_hash=_drawn(spec, b"lastcommit", h),
            data_hash=rb.merkle([]), evidence_hash=rb.merkle([]))
        b.hash = rb.header_hash(b)
        b.parts_total, b.parts_hash = 1, _drawn(spec, b"parts", h)
        last_id = b.id
        yield b, list(priorities), lead


def _make_range(job) -> List[LightBlock]:
    """A worker's share: the light blocks of heights lo..hi. The headers
    below are made again here (tens of microseconds each) rather than sent."""
    spec, lo, hi = job
    vals = spec.valset()
    out = []
    for b, priorities, lead in headers(spec, vals, hi):
        if b.height < lo:
            continue
        commit = rb.sign_commit(vals, spec.seed, spec.chain_id, b.height,
                                b.id, b.time_ns, spec.absent_per_commit)
        out.append(LightBlock(b, commit, vals, priorities, lead).seal())
    return out


def make_chain(spec: ChainSpec, n_blocks: int, workers: int = 1
               ) -> Tuple[rc.ValSet, List[LightBlock]]:
    """-> (the set, light blocks 1..n), signed in ``workers`` processes
    (started afresh: they import this module and nothing of the caller's)."""
    vals = spec.valset()
    if workers <= 1 or n_blocks < 2 * workers:
        chain = _make_range((spec, 1, n_blocks))
    else:
        step = -(-n_blocks // (4 * workers))
        jobs = [(spec, lo, min(lo + step - 1, n_blocks))
                for lo in range(1, n_blocks + 1, step)]
        with ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("spawn")
        ) as pool:
            chain = [lb for part in pool.map(_worker, jobs) for lb in part]
    for lb in chain:        # one set object, not one a worker
        lb.vals = vals
    return vals, chain


def _worker(job) -> List[LightBlock]:
    out = _make_range(job)
    for lb in out:
        lb.vals = None      # private keys do not pickle; the caller has its own
    return out


# -- faults: what a lying provider serves in a light block's place ---------

def with_commit(lb: LightBlock, commit: rc.CommitData) -> LightBlock:
    return replace(lb, commit=commit).seal()


def tampered(lb: LightBlock, seed: int) -> LightBlock:
    """One bit flipped in a signature the seed draws from those after the
    2/3 point: VerifyCommitLight's early exit would let it through, a
    verifier of every signature does not."""
    present = [i for i, s in enumerate(lb.commit.sigs) if s[0] == rc.COMMIT]
    at = random.Random(seed ^ 0x7A3).choice(
        present[len(lb.vals.pubs) * 2 // 3 + 1:])
    return with_commit(lb, rc.tamper_signature(lb.commit, at))


def starved(lb: LightBlock, seed: int) -> LightBlock:
    """So many slots absent that exactly 2/3 of the power is left on the
    header: one signature short."""
    c = lb.commit
    keep = lb.vals.total_power * 2 // 3 // lb.vals.powers[0]
    present = [i for i, s in enumerate(c.sigs) if s[0] == rc.COMMIT]
    drop = set(random.Random(seed ^ 0x51A).sample(present,
                                                  len(present) - keep))
    return with_commit(lb, replace(c, sigs=[
        (rc.ABSENT, 0, b"") if i in drop else s
        for i, s in enumerate(c.sigs)]))


def fork(spec: ChainSpec, chain: List[LightBlock], from_height: int,
         n_blocks: int, valset_seed: Optional[int] = None
         ) -> Dict[int, LightBlock]:
    """``n_blocks`` light blocks on top of the chain's block below
    ``from_height`` that are not the chain's: other drawn hashes (the
    fork's own seed), each header well formed and signed by more than 2/3
    of the set it names. With ``valset_seed`` that set is another one
    than the chain's, which alone breaks the link to the header below."""
    other = replace(spec, seed=spec.seed ^ 0xF02C, valset_seed=valset_seed
                    if valset_seed is not None else spec.seed)
    vals = other.valset()
    out = {}
    for b, priorities, lead in headers(
            other, vals, n_blocks, start=chain[from_height - 2].header):
        commit = rb.sign_commit(vals, other.seed, spec.chain_id, b.height,
                                b.id, b.time_ns, spec.absent_per_commit)
        out[b.height] = LightBlock(b, commit, vals, priorities, lead).seal()
    return out


# -- the plain serial sync ---------------------------------------------------

class Refused(Exception):
    def __init__(self, kind: str, what: str = ""):
        super().__init__(f"{kind}: {what}" if what else kind)
        self.kind = kind


@dataclass
class Outcome:
    """What a session came to: the heights it added to the trusted store,
    in order, or the first height refused and why (nothing of a refused
    session is trusted), and the evidence handed to the primary."""
    trusted: List[int] = field(default_factory=list)
    refused: Optional[Tuple[int, str]] = None
    evidence_to_primary: int = 0


Serve = Callable[[int], LightBlock]


class Sync:
    """A light client's trusted state and the serial loop over served
    light blocks.

    ``skip`` names ONE check to leave out: the CONTROLS, not the reference
    (``signatures``: no signature is verified; ``power``: the 2/3 tally is
    not asked for; ``link``: a header may name any validator set;
    ``witness``: no witness is asked). ``verify_at`` limits signature
    verification to those heights (None: every height); every other check
    is made at all.
    """

    def __init__(self, chain_id: str, root: LightBlock,
                 trusting_period_ns: int, max_clock_drift_ns: int,
                 pruning_size: int, skip: str = "",
                 verify_at: Optional[set] = None):
        self.chain_id = chain_id
        self.period = trusting_period_ns
        self.drift = max_clock_drift_ns
        self.pruning_size = pruning_size
        self.skip = skip
        self.verify_at = verify_at
        self.last = root
        self.stored: Dict[int, bytes] = {root.height: root.wire}

    # -- types/validator_set.go:722, every signature -----------------------

    def _sig_ok(self, lb: LightBlock, idx: int) -> bool:
        if self.skip == "signatures" or (
                self.verify_at is not None
                and lb.height not in self.verify_at):
            return True
        try:
            lb.vals.pub_objs[idx].verify(lb.commit.sigs[idx][2],
                                         lb.commit.sign_bytes(idx))
        except (InvalidSignature, ValueError):
            return False
        return True

    def verify_commit_light(self, lb: LightBlock) -> None:
        c = lb.commit
        if len(c.sigs) != len(lb.vals.pubs) or c.height != lb.height or \
                rb._commit_id(c) != lb.header.id:
            raise Refused(INVALID_HEADER, "the commit is not this header's")
        tallied = 0
        for idx, (flag, _ts, _sig) in enumerate(c.sigs):
            if flag != rc.COMMIT:
                continue
            if not self._sig_ok(lb, idx):
                raise Refused(BAD_SIGNATURE, f"slot {idx}")
            tallied += lb.vals.powers[idx]
        needed = lb.vals.total_power * 2 // 3
        if self.skip != "power" and tallied <= needed:
            raise Refused(LOW_POWER, f"{tallied} of more than {needed}")

    # -- light/verifier.go:93 ------------------------------------------------

    def verify_adjacent(self, trusted: LightBlock, lb: LightBlock,
                        now_ns: int) -> None:
        t, h = trusted.header, lb.header
        if h.height != t.height + 1:
            raise Refused(INVALID_HEADER, "not adjacent")
        if t.time_ns + self.period <= now_ns:
            raise Refused(EXPIRED)
        want = {
            "chain id": h.chain_id == self.chain_id,
            "commit height": lb.commit.height == h.height,
            "commit block hash": lb.commit.block_hash == rb.header_hash(h),
            "time after the trusted": h.time_ns > t.time_ns,
            "time not from the future": h.time_ns < now_ns + self.drift,
            "validators hash is the set's":
                h.validators_hash == rb.validators_hash(lb.vals),
        }
        for name, ok in want.items():
            if not ok:
                raise Refused(INVALID_HEADER, "wrong " + name)
        if self.skip != "link" and \
                h.validators_hash != t.next_validators_hash:
            raise Refused(BROKEN_LINK)
        self.verify_commit_light(lb)

    # -- light/detector.go:28 ------------------------------------------------

    def _witness_proves(self, base: LightBlock, wb: LightBlock) -> bool:
        """The witness's other header at the target stands on its own
        (light/verifier.go:32 from the session's base): more than 1/3 of
        the base's power, by address, and more than 2/3 of its own set
        signed it, every signature good."""
        if rb._commit_id(wb.commit) != wb.header.id or \
                wb.header.validators_hash != rb.validators_hash(wb.vals):
            return False
        try:
            self.verify_commit_light(wb)
        except Refused:
            return False
        power = dict(zip(base.vals.addrs, base.vals.powers))
        overlap = sum(power.get(wb.vals.addrs[i], 0)
                      for i, s in enumerate(wb.commit.sigs)
                      if s[0] == rc.COMMIT)
        return overlap > base.vals.total_power // 3

    # -- light/client.go:558, :613 -------------------------------------------

    def session(self, serve: Serve, target: int, now_ns: int,
                witness: Optional[Serve] = None) -> Outcome:
        """verify_light_block_at_height(target): every header from the
        last trusted to the target in order, then the witness, then the
        stores; the first refusal ends the session with nothing stored."""
        out = Outcome()
        base = cur = self.last
        verified = []
        for h in range(base.height + 1, target + 1):
            lb = serve(h)
            try:
                self.verify_adjacent(cur, lb, now_ns)
            except Refused as e:
                out.refused = (h, e.kind)
                return out
            verified.append(lb)
            cur = lb
        if witness is not None and self.skip != "witness" and verified:
            wb = witness(target)
            if wb.header.hash != cur.header.hash and \
                    self._witness_proves(base, wb):
                out.refused = (target, CONFLICTING_WITNESS)
                out.evidence_to_primary = 1
                return out
        for lb in verified:
            self.stored[lb.height] = lb.wire
            out.trusted.append(lb.height)
            if len(self.stored) > self.pruning_size:
                del self.stored[next(iter(self.stored))]    # the oldest
        self.last = cur
        return out

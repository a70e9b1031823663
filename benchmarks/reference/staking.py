"""A Tendermint v0.34 chain whose validator set moves every height, made
from a seed, as ONE validator of it receives it — and the plain protocol
that decides what the set is at each height. Nothing here imports
``tmtpu``; signing and verifying go through ``cryptography`` (OpenSSL),
every byte string is encoded with ``reference/blocks.py``'s encoders.

The source, step by step:

- the set (types/validator_set.go): ``NewValidatorSet`` (an update of an
  empty set, then one turn), ``UpdateWithChangeSet`` (processChanges,
  verifyRemovals, verifyUpdates, computeNewPriorities — a newcomer at
  -(T + T>>3) —, applyUpdates and applyRemovals by address, the total,
  ``RescalePriorities`` at a window of 2T, the shift by the average, the
  sort by power descending then address ascending) and
  ``IncrementProposerPriority`` with its rescale (``rotate`` below: the
  proposer is the highest priority, the lower address on a tie);
- state/execution.go ``updateState``: the updates an EndBlock returns at
  height H change the set of H+2; every height turns it once;
- state/state.go ``MedianTime``: the block's time is the power-weighted
  median of its LastCommit's times, weighted by the set of that commit;
- the app: v0.34 persistent_kvstore's ``val:`` txs, each an update the
  EndBlock returns (a removal of a key the table lacks is refused and
  returns none); ``key=value`` txs as ``reference/kvstore.py``;
- the reactor sends a peer the votes it lacks in random order
  (consensus/reactor.go ``PickVoteToSend`` over ``BitArray.PickRandom``):
  a height's prevotes, then its precommits, each in an order of its own.

What the deployment assumes (``assumed`` of its configuration): powers by
a Zipf law of exponent 1 over a seed-drawn ranking of the keys, scaled to
``total_power``; every block carries ``changes_per_height`` power changes
of distinct validators drawn with probability proportional to power, each
new power ``round(old x (1 + u))``, u uniform on [-span, span], at least 1;
every ``join_every``-th height the lowest-power validator other than the
node leaves and a fresh key joins at the lowest power that remains. The
node's key is drawn among the lowest-power validators that propose no
height of the chain; no tx names it.

Two departures from the Go node, forced by the program under test and
stated so that they are not mistaken for the source's: a ``val:`` tx
carries its key in hex (the program's kvstore; Go's takes base64), and it
counts among the txs the app hash counts (``reference/blocks.py``: the
kvstore's app hash is the count of txs it applied; Go's persistent
kvstore does not count a ``val:`` tx).
"""

from __future__ import annotations

import hashlib
import multiprocessing
import random
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Dict, List, Optional, Tuple

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from benchmarks.reference import blocks as rb
from benchmarks.reference import commits as rc
from benchmarks.reference import kvstore as rk
from benchmarks.reference import rounds as rr
from benchmarks.reference.light import encode_validator_set

MAX_TOTAL_VOTING_POWER = (1 << 63) // 8     # types/validator_set.go:25
PRIORITY_WINDOW_SIZE_FACTOR = 2             # types/validator_set.go:31
_I64_MIN, _I64_MAX = -(1 << 63), (1 << 63) - 1
VAL_PREFIX = b"val:"


def _clip(v: int) -> int:
    """safeAddClip / safeSubClip: int64 arithmetic that saturates."""
    return _I64_MIN if v < _I64_MIN else _I64_MAX if v > _I64_MAX else v


# -- IncrementProposerPriority ------------------------------------------------

def _rescale(priorities: List[int], diff_max: int) -> None:
    """validator_set.go:143 RescalePriorities: a spread above ``diff_max``
    is divided by ceil(spread / diff_max), truncating toward zero."""
    if diff_max <= 0:
        return
    diff = max(priorities) - min(priorities)
    if diff > diff_max:
        ratio = (diff + diff_max - 1) // diff_max
        for i, p in enumerate(priorities):
            priorities[i] = abs(p) // ratio * (1 if p >= 0 else -1)


def _center(priorities: List[int], total: int) -> None:
    """RescalePriorities at a window of twice the total, then
    shiftByAvgProposerPriority (big.Int division: the floor)."""
    _rescale(priorities, PRIORITY_WINDOW_SIZE_FACTOR * total)
    avg = sum(priorities) // len(priorities)
    for i, p in enumerate(priorities):
        priorities[i] = _clip(p - avg)


def _turn(powers: List[int], addrs: List[bytes], priorities: List[int]
          ) -> int:
    """validator_set.go:116 IncrementProposerPriority(1) on a set's lists,
    in place -> the proposer's index: centre, give every validator its
    power, take the total from the one with the highest priority (the
    lower address on a tie)."""
    total = sum(powers)
    _center(priorities, total)
    lead = 0
    for i, power in enumerate(powers):
        priorities[i] = _clip(priorities[i] + power)
        if priorities[i] > priorities[lead] or (
                priorities[i] == priorities[lead] and addrs[i] < addrs[lead]):
            lead = i
    priorities[lead] = _clip(priorities[lead] - total)
    return lead


def rotate(vals: rc.ValSet, priorities: List[int]) -> int:
    """``reference/light.py rotate`` with Go's RescalePriorities: one turn
    of a set in place -> the proposer's index. At equal powers the spread
    never passes the window and the tie goes to the earlier index, which
    is the lower address: the same priorities and proposers as
    ``light.rotate``."""
    return _turn(vals.powers, vals.addrs, priorities)


# -- UpdateWithChangeSet ----------------------------------------------------------

class Member:
    """types/validator.go Validator: a key (its id in ``Keys``), its
    address, voting power and proposer priority."""
    __slots__ = ("key", "address", "power", "priority")

    def __init__(self, key: int, address: bytes, power: int,
                 priority: int = 0):
        self.key, self.address = key, address
        self.power, self.priority = power, priority

    def copy(self) -> "Member":
        return Member(self.key, self.address, self.power, self.priority)


class StakeSet:
    """types/validator_set.go ValidatorSet: members in the set's order and
    the proposer of the last turn (an index)."""

    def __init__(self, members: Optional[List[Member]] = None,
                 proposer: Optional[int] = None):
        self.members: List[Member] = members or []
        self.proposer = proposer

    @classmethod
    def new(cls, members: List[Member]) -> "StakeSet":
        """NewValidatorSet: the members as an update of an empty set, then
        one turn."""
        s = cls()
        s.update_with_change_set(members, allow_deletes=False)
        s.increment()
        return s

    def copy(self) -> "StakeSet":
        return StakeSet([m.copy() for m in self.members], self.proposer)

    @property
    def total(self) -> int:
        return sum(m.power for m in self.members)

    def index_of(self, key: int) -> int:
        return next(i for i, m in enumerate(self.members) if m.key == key)

    def increment(self) -> int:
        """IncrementProposerPriority(1) -> the proposer's index."""
        priorities = [m.priority for m in self.members]
        self.proposer = _turn([m.power for m in self.members],
                              [m.address for m in self.members], priorities)
        for m, p in zip(self.members, priorities):
            m.priority = p
        return self.proposer

    def update_with_change_set(self, changes: List[Member],
                               allow_deletes: bool = True) -> None:
        """validator_set.go:591 updateWithChangeSet, in its order of steps.
        Raises ValueError where Go returns an error."""
        if not changes:
            return
        updates, removals = _process_changes(changes)
        if not allow_deletes and removals:
            raise ValueError("cannot process validators with voting power 0")
        by_addr = {m.address: m for m in self.members}
        if sum(1 for u in updates if u.address not in by_addr) == 0 and \
                len(self.members) == len(removals):
            raise ValueError("applying the validator changes would result "
                             "in empty set")
        removed = _verify_removals(removals, by_addr)
        tvp = _verify_updates(updates, by_addr, self.total, removed)
        _compute_new_priorities(updates, by_addr, tvp)
        # applyUpdates (a merge by address, the update in place of the
        # member), then applyRemovals
        merged = dict(by_addr)
        for u in updates:
            merged[u.address] = u
        for r in removals:
            del merged[r.address]
        self.members = [merged[a] for a in sorted(merged)]
        if self.total > MAX_TOTAL_VOTING_POWER:
            raise ValueError("total voting power exceeds the maximum")
        priorities = [m.priority for m in self.members]
        _center(priorities, self.total)
        for m, p in zip(self.members, priorities):
            m.priority = p
        self.members.sort(key=lambda m: (-m.power, m.address))

    def view(self, keys: "Keys") -> rc.ValSet:
        """The set as ``reference/commits.py`` and ``blocks.py`` take it."""
        ks = [m.key for m in self.members]
        return rc.ValSet([keys.privs[k] for k in ks],
                         [keys.pubs[k] for k in ks],
                         [keys.pub_objs[k] for k in ks],
                         [m.address for m in self.members],
                         [m.power for m in self.members])

    def encode(self, keys: "Keys") -> bytes:
        """proto ValidatorSet: the members with their priorities, the
        proposer, the total (what a state store keeps a height)."""
        return encode_validator_set(self.view(keys),
                                    [m.priority for m in self.members],
                                    self.proposer)


def _process_changes(changes: List[Member]) -> Tuple[List[Member],
                                                     List[Member]]:
    updates, removals = [], []
    prev = None
    for c in sorted((c.copy() for c in changes), key=lambda c: c.address):
        if c.address == prev:
            raise ValueError("duplicate entry in changes")
        if c.power < 0:
            raise ValueError("voting power can't be negative")
        if c.power > MAX_TOTAL_VOTING_POWER:
            raise ValueError("voting power exceeds the maximum")
        (removals if c.power == 0 else updates).append(c)
        prev = c.address
    return updates, removals


def _verify_removals(removals: List[Member], by_addr: Dict[bytes, Member]
                     ) -> int:
    removed = 0
    for r in removals:
        if r.address not in by_addr:
            raise ValueError("failed to find validator to remove")
        removed += by_addr[r.address].power
    if len(removals) > len(by_addr):
        raise ValueError("more deletes than validators")
    return removed


def _verify_updates(updates: List[Member], by_addr: Dict[bytes, Member],
                    total: int, removed: int) -> int:
    """-> the total after the updates, before the removals; the running
    total over the updates in order of their deltas must stay in bounds."""
    def delta(u):
        old = by_addr.get(u.address)
        return u.power - (old.power if old else 0)

    tvp = total - removed
    for u in sorted(updates, key=delta):
        tvp += delta(u)
        if tvp > MAX_TOTAL_VOTING_POWER:
            raise ValueError("total voting power overflow")
    return tvp + removed


def _compute_new_priorities(updates: List[Member],
                            by_addr: Dict[bytes, Member], tvp: int) -> None:
    for u in updates:
        old = by_addr.get(u.address)
        u.priority = -(tvp + (tvp >> 3)) if old is None else old.priority


# -- keys, the app -----------------------------------------------------------------

class Keys:
    """Every key the chain will ever hold, by id: ids below ``n`` are the
    genesis set's, the rest joiners' in the order they join."""

    def __init__(self, seed: int, n: int):
        self.seed, self.n = seed, n
        self.privs, self.pubs, self.pub_objs, self.addrs = [], [], [], []
        for k in range(n):
            self._derive(k)

    def _derive(self, k: int) -> None:
        secret = b"bench-stake-%d-%d" % (self.seed, k) if k < self.n \
            else b"bench-join-%d-%d" % (self.seed, k - self.n)
        sk = Ed25519PrivateKey.from_private_bytes(
            hashlib.sha256(secret).digest())
        pub = sk.public_key().public_bytes_raw()
        self.privs.append(sk)
        self.pubs.append(pub)
        self.pub_objs.append(sk.public_key())
        self.addrs.append(hashlib.sha256(pub).digest()[:20])

    def ensure(self, k: int) -> None:
        while len(self.pubs) <= k:
            self._derive(len(self.pubs))

    def member(self, k: int, power: int) -> Member:
        self.ensure(k)
        return Member(k, self.addrs[k], power)


def val_tx(pub: bytes, power: int) -> bytes:
    """The kvstore's ``val:<hex pubkey>!<power>``."""
    return VAL_PREFIX + pub.hex().encode() + b"!%d" % power


def parse_val_tx(tx: bytes) -> Optional[Tuple[bytes, int]]:
    try:
        pk_hex, _, power = tx[len(VAL_PREFIX):].decode().partition("!")
        return bytes.fromhex(pk_hex), int(power)
    except (ValueError, UnicodeDecodeError):
        return None


def validator_update(pub: bytes, power: int) -> bytes:
    """abci ValidatorUpdate{pub_key: PublicKey{ed25519}, power}: what the
    app answers a ``/val`` query with."""
    return rb._msg(1, rb._msg(1, pub)) + rb._int(2, power)


class App:
    """v0.34 persistent_kvstore as far as a chain's txs reach it: the
    key-value state, the validator table (pubkey -> power) and the
    updates a block's EndBlock returns."""

    def __init__(self, genesis: List[Tuple[bytes, int]]):
        self.state: Dict[bytes, bytes] = {}
        self.validators: Dict[bytes, int] = dict(genesis)

    def deliver_block(self, txs: List[bytes]) -> List[Tuple[bytes, int]]:
        updates = []
        for tx in txs:
            if not tx.startswith(VAL_PREFIX):
                self.state.update(rk.final_state([tx]))
                continue
            parsed = parse_val_tx(tx)
            if parsed is None:
                continue
            pub, power = parsed
            if power == 0:
                if pub not in self.validators:
                    continue        # "Cannot remove non-existent validator"
                del self.validators[pub]
            else:
                self.validators[pub] = power
            updates.append((pub, power))
        return updates


# -- the deployment ------------------------------------------------------------------

@dataclass
class StakingSpec:
    """Everything a worker process needs to sign its share."""
    seed: int
    chain_id: str
    genesis_time_ns: int
    validators: int
    total_power: int = 250_000_000
    changes_per_height: int = 8
    change_span: float = 0.02
    join_every: int = 5
    txs_per_block: int = 16
    tx_bytes: int = 1024
    app_version: int = 1

    def params(self) -> rb.ChainParams:
        return rb.ChainParams(self.chain_id, self.genesis_time_ns,
                              app_version=self.app_version)


def zipf_powers(spec: StakingSpec) -> List[int]:
    """The genesis power of key 0..n-1: rank r (1-based, a seed-drawn
    ranking of the keys) holds total / (r x H_n), at least 1; what the
    floors leave goes to rank 1, so the powers sum to ``total_power``."""
    n = spec.validators
    rank = list(range(n))
    random.Random(spec.seed ^ 0x21FF).shuffle(rank)     # rank[key] = r - 1
    h_n = sum(1.0 / r for r in range(1, n + 1))
    by_rank = [max(1, int(spec.total_power / (r * h_n)))
               for r in range(1, n + 1)]
    by_rank[0] += spec.total_power - sum(by_rank)
    return [by_rank[rank[k]] for k in range(n)]


@dataclass
class Plan:
    """The chain but for its signatures: the set of every height
    (``sets[h]``, h = 1 .. n + 2), each block's txs and the updates its
    EndBlock returned, and the node's key."""
    spec: StakingSpec
    keys: Keys
    node_key: int
    genesis: List[Tuple[int, int]]          # (key, power)
    sets: List[Optional[StakeSet]]
    txs: List[List[bytes]]                  # txs[h], txs[0] empty
    updates: List[List[Tuple[int, int]]]    # (key, power) of block h
    joins: Dict[int, int] = field(default_factory=dict)    # key -> height
    leaves: Dict[int, int] = field(default_factory=dict)

    def proposers(self, upto: int) -> List[int]:
        """The proposer's key at heights 1..upto."""
        return [self.sets[h].members[self.sets[h].proposer].key
                for h in range(1, upto + 1)]


def _block_changes(spec: StakingSpec, keys: Keys, table: Dict[int, int],
                   node_key: int, h: int, next_joiner: int
                   ) -> List[Tuple[int, int]]:
    """Block h's validator txs as (key, new power): the leave and the join
    first where the height has them, then the power changes."""
    rng = random.Random(spec.seed * 1_000_003 + h * 7919 + 0x57A4E)
    out = []
    if spec.join_every and h % spec.join_every == 0:
        others = [k for k in table if k != node_key]
        leaver = min(others, key=lambda k: (table[k], keys.addrs[k]))
        out.append((leaver, 0))
        out.append((next_joiner, min(table[k] for k in table
                                     if k != leaver)))
    taken = {k for k, _p in out} | {node_key}
    cands = sorted(k for k in table if k not in taken)
    cum, run = [], 0
    for k in cands:
        run += table[k]
        cum.append(run)
    chosen: List[int] = []
    while len(chosen) < min(spec.changes_per_height, len(cands)):
        k = rng.choices(cands, cum_weights=cum)[0]
        if k not in chosen:
            chosen.append(k)
    for k in chosen:
        u = rng.uniform(-spec.change_span, spec.change_span)
        out.append((k, max(1, round(table[k] * (1 + u)))))
    return out


def make_plan(spec: StakingSpec, n_heights: int, node_key: int,
              keys: Optional[Keys] = None) -> Plan:
    keys = keys or Keys(spec.seed, spec.validators)
    genesis = list(enumerate(zipf_powers(spec)))
    table = dict(genesis)               # the app's validator table, by key
    sets: List[Optional[StakeSet]] = [None] * (n_heights + 3)
    sets[1] = StakeSet.new([keys.member(k, p) for k, p in genesis])
    sets[2] = sets[1].copy()
    sets[2].increment()
    txs: List[List[bytes]] = [[]]
    updates: List[List[Tuple[int, int]]] = [[]]
    plan = Plan(spec, keys, node_key, genesis, sets, txs, updates)
    next_joiner = spec.validators
    for h in range(1, n_heights + 1):
        changes = _block_changes(spec, keys, table, node_key, h, next_joiner)
        for k, power in changes:
            keys.ensure(k)
            if k not in table:
                plan.joins[k] = h
                next_joiner += 1
            elif power == 0:
                plan.leaves[k] = h
        block_txs = rb.make_txs(spec.seed, h, spec.txs_per_block,
                                spec.tx_bytes) + [
            val_tx(keys.pubs[k], p) for k, p in changes]
        txs.append(block_txs)
        # the app's EndBlock, then updateState: the set of h + 2
        app = App([(keys.pubs[k], p) for k, p in table.items()])
        got = app.deliver_block(block_txs)
        by_pub = {keys.pubs[k]: k for k, _p in changes}
        block_updates = [(by_pub[pub], p) for pub, p in got]
        updates.append(block_updates)
        for k, p in block_updates:
            if p == 0:
                del table[k]
            else:
                table[k] = p
        nxt = sets[h + 1].copy()
        nxt.update_with_change_set([keys.member(k, p)
                                    for k, p in block_updates])
        nxt.increment()
        sets[h + 2] = nxt
    return plan


def choose_node(spec: StakingSpec, n_heights: int) -> Plan:
    """The plan whose node is drawn from the seed among the tenth of the
    keys with the least genesis power and proposes none of heights
    1..n+1 (every proposal reaches it from a peer)."""
    keys = Keys(spec.seed, spec.validators)
    powers = zipf_powers(spec)
    low = sorted(range(spec.validators),
                 key=lambda k: (powers[k], keys.addrs[k]))
    pool = low[:max(1, spec.validators // 10)]
    rng = random.Random(spec.seed ^ 0x11FE)
    while pool:
        k = pool.pop(rng.randrange(len(pool)))
        plan = make_plan(spec, n_heights, k, keys)
        if k not in plan.proposers(n_heights + 1):
            return plan
    raise ValueError("every low-power key proposes a height of the chain")


# -- signing, in worker processes ---------------------------------------------------

_WORKER: dict = {}


def _worker_init(spec: StakingSpec) -> None:
    _WORKER["spec"] = spec
    _WORKER["keys"] = Keys(spec.seed, spec.validators)


def _sign_share(job) -> List[Tuple[int, bytes, bytes]]:
    """(vote type, height, block id, block time, [(index, key)]) ->
    [(index, signature, wire bytes)] of those validators' votes."""
    vtype, height, bid, time_ns, members = job
    spec, keys = _WORKER["spec"], _WORKER["keys"]
    out = []
    for i, k in members:
        keys.ensure(k)
        v = rr.Vote(vtype, height, 0, bid, rr.vote_time(vtype, time_ns, i),
                    i, b"")
        v.signature = keys.privs[k].sign(rr.vote_sign_bytes(spec.chain_id,
                                                             v))
        out.append((i, v.signature, rr.vote_wire(
            SimpleNamespace(addrs={i: keys.addrs[k]}), v)))
    return out


class Signers:
    """``workers`` processes that hold every key of the chain (started
    afresh: they import this module and nothing of the caller's), or this
    process when ``workers`` is 1."""

    def __init__(self, spec: StakingSpec, keys: Keys, workers: int):
        self.workers = max(1, workers)
        self.pool = None
        if self.workers > 1:
            self.pool = ProcessPoolExecutor(
                self.workers,
                mp_context=multiprocessing.get_context("spawn"),
                initializer=_worker_init, initargs=(spec,))
        else:
            _WORKER["spec"], _WORKER["keys"] = spec, keys

    def sign(self, jobs: list) -> List[Tuple[int, bytes, bytes]]:
        if self.pool is None:
            parts = [_sign_share(j) for j in jobs]
        else:
            parts = list(self.pool.map(_sign_share, jobs))
        return [x for part in parts for x in part]

    def jobs(self, vtype, height, bid, time_ns, members) -> list:
        step = -(-len(members) // self.workers)
        return [(vtype, height, bid, time_ns, members[k:k + step])
                for k in range(0, len(members), step)]

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown()


# -- the chain, as the node receives it ---------------------------------------------

@dataclass
class HeightData:
    """One height as the node's peers send it; the votes in delivery
    order, ``prevote_order`` / ``precommit_order`` the set indices of that
    order."""
    block: rb.Block
    vals: rc.ValSet                     # the set of this height
    proposer: int
    proposal: bytes = b""
    parts: List[bytes] = field(default_factory=list)
    prevotes: List[bytes] = field(default_factory=list)
    precommits: List[bytes] = field(default_factory=list)
    prevote_order: List[int] = field(default_factory=list)
    precommit_order: List[int] = field(default_factory=list)
    commit: Optional[rc.CommitData] = None      # the co-signers' precommits


@dataclass
class Chain:
    spec: StakingSpec
    plan: Plan
    heights: List[HeightData]   # heights[h - 1]
    tips: List[rb.Tip]          # tips[h]: the state after block h

    @property
    def keys(self) -> Keys:
        return self.plan.keys

    def node(self, height: int) -> int:
        """The node's index in the set of ``height``."""
        return self.plan.sets[height].index_of(self.plan.node_key)

    def co_signers(self, height: int) -> List[int]:
        node = self.node(height)
        return [i for i in range(len(self.plan.sets[height].members))
                if i != node]

    def vals(self, height: int) -> rc.ValSet:
        return self.heights[height - 1].vals if height <= len(
            self.heights) else self.plan.sets[height].view(self.keys)

    def vote(self, vtype: int, height: int, index: int,
             bid: Optional[rb.BlockID] = None) -> rr.Vote:
        """Validator ``index``'s vote of the chain at ``height`` again (or,
        with ``bid``, its signed vote for another block id)."""
        b = self.heights[height - 1].block
        return rr.sign_vote(self.vals(height), self.spec.chain_id, vtype,
                            height, b.id if bid is None else bid, index,
                            rr.vote_time(vtype, b.time_ns, index))

    def app(self, height: int) -> App:
        """The app after blocks 1..height."""
        app = App([(self.keys.pubs[k], p) for k, p in self.plan.genesis])
        for h in range(1, height + 1):
            app.deliver_block(self.plan.txs[h])
        return app


def delivery_order(seed: int, height: int, vtype: int,
                   indices: List[int]) -> List[int]:
    """The order a gossiping peer hands over a height's votes of one type:
    a permutation the seed draws for that height and type."""
    order = list(indices)
    random.Random(seed * 1_000_003 + height * 31 + vtype).shuffle(order)
    return order


def make_block(spec: StakingSpec, plan: Plan, h: int, tip: rb.Tip,
               last: Optional[rc.ValSet]) -> rb.Block:
    """state/state.go MakeBlock at height h by its proposer: the set of h
    and of h + 1 hashed into the header, the time the weighted median of
    the LastCommit under ``last``, the set that signed it."""
    p = spec.params()
    vals, nxt = plan.sets[h].view(plan.keys), plan.sets[h + 1].view(plan.keys)
    if h == 1:
        last_commit = rc.CommitData(spec.chain_id, 0, 0, b"", 0, b"", [])
        time_ns = spec.genesis_time_ns
    else:
        last_commit = tip.commit
        time_ns = rb.median_time(last, last_commit)
    return rb.Block(
        height=h, time_ns=time_ns, last_block_id=tip.block_id,
        last_commit=last_commit, txs=plan.txs[h],
        validators_hash=rb.validators_hash(vals),
        next_validators_hash=rb.validators_hash(nxt),
        consensus_hash=p.consensus_hash(), app_hash=tip.app_hash,
        last_results_hash=tip.last_results_hash,
        proposer=vals.addrs[plan.sets[h].proposer], chain_id=spec.chain_id,
        app_version=p.app_version, block_version=p.block_version,
    ).seal(last if last is not None else vals)


def make_chain(spec: StakingSpec, n_heights: int, workers: int = 1) -> Chain:
    plan = choose_node(spec, n_heights)
    keys = plan.keys
    signers = Signers(spec, keys, workers)
    try:
        tips = [rb.Tip(time_ns=spec.genesis_time_ns)]
        heights: List[HeightData] = []
        last = None
        for h in range(1, n_heights + 1):
            s = plan.sets[h]
            vals = s.view(keys)
            b = make_block(spec, plan, h, tips[-1], last)
            node = s.index_of(plan.node_key)
            members = [(i, m.key) for i, m in enumerate(s.members)
                       if i != node]
            signed = signers.sign(signers.jobs(rr.PRECOMMIT, h, b.id,
                                               b.time_ns, members))
            sigs = [(rc.ABSENT, 0, b"")] * len(s.members)
            wires = {}
            for i, sig, wire in signed:
                sigs[i] = (rc.COMMIT, rr.vote_time(rr.PRECOMMIT, b.time_ns,
                                                   i), sig)
                wires[i] = wire
            commit = rc.CommitData(spec.chain_id, h, 0, b.id[0], b.id[1],
                                   b.id[2], sigs)
            co = [i for i, _k in members]
            order = delivery_order(spec.seed, h, rr.PRECOMMIT, co)
            heights.append(HeightData(
                b, vals, s.proposer,
                rr.proposal_wire(vals, spec.chain_id, b, s.proposer),
                rr.part_wires(b), [], [wires[i] for i in order], [], order,
                commit))
            tips.append(rb.advance(tips[-1], b, commit))
            last = vals
        # the prevotes depend on nothing but the block ids
        for hd in heights:
            h = hd.block.height
            s = plan.sets[h]
            node = s.index_of(plan.node_key)
            members = [(i, m.key) for i, m in enumerate(s.members)
                       if i != node]
            wires = {i: wire for i, _sig, wire in signers.sign(signers.jobs(
                rr.PREVOTE, h, hd.block.id, hd.block.time_ns, members))}
            hd.prevote_order = delivery_order(spec.seed, h, rr.PREVOTE,
                                              [i for i, _k in members])
            hd.prevotes = [wires[i] for i in hd.prevote_order]
    finally:
        signers.close()
    return Chain(spec, plan, heights, tips)


def vote_set_power(vals: rc.ValSet, indices) -> int:
    """The power of the validators at ``indices`` of a set: what a vote
    set holding their votes has tallied."""
    return sum(vals.powers[i] for i in indices)


def starved_prefix(vals: rc.ValSet, order: List[int], node: int) -> int:
    """The longest prefix of a precommit order that, with the node's own
    precommit, holds at most 2/3 of the power: one more precommit and the
    block commits."""
    needed = vals.total_power * 2 // 3
    power = vals.powers[node]
    for k, i in enumerate(order):
        power += vals.powers[i]
        if power > needed:
            return k
    return len(order)

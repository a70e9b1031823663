"""Validator and ValidatorSet (reference: types/validator.go,
types/validator_set.go).

ValidatorSet reproduces the reference's observable behavior — proposer
priority rotation (IncrementProposerPriority, validator_set.go:116),
rescale/centering, UpdateWithChangeSet merge semantics
(validator_set.go:591), ordering by (voting power desc, address asc)
(validator_set.go:906), and the SimpleValidator merkle hash
(validator_set.go:347) — with one architectural difference: all commit
verification (VerifyCommit :667, VerifyCommitLight :722,
VerifyCommitLightTrusting :775) is **batch-first**, collecting every
signature into a crypto.BatchVerifier so full 10k-validator commits verify
as one TPU dispatch instead of a serial CPU loop.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from tmtpu.crypto.encoding import pubkey_from_proto, pubkey_to_proto
from tmtpu.crypto.keys import PubKey
from tmtpu.crypto.merkle import hash_from_byte_slices
from tmtpu.libs import metrics as _m
from tmtpu.types import pb

MAX_TOTAL_VOTING_POWER = (1 << 63) // 8  # types/validator_set.go:17
PRIORITY_WINDOW_SIZE_FACTOR = 2

_I64_MAX = (1 << 63) - 1
_I64_MIN = -(1 << 63)


def _clip(v: int) -> int:
    return max(_I64_MIN, min(_I64_MAX, v))


class Validator:
    __slots__ = ("address", "pub_key", "voting_power", "proposer_priority")

    def __init__(self, pub_key: PubKey, voting_power: int,
                 proposer_priority: int = 0, address: Optional[bytes] = None):
        self.pub_key = pub_key
        self.address = address if address is not None else pub_key.address()
        self.voting_power = int(voting_power)
        self.proposer_priority = int(proposer_priority)

    def copy(self) -> "Validator":
        return Validator(self.pub_key, self.voting_power,
                         self.proposer_priority, self.address)

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """Higher priority wins; ties broken by lower address
        (validator.go CompareProposerPriority)."""
        if other is None:
            return self
        if self.proposer_priority != other.proposer_priority:
            return self if self.proposer_priority > other.proposer_priority else other
        if self.address < other.address:
            return self
        if self.address > other.address:
            return other
        raise ValueError("cannot compare identical validators")

    def validate_basic(self) -> None:
        if self.pub_key is None:
            raise ValueError("validator has nil pubkey")
        if self.voting_power < 0:
            raise ValueError("validator has negative voting power")
        if len(self.address) != 20:
            raise ValueError("validator address is wrong size")

    def bytes(self) -> bytes:
        """SimpleValidator proto encoding — the merkle leaf for
        ValidatorSet.Hash (validator.go:117-133)."""
        return pb.SimpleValidator(
            pub_key=pubkey_to_proto(self.pub_key),
            voting_power=self.voting_power,
        ).encode()

    def to_proto(self) -> pb.Validator:
        return pb.Validator(
            address=self.address,
            pub_key=pubkey_to_proto(self.pub_key),
            voting_power=self.voting_power,
            proposer_priority=self.proposer_priority,
        )

    @classmethod
    def from_proto(cls, m: pb.Validator) -> "Validator":
        return cls(pubkey_from_proto(m.pub_key), m.voting_power,
                   m.proposer_priority, bytes(m.address))

    def __eq__(self, other):
        return (isinstance(other, Validator) and self.address == other.address
                and self.pub_key == other.pub_key
                and self.voting_power == other.voting_power
                and self.proposer_priority == other.proposer_priority)

    def __repr__(self):
        return (f"Validator{{{self.address.hex().upper()[:12]} "
                f"VP:{self.voting_power} A:{self.proposer_priority}}}")


def _sorted_by_power(vals: List[Validator]) -> List[Validator]:
    # (voting power desc, address asc) — validator_set.go:906
    return sorted(vals, key=lambda v: (-v.voting_power, v.address))


class ValidatorSet:
    def __init__(self, validators: Optional[List[Validator]] = None):
        self.validators: List[Validator] = []
        self.proposer: Optional[Validator] = None
        self._total_voting_power = 0
        # "hash" / "encode" -> (content, bytes) of the last such call: the
        # bytes are handed back only while the content read at the call
        # equals the content they were computed from (_kept), so no
        # mutator has anything to clear
        self._memo: dict = {}
        if validators:
            self._update_with_change_set(
                [v.copy() for v in validators], allow_deletes=False
            )
            self.increment_proposer_priority(1)

    # -- basic accessors ----------------------------------------------------

    def size(self) -> int:
        return len(self.validators)

    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def has_address(self, address: bytes) -> bool:
        return any(v.address == address for v in self.validators)

    def get_by_address(self, address: bytes) -> Tuple[int, Optional[Validator]]:
        for i, v in enumerate(self.validators):
            if v.address == address:
                return i, v.copy()
        return -1, None

    def get_by_index(self, index: int) -> Tuple[Optional[bytes], Optional[Validator]]:
        if index < 0 or index >= len(self.validators):
            return None, None
        v = self.validators[index]
        return v.address, v.copy()

    def total_voting_power(self) -> int:
        if self._total_voting_power == 0:
            self._update_total_voting_power()
        return self._total_voting_power

    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total += v.voting_power
            if total > MAX_TOTAL_VOTING_POWER:
                raise OverflowError(
                    "total voting power exceeds MaxTotalVotingPower"
                )
        self._total_voting_power = total

    def copy(self) -> "ValidatorSet":
        vs = ValidatorSet()
        vs.validators = [v.copy() for v in self.validators]
        vs.proposer = self.proposer.copy() if self.proposer else None
        vs._total_voting_power = self._total_voting_power
        # the copies share each PubKey and address object, so the copy's
        # content check is identity comparisons until it is mutated
        vs._memo = dict(self._memo)
        return vs

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValueError("validator set is nil or empty")
        for v in self.validators:
            v.validate_basic()
        if self.proposer is None:
            raise ValueError("proposer failed validate basic: nil")
        self.proposer.validate_basic()

    # -- proposer priority machinery ---------------------------------------

    def increment_proposer_priority(self, times: int) -> None:
        """validator_set.go:116 — rescale, center, then rotate ``times``."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("times must be positive")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority + v.voting_power)
        mostest = self._get_val_with_most_priority()
        mostest.proposer_priority = _clip(
            mostest.proposer_priority - self.total_voting_power()
        )
        return mostest

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_proposer_priority(times)
        return c

    def rescale_priorities(self, diff_max: int) -> None:
        """Cap max-min priority spread at diff_max by integer division
        (validator_set.go:143)."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if diff_max <= 0:
            return
        diff = self._max_min_priority_diff()
        if diff > diff_max:
            ratio = (diff + diff_max - 1) // diff_max
            for v in self.validators:
                # Go integer division truncates toward zero.
                q = abs(v.proposer_priority) // ratio
                v.proposer_priority = q if v.proposer_priority >= 0 else -q

    def _max_min_priority_diff(self) -> int:
        prios = [v.proposer_priority for v in self.validators]
        return abs(max(prios) - min(prios))

    def _compute_avg_proposer_priority(self) -> int:
        n = len(self.validators)
        s = sum(v.proposer_priority for v in self.validators)
        # Go big.Int.Div with positive divisor floors, same as Python //.
        return s // n

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority - avg)

    def _get_val_with_most_priority(self) -> Validator:
        res = None
        for v in self.validators:
            res = v.compare_proposer_priority(res) if res else v
        return res

    def get_proposer(self) -> Optional[Validator]:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        proposer = None
        for v in self.validators:
            if proposer is None or v.address != proposer.address:
                proposer = v.compare_proposer_priority(proposer) if proposer else v
        return proposer

    # -- updates (validator_set.go:591 updateWithChangeSet) -----------------

    def update_with_change_set(self, changes: List[Validator]) -> Dict[str, int]:
        """validator_set.go:591 UpdateWithChangeSet -> how many of the
        changes were a member's new power, a join and a leave."""
        return self._update_with_change_set([v.copy() for v in changes],
                                            allow_deletes=True)

    def _update_with_change_set(self, changes: List[Validator],
                                allow_deletes: bool) -> Dict[str, int]:
        if not changes:
            return {"power": 0, "join": 0, "leave": 0}
        # split & validate changes (processChanges)
        by_addr = {}
        for c in sorted(changes, key=lambda v: v.address):
            if c.address in by_addr:
                raise ValueError(f"duplicate entry {c.address.hex()} in changes")
            if c.voting_power < 0:
                raise ValueError("voting power cannot be negative")
            if c.voting_power > MAX_TOTAL_VOTING_POWER:
                raise ValueError("voting power exceeds maximum")
            by_addr[c.address] = c
        updates = [c for c in by_addr.values() if c.voting_power > 0]
        deletes = [c for c in by_addr.values() if c.voting_power == 0]
        if not allow_deletes and deletes:
            raise ValueError("cannot process validators with voting power 0")
        # the members by address, once: GetByAddress a change is a scan of
        # the set in Go, 10,000 compares each at the protocol's cap
        members = {v.address: v for v in self.validators}
        num_new = sum(1 for u in updates if u.address not in members)
        if num_new == 0 and len(self.validators) == len(deletes):
            raise ValueError("applying the validator changes would result in empty set")
        # verifyRemovals
        removed_power = 0
        for d in deletes:
            val = members.get(d.address)
            if val is None:
                raise ValueError(f"failed to find validator {d.address.hex()} to remove")
            removed_power += val.voting_power
        # verifyUpdates: total power after updates (before removals)
        delta = 0
        for u in updates:
            old = members.get(u.address)
            delta += u.voting_power - (old.voting_power if old else 0)
        tvp_after_updates = self.total_voting_power() + delta if self.validators \
            else delta
        if tvp_after_updates > MAX_TOTAL_VOTING_POWER:
            raise OverflowError("total voting power would exceed maximum")
        # computeNewPriorities: new validators start deep negative
        for u in updates:
            old = members.get(u.address)
            if old is None:
                u.proposer_priority = -(tvp_after_updates + (tvp_after_updates >> 3))
            else:
                u.proposer_priority = old.proposer_priority
        # applyUpdates: address-sorted merge, updates win
        merged = dict(members)
        for u in updates:
            merged[u.address] = u
        for d in deletes:
            merged.pop(d.address, None)
        self.validators = [merged[a] for a in sorted(merged)]
        self._total_voting_power = 0
        self._update_total_voting_power()
        self.rescale_priorities(
            PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        )
        self._shift_by_avg_proposer_priority()
        self.validators = _sorted_by_power(self.validators)
        return {"power": len(updates) - num_new, "join": num_new,
                "leave": len(deletes)}

    # -- hashing / proto ----------------------------------------------------

    def _kept(self, what: str, content, compute) -> bytes:
        """``compute()``, or the bytes it gave when ``content`` was last
        what it is now."""
        memo = self._memo.get(what)
        if memo is not None and memo[0] == content:
            _m.types_valset_memo_hits.inc(what=what)
            return memo[1]
        _m.types_valset_memo_misses.inc(what=what)
        value = compute()
        self._memo[what] = (content, value)
        return value

    def hash(self) -> bytes:
        """Merkle root over the SimpleValidator leaves (validator_set.go:347),
        kept while the (public key, voting power) sequence it covers is
        what it was: priorities do not enter it."""
        return self._kept(
            "hash", [(v.pub_key, v.voting_power) for v in self.validators],
            lambda: hash_from_byte_slices(
                [v.bytes() for v in self.validators]))

    def encode(self) -> bytes:
        """``to_proto().encode()``, kept while everything those bytes hold
        is what it was: each validator's address, key, power and priority,
        the proposer's, the total."""
        p = self.proposer
        return self._kept("encode", (
            self.total_voting_power(),
            (p.address, p.pub_key, p.voting_power, p.proposer_priority)
            if p else None,
            [(v.address, v.pub_key, v.voting_power, v.proposer_priority)
             for v in self.validators],
        ), lambda: self.to_proto().encode())

    def to_proto(self) -> pb.ValidatorSet:
        return pb.ValidatorSet(
            validators=[v.to_proto() for v in self.validators],
            proposer=self.proposer.to_proto() if self.proposer else None,
            total_voting_power=self.total_voting_power(),
        )

    @classmethod
    def restore(cls, validators: List[Validator],
                proposer: Optional[Validator] = None) -> "ValidatorSet":
        """Rebuild a set from already-ordered validators carrying their
        proposer priorities (RPC /validators, light provider) — no re-sort,
        no priority reset, so hash() matches the originating node's set."""
        vs = cls()
        vs.validators = [v.copy() for v in validators]
        vs.proposer = proposer.copy() if proposer else \
            (vs._get_val_with_most_priority() if vs.validators else None)
        vs._update_total_voting_power()
        return vs

    @classmethod
    def from_proto(cls, m: pb.ValidatorSet) -> "ValidatorSet":
        vs = cls()
        vs.validators = [Validator.from_proto(v) for v in m.validators]
        vs.proposer = Validator.from_proto(m.proposer) if m.proposer else None
        vs._update_total_voting_power()
        return vs

    def __eq__(self, other):
        return (isinstance(other, ValidatorSet)
                and self.validators == other.validators)

    def __repr__(self):
        return f"ValidatorSet{{T:{self.total_voting_power()} {self.validators}}}"

    # -- commit verification (batch-first) ----------------------------------
    # See tmtpu/types/commit_verify.py — implemented there to avoid a module
    # cycle with block.py; bound onto this class at import time.

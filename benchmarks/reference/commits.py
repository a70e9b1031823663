"""Commits over a validator set, made from a seed, and the plain serial
reference of ``VerifyCommit`` (types/validator_set.go:667) they are judged
by. Signing and verifying go through ``cryptography`` (OpenSSL); the
canonical sign-bytes are encoded here from the protobuf definition
(proto/tendermint/types/canonical.proto). Nothing here imports ``tmtpu``.
"""

from __future__ import annotations

import hashlib
import random
from dataclasses import dataclass
from typing import List, Optional, Tuple

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey, Ed25519PublicKey)

ABSENT, COMMIT, NIL = 1, 2, 3          # types/block.go BlockIDFlag
PRECOMMIT = 2                          # SignedMsgType


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field_bytes(num: int, body: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(body)) + body


def _sfixed64(num: int, v: int) -> bytes:
    return _varint(num << 3 | 1) + v.to_bytes(8, "little", signed=True) \
        if v else b""


def vote_sign_bytes(chain_id: str, height: int, round_: int,
                    block_hash: bytes, parts_total: int, parts_hash: bytes,
                    timestamp_ns: int, nil: bool = False) -> bytes:
    """Length-delimited CanonicalVote of a precommit. proto3: zero
    scalars are left out; the timestamp is a non-nullable message and is
    always written; a nil vote has no block id."""
    body = _varint(1 << 3) + _varint(PRECOMMIT)
    body += _sfixed64(2, height) + _sfixed64(3, round_)
    if not nil:
        psh = (_varint(1 << 3) + _varint(parts_total) if parts_total
               else b"") + _field_bytes(2, parts_hash)
        body += _field_bytes(4, _field_bytes(1, block_hash)
                             + _field_bytes(2, psh))
    secs, nanos = divmod(timestamp_ns, 10**9)
    ts = (_varint(1 << 3) + _varint(secs) if secs else b"") + \
        (_varint(2 << 3) + _varint(nanos) if nanos else b"")
    body += _field_bytes(5, ts)
    body += _field_bytes(6, chain_id.encode())
    return _varint(len(body)) + body


@dataclass
class ValSet:
    """Validators in the set's own order: voting power descending, then
    address ascending (types/validator_set.go ValidatorsByVotingPower)."""
    privs: List[Ed25519PrivateKey]
    pubs: List[bytes]
    pub_objs: List[Ed25519PublicKey]
    addrs: List[bytes]
    powers: List[int]

    @property
    def total_power(self) -> int:
        return sum(self.powers)


def make_valset(seed: int, n: int, power: int = 1) -> ValSet:
    rows = []
    for i in range(n):
        sk = Ed25519PrivateKey.from_private_bytes(
            hashlib.sha256(b"bench-val-%d-%d" % (seed, i)).digest())
        pub = sk.public_key().public_bytes_raw()
        rows.append((hashlib.sha256(pub).digest()[:20], pub, sk))
    rows.sort(key=lambda r: r[0])
    return ValSet([r[2] for r in rows], [r[1] for r in rows],
                  [r[2].public_key() for r in rows],
                  [r[0] for r in rows], [power] * n)


@dataclass
class CommitData:
    chain_id: str
    height: int
    round: int
    block_hash: bytes
    parts_total: int
    parts_hash: bytes
    # per validator slot: (flag, timestamp_ns, signature)
    sigs: List[Tuple[int, int, bytes]]

    def sign_bytes(self, idx: int) -> bytes:
        flag, ts, _sig = self.sigs[idx]
        return vote_sign_bytes(self.chain_id, self.height, self.round,
                               self.block_hash, self.parts_total,
                               self.parts_hash, ts, nil=flag == NIL)

    def present(self) -> int:
        return sum(1 for f, _t, _s in self.sigs if f != ABSENT)


def make_commit(vals: ValSet, seed: int, k: int, chain_id: str,
                n_absent: int, n_nil: int = 0) -> CommitData:
    """Commit number ``k`` of a seed. The seed chooses WHICH validators
    are absent (or vote nil), never how many: every commit holds
    ``len(vals) - n_absent`` signatures."""
    rng = random.Random(seed * 1_000_003 + k)
    n = len(vals.pubs)
    out_of = rng.sample(range(n), n_absent + n_nil)
    absent, nil = set(out_of[:n_absent]), set(out_of[n_absent:])
    c = CommitData(chain_id, 1_000 + k, 0,
                   hashlib.sha256(b"bench-block-%d-%d" % (seed, k)).digest(),
                   1, hashlib.sha256(b"bench-parts-%d-%d" % (seed, k)).digest(),
                   [])
    base = 1_700_000_000 * 10**9 + k * 10**9
    for i in range(n):
        if i in absent:
            c.sigs.append((ABSENT, 0, b""))
            continue
        c.sigs.append((NIL if i in nil else COMMIT, base + i, b""))
        c.sigs[i] = (c.sigs[i][0], base + i,
                     vals.privs[i].sign(c.sign_bytes(i)))
    return c


def tamper_signature(c: CommitData, idx: int) -> CommitData:
    """A copy with one bit of validator ``idx``'s signature flipped."""
    flag, ts, sig = c.sigs[idx]
    if flag == ABSENT:
        raise ValueError("cannot tamper with an absent slot")
    bad = bytearray(sig)
    bad[7] ^= 0x10
    sigs = list(c.sigs)
    sigs[idx] = (flag, ts, bytes(bad))
    return CommitData(c.chain_id, c.height, c.round, c.block_hash,
                      c.parts_total, c.parts_hash, sigs)


# An outcome is what a caller of VerifyCommit can tell apart:
# ("ok",), ("bad_sig", lane) with ``lane`` counted over the present
# signatures, or ("low_power", got, needed).
Outcome = Tuple


def verify_commit(vals: ValSet, c: CommitData,
                  stop_at_quorum: bool = False) -> Outcome:
    """validator_set.go:667: every signature that is present is verified,
    power is tallied over the votes for the block, and more than 2/3 of
    the total has to be there.

    ``stop_at_quorum`` is the CONTROL, not the reference: it returns as
    soon as 2/3 is tallied (VerifyCommitLight's early exit), which breaks
    the configuration's guarantee that one bad signature anywhere refuses
    the commit."""
    needed = vals.total_power * 2 // 3
    tallied = 0
    lane = -1
    first_bad: Optional[int] = None
    for idx, (flag, _ts, sig) in enumerate(c.sigs):
        if flag == ABSENT:
            continue
        lane += 1
        try:
            vals.pub_objs[idx].verify(sig, c.sign_bytes(idx))
        except (InvalidSignature, ValueError):
            if first_bad is None:
                first_bad = lane
            if stop_at_quorum:
                return ("bad_sig", lane)
            continue
        if flag == COMMIT:
            tallied += vals.powers[idx]
        if stop_at_quorum and tallied > needed:
            return ("ok",)
    if first_bad is not None:
        return ("bad_sig", first_bad)
    if tallied <= needed:
        return ("low_power", tallied, needed)
    return ("ok",)

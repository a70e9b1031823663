"""Batch-first commit verification — the framework's replacement for the
reference's serial loops in types/validator_set.go:667 (VerifyCommit),
:722 (VerifyCommitLight) and :775 (VerifyCommitLightTrusting).

Design: instead of verifying signature-by-signature and early-exiting, all
relevant (pubkey, sign-bytes, signature) triples are collected into one
crypto.BatchVerifier — a single TPU dispatch for a full 10k-validator
commit. Semantics preserved:

- VerifyCommit checks EVERY non-absent signature (the reference documents
  why: ABCI LastCommitInfo incentivization needs the full mask) and tallies
  only BlockIDFlagCommit votes toward the +2/3 threshold;
- VerifyCommitLight/Trusting only need +2/3 of tallied power; the batch
  path verifies all candidate sigs at once (cheaper on TPU than two
  round-trips) and tallies the valid ones — any invalid signature still
  fails the call, which is strictly stricter than the reference's
  early-exit, never weaker: a commit accepted here is accepted there.

Sign bytes: every entry asks the commit once a call for
``Commit.vote_sign_bytes_for(chain_id)`` and hands it each CommitSig. What
a commit's precommits share (type, height, round, block id, chain id) is
encoded once there and each signature fills in its timestamp; nothing is
kept on the Commit, so a call starts from the CommitSigs as they are.

Bound onto ValidatorSet at import (kept separate to avoid a module cycle
between validator.py and block.py).
"""

from __future__ import annotations

from typing import Optional

from tmtpu.crypto import batch as crypto_batch
from tmtpu.libs import trace
from tmtpu.types.block import BlockID, Commit
from tmtpu.types.validator import ValidatorSet


class VerificationError(Exception):
    pass


class ErrNotEnoughVotingPowerSigned(VerificationError):
    def __init__(self, got: int, needed: int):
        super().__init__(
            f"invalid commit -- insufficient voting power: got {got}, "
            f"needed more than {needed}"
        )
        self.got = got
        self.needed = needed


def _check_commit_basics(vals: ValidatorSet, commit: Commit, height: int,
                         block_id: Optional[BlockID],
                         check_size: bool = True) -> None:
    if commit is None:
        raise VerificationError("nil commit")
    if check_size and vals.size() != len(commit.signatures):
        raise VerificationError(
            f"Invalid commit -- wrong set size: {vals.size()} vs "
            f"{len(commit.signatures)}"
        )
    if height != commit.height:
        raise VerificationError(
            f"Invalid commit -- wrong height: {height} vs {commit.height}"
        )
    if block_id is not None and block_id != commit.block_id:
        raise VerificationError(
            f"Invalid commit -- wrong block ID: want {block_id}, got "
            f"{commit.block_id}"
        )


def verify_commit(vals: ValidatorSet, chain_id: str, block_id: BlockID,
                  height: int, commit: Commit,
                  backend: Optional[str] = None, min_lanes: int = 0) -> None:
    """validator_set.go:667 — all signatures must be valid; tallied power of
    BlockIDFlagCommit votes must exceed 2/3 of total. ``min_lanes`` pins
    the flush's device shape (a node's ``validate_block`` gives the set's
    own, which it warmed: whatever the sigcache leaves of a LastCommit
    then meets a compiled shape)."""
    _check_commit_basics(vals, commit, height, block_id)
    with trace.span("commit_verify.verify_commit", height=height,
                    sigs=len(commit.signatures)):
        bv = crypto_batch.new_batch_verifier(backend, min_lanes=min_lanes)
        with trace.span("commit_verify.collect"):
            sign_bytes = commit.vote_sign_bytes_for(chain_id)
            for idx, cs in enumerate(commit.signatures):
                if cs.is_absent():
                    continue
                # Verification is purely by index; sign bytes don't include
                # the validator address (validator_set.go:692 does no
                # address check). Power rides the batch so the +2/3 tally
                # comes back fused from the device: only BlockIDFlagCommit
                # votes count toward the threshold.
                val = vals.validators[idx]
                bv.add(val.pub_key, sign_bytes(cs), cs.signature,
                       power=val.voting_power if cs.for_block() else 0)
        all_ok, mask, tallied = bv.verify_tally()
    if not all_ok:
        raise VerificationError(f"wrong signature (#{mask.index(False)})")
    needed = vals.total_voting_power() * 2 // 3
    if tallied <= needed:
        raise ErrNotEnoughVotingPowerSigned(tallied, needed)


def verify_commit_light(vals: ValidatorSet, chain_id: str, block_id: BlockID,
                        height: int, commit: Commit,
                        backend: Optional[str] = None) -> None:
    """validator_set.go:722 — only BlockIDFlagCommit sigs count and need
    verifying; +2/3 of total power must have signed the block."""
    _check_commit_basics(vals, commit, height, block_id)
    with trace.span("commit_verify.verify_commit_light", height=height,
                    sigs=len(commit.signatures)):
        bv = crypto_batch.new_batch_verifier(backend)
        with trace.span("commit_verify.collect"):
            sign_bytes = commit.vote_sign_bytes_for(chain_id)
            for idx, cs in enumerate(commit.signatures):
                if not cs.for_block():
                    continue
                val = vals.validators[idx]
                bv.add(val.pub_key, sign_bytes(cs), cs.signature,
                       power=val.voting_power)
        all_ok, mask, tallied = bv.verify_tally()
    if not all_ok:
        raise VerificationError("wrong signature in commit")
    needed = vals.total_voting_power() * 2 // 3
    if tallied <= needed:
        raise ErrNotEnoughVotingPowerSigned(tallied, needed)


def verify_commit_light_trusting(vals: ValidatorSet, chain_id: str,
                                 commit: Commit, trust_num: int,
                                 trust_den: int,
                                 backend: Optional[str] = None) -> None:
    """validator_set.go:775 — for the light client's skipping verification:
    validators are looked up by ADDRESS (indices may differ between the
    trusted set and the commit's set); tallied power must exceed
    trust_num/trust_den (default 1/3) of the trusted total."""
    if trust_den <= 0 or trust_num <= 0:
        raise VerificationError("trustLevel must be positive")
    if commit is None:
        raise VerificationError("nil commit")
    with trace.span("commit_verify.verify_commit_light_trusting",
                    sigs=len(commit.signatures)):
        bv = crypto_batch.new_batch_verifier(backend)
        seen = set()
        # one O(n) index instead of an O(n) scan per signature (10k x 10k
        # address comparisons would dwarf the batch dispatch)
        by_address = {v.address: (i, v)
                      for i, v in enumerate(vals.validators)}
        with trace.span("commit_verify.collect"):
            sign_bytes = commit.vote_sign_bytes_for(chain_id)
            for cs in commit.signatures:
                if not cs.for_block():
                    continue
                entry = by_address.get(cs.validator_address)
                if entry is None:
                    continue  # unknown validator: not in the trusted set
                val_idx, val = entry
                if val_idx in seen:
                    raise VerificationError(
                        f"double vote from validator "
                        f"{cs.validator_address.hex()}"
                    )
                seen.add(val_idx)
                bv.add(val.pub_key, sign_bytes(cs), cs.signature,
                       power=val.voting_power)
        all_ok, mask, tallied = bv.verify_tally()
    if not all_ok:
        raise VerificationError("wrong signature in commit")
    needed = vals.total_voting_power() * trust_num // trust_den
    if tallied <= needed:
        raise ErrNotEnoughVotingPowerSigned(tallied, needed)


def verify_commits_light_batch(entries, backend=None, min_lanes: int = 0):
    """Verify MANY blocks' commits in one batch dispatch — the fast-sync
    fused path (new vs the reference, which runs VerifyCommitLight per block
    in blockchain/v0/reactor.go:366). ``entries`` is a list of
    (vals, chain_id, block_id, height, commit); all for-block signatures
    across all entries ride a single BatchVerifier (one TPU dispatch for a
    whole run of fetched blocks), then per-entry +2/3 thresholds are checked
    against the mask segments. ``min_lanes`` pins the dispatch's shape
    (crypto/batch.py ``BatchVerifier``): the caller gives the lanes of the
    longest run it makes, and a shorter one pads to the same shape.

    Returns a list the same length as ``entries``: None for a verified
    commit, or the VerificationError for that entry (so fast sync can apply
    the verified prefix and re-request exactly the failing block).
    """
    with trace.span("commit_verify.verify_commits_light_batch",
                    commits=len(entries)):
        bv = crypto_batch.new_batch_verifier(backend, min_lanes=min_lanes)
        segments = []  # (start, count, tallied, needed, pre_err)
        with trace.span("commit_verify.collect"):
            for vals, chain_id, block_id, height, commit in entries:
                start = bv.count()
                try:
                    _check_commit_basics(vals, commit, height, block_id)
                except VerificationError as e:
                    segments.append((start, 0, 0, 0, e))
                    continue
                tallied = 0
                sign_bytes = commit.vote_sign_bytes_for(chain_id)
                for idx, cs in enumerate(commit.signatures):
                    if not cs.for_block():
                        continue
                    val = vals.validators[idx]
                    bv.add(val.pub_key, sign_bytes(cs), cs.signature)
                    tallied += val.voting_power
                segments.append((start, bv.count() - start, tallied,
                                 vals.total_voting_power() * 2 // 3, None))
        _, mask = bv.verify()
    out = []
    for start, count, tallied, needed, pre_err in segments:
        if pre_err is not None:
            out.append(pre_err)
        elif not all(mask[start:start + count]):
            out.append(VerificationError("wrong signature in commit"))
        elif tallied <= needed:
            out.append(ErrNotEnoughVotingPowerSigned(tallied, needed))
        else:
            out.append(None)
    return out


# Bind as methods.
ValidatorSet.verify_commit = (
    lambda self, chain_id, block_id, height, commit, backend=None,
    min_lanes=0:
    verify_commit(self, chain_id, block_id, height, commit, backend,
                  min_lanes)
)
ValidatorSet.verify_commit_light = (
    lambda self, chain_id, block_id, height, commit, backend=None:
    verify_commit_light(self, chain_id, block_id, height, commit, backend)
)
ValidatorSet.verify_commit_light_trusting = (
    lambda self, chain_id, commit, trust_num=1, trust_den=3, backend=None:
    verify_commit_light_trusting(self, chain_id, commit, trust_num,
                                 trust_den, backend)
)

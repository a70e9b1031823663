"""Pure light-client verification (reference: light/verifier.go).

VerifyAdjacent (:93) and VerifyNonAdjacent (:32) re-expressed batch-first:
each hop costs exactly one fused BatchVerifier dispatch through
verify_commit_light / verify_commit_light_trusting (two for non-adjacent),
so a 10k-validator hop is one TPU launch instead of 10k serial verifies.

``verify_adjacent_run`` is new vs the reference: a whole run of adjacent
headers (sequential sync over N blocks) verifies in ONE device dispatch via
types.commit_verify.verify_commits_light_batch.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from tmtpu.libs import trace
from tmtpu.types import commit_verify
from tmtpu.types.light_block import LightBlock, SignedHeader
from tmtpu.types.validator import ValidatorSet

# light/verifier.go:16 DefaultTrustLevel — one correct validator suffices
DEFAULT_TRUST_LEVEL = (1, 3)


class LightError(Exception):
    pass


class ErrOldHeaderExpired(LightError):
    def __init__(self, expired_at_ns: int, now_ns: int):
        super().__init__(
            f"old header expired at {expired_at_ns} (now: {now_ns})")
        self.expired_at_ns = expired_at_ns
        self.now_ns = now_ns


class ErrInvalidHeader(LightError):
    def __init__(self, reason):
        super().__init__(f"invalid header: {reason}")
        self.reason = reason


class ErrNewValSetCantBeTrusted(LightError):
    """<1/3 of the trusted validators signed the new header
    (light/verifier.go ErrNewValSetCantBeTrusted)."""

    def __init__(self, reason):
        super().__init__(f"cant trust new val set: {reason}")
        self.reason = reason


class ErrVerificationFailed(LightError):
    """light/errors.go ErrVerificationFailed — the hop ``from_height`` →
    ``to_height`` was refused for ``reason`` (the hop's own error)."""

    def __init__(self, from_height: int, to_height: int, reason):
        super().__init__(f"verify from #{from_height} to #{to_height} "
                         f"failed: {reason}")
        self.from_height = from_height
        self.to_height = to_height
        self.reason = reason


def validate_trust_level(num: int, den: int) -> None:
    """verifier.go:195 ValidateTrustLevel — must be within [1/3, 1]."""
    if num * 3 < den or num > den or den == 0:
        raise LightError(f"trustLevel must be within [1/3, 1], given "
                         f"{num}/{den}")


def header_expired(h: SignedHeader, trusting_period_ns: int,
                   now_ns: int) -> bool:
    """verifier.go:209 HeaderExpired."""
    return h.header.time + trusting_period_ns <= now_ns


def _verify_new_header_and_vals(untrusted: SignedHeader,
                                untrusted_vals: ValidatorSet,
                                trusted: SignedHeader, now_ns: int,
                                max_clock_drift_ns: int) -> None:
    """verifier.go:153 verifyNewHeaderAndVals."""
    untrusted.validate_basic(trusted.header.chain_id)
    if untrusted.header.height <= trusted.header.height:
        raise ValueError(
            f"expected new header height {untrusted.header.height} to be "
            f"greater than old header height {trusted.header.height}")
    if untrusted.header.time <= trusted.header.time:
        raise ValueError(
            f"expected new header time {untrusted.header.time} to be after "
            f"old header time {trusted.header.time}")
    if untrusted.header.time >= now_ns + max_clock_drift_ns:
        raise ValueError(
            f"new header has a time from the future {untrusted.header.time} "
            f"(now: {now_ns}, max drift: {max_clock_drift_ns})")
    if untrusted.header.validators_hash != untrusted_vals.hash():
        raise ValueError(
            f"expected new header validators "
            f"({untrusted.header.validators_hash.hex().upper()}) to match "
            f"those supplied ({untrusted_vals.hash().hex().upper()}) at "
            f"height {untrusted.header.height}")


def verify_adjacent(trusted: SignedHeader, untrusted: SignedHeader,
                    untrusted_vals: ValidatorSet, trusting_period_ns: int,
                    now_ns: int, max_clock_drift_ns: int,
                    backend: Optional[str] = None) -> None:
    """verifier.go:93 VerifyAdjacent — height X → X+1."""
    if untrusted.header.height != trusted.header.height + 1:
        raise LightError("headers must be adjacent in height")
    if header_expired(trusted, trusting_period_ns, now_ns):
        raise ErrOldHeaderExpired(
            trusted.header.time + trusting_period_ns, now_ns)
    try:
        _verify_new_header_and_vals(untrusted, untrusted_vals, trusted,
                                    now_ns, max_clock_drift_ns)
    except ValueError as e:
        raise ErrInvalidHeader(e) from e
    if untrusted.header.validators_hash != trusted.header.next_validators_hash:
        raise LightError(
            f"expected old header next validators "
            f"({trusted.header.next_validators_hash.hex().upper()}) to match "
            f"those from new header "
            f"({untrusted.header.validators_hash.hex().upper()})")
    try:
        commit_verify.verify_commit_light(
            untrusted_vals, trusted.header.chain_id,
            untrusted.commit.block_id, untrusted.header.height,
            untrusted.commit, backend=backend)
    except commit_verify.VerificationError as e:
        raise ErrInvalidHeader(e) from e


def verify_non_adjacent(trusted: SignedHeader, trusted_vals: ValidatorSet,
                        untrusted: SignedHeader,
                        untrusted_vals: ValidatorSet,
                        trusting_period_ns: int, now_ns: int,
                        max_clock_drift_ns: int,
                        trust_level: Tuple[int, int] = DEFAULT_TRUST_LEVEL,
                        backend: Optional[str] = None) -> None:
    """verifier.go:32 VerifyNonAdjacent — the skipping hop."""
    if untrusted.header.height == trusted.header.height + 1:
        raise LightError("headers must be non adjacent in height")
    if header_expired(trusted, trusting_period_ns, now_ns):
        raise ErrOldHeaderExpired(
            trusted.header.time + trusting_period_ns, now_ns)
    try:
        _verify_new_header_and_vals(untrusted, untrusted_vals, trusted,
                                    now_ns, max_clock_drift_ns)
    except ValueError as e:
        raise ErrInvalidHeader(e) from e
    # +trust_level of the TRUSTED validators must have signed the new header
    try:
        commit_verify.verify_commit_light_trusting(
            trusted_vals, trusted.header.chain_id, untrusted.commit,
            trust_level[0], trust_level[1], backend=backend)
    except commit_verify.ErrNotEnoughVotingPowerSigned as e:
        raise ErrNewValSetCantBeTrusted(e) from e
    # +2/3 of the NEW validators must have signed (last: DOS-resistant order,
    # verifier.go:69-77)
    try:
        commit_verify.verify_commit_light(
            untrusted_vals, trusted.header.chain_id,
            untrusted.commit.block_id, untrusted.header.height,
            untrusted.commit, backend=backend)
    except commit_verify.VerificationError as e:
        raise ErrInvalidHeader(e) from e


def verify(trusted: SignedHeader, trusted_vals: ValidatorSet,
           untrusted: SignedHeader, untrusted_vals: ValidatorSet,
           trusting_period_ns: int, now_ns: int, max_clock_drift_ns: int,
           trust_level: Tuple[int, int] = DEFAULT_TRUST_LEVEL,
           backend: Optional[str] = None) -> None:
    """verifier.go:135 Verify — dispatches adjacent/non-adjacent."""
    if untrusted.header.height != trusted.header.height + 1:
        verify_non_adjacent(trusted, trusted_vals, untrusted, untrusted_vals,
                            trusting_period_ns, now_ns, max_clock_drift_ns,
                            trust_level, backend=backend)
    else:
        verify_adjacent(trusted, untrusted, untrusted_vals,
                        trusting_period_ns, now_ns, max_clock_drift_ns,
                        backend=backend)


def verify_backwards(untrusted: SignedHeader, trusted: SignedHeader) -> None:
    """verifier.go:224 VerifyBackwards — header H-1 against trusted H via
    the LastBlockID hash link (no signature checks needed)."""
    untrusted.header.validate_basic()
    if untrusted.header.chain_id != trusted.header.chain_id:
        raise ErrInvalidHeader("header belongs to another chain")
    if untrusted.header.time >= trusted.header.time:
        raise ErrInvalidHeader(
            "expected older header time to be before newer header time")
    if trusted.header.last_block_id.hash != untrusted.header.hash():
        raise ErrInvalidHeader(
            f"older header hash {untrusted.header.hash().hex().upper()} does "
            f"not match trusted header's last block id "
            f"{trusted.header.last_block_id.hash.hex().upper()}")


def verify_adjacent_run(trusted: LightBlock, run: List[LightBlock],
                        trusting_period_ns: int, now_ns: int,
                        max_clock_drift_ns: int,
                        backend: Optional[str] = None,
                        min_lanes: int = 0) -> int:
    """Verify a run of ADJACENT light blocks after ``trusted`` with a single
    fused signature dispatch (new vs the reference's per-hop loop in
    light/client.go:613 verifySequential), padded as if it held
    ``min_lanes`` signatures (0: by its own length). Returns the number of
    verified blocks from the front of the run; structural failure or a bad
    commit at position i leaves 0..i-1 verified, matching what a caller can
    commit.
    """
    if not run:
        return 0
    prev = trusted
    entries = []
    with trace.span("light.check", blocks=len(run)):
        for lb in run:
            try:
                if lb.height() != prev.height() + 1:
                    raise LightError("headers must be adjacent in height")
                if header_expired(prev.signed_header, trusting_period_ns,
                                  now_ns):
                    raise ErrOldHeaderExpired(
                        prev.header.time + trusting_period_ns, now_ns)
                _verify_new_header_and_vals(
                    lb.signed_header, lb.validator_set, prev.signed_header,
                    now_ns, max_clock_drift_ns)
                if lb.header.validators_hash != \
                        prev.header.next_validators_hash:
                    raise LightError("next validators hash mismatch")
            except (LightError, ValueError):
                break
            entries.append((lb.validator_set, prev.header.chain_id,
                            lb.commit.block_id, lb.height(), lb.commit))
            prev = lb
    if not entries:
        return 0
    with trace.span("light.verify_run", blocks=len(entries)):
        errs = commit_verify.verify_commits_light_batch(
            entries, backend=backend, min_lanes=min_lanes)
    ok = 0
    for e in errs:
        if e is not None:
            break
        ok += 1
    return ok

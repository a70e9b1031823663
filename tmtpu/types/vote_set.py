"""VoteSet (reference: types/vote_set.go) — per-(height, round, type) vote
accumulation with 2/3-majority tracking.

Behavior reproduced from the reference: the addVote validation cascade
(:156-218 — index/address/HRS checks, duplicate and conflict handling),
power tallying per block key with bitarrays (:233-304), peer-maj23
subscriptions (:356), and MakeCommit (:612).

Batch-first addition is new: ``add_votes`` verifies a whole list of votes
through one crypto.BatchVerifier dispatch (the TPU path), then runs the
same bookkeeping per valid vote. ``add_vote`` is the serial compatibility
wrapper.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Tuple

from tmtpu.crypto import batch as crypto_batch
from tmtpu.libs import metrics as _metrics
from tmtpu.libs import timeline, trace
from tmtpu.libs import valstats as _valstats
from tmtpu.libs.bits import BitArray
from tmtpu.types.block import BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, \
    BLOCK_ID_FLAG_NIL, BlockID, Commit, CommitSig
from tmtpu.types.validator import ValidatorSet
from tmtpu.types.vote import ErrVoteConflictingVotes, MAX_VOTES_COUNT, \
    PRECOMMIT, Vote, VoteError, is_vote_type_valid, vote_sign_bytes_template

# An honest set sees one or two block ids (the proposal's, nil); votes that
# name more, signed or not, get a template each that the set does not keep.
_MAX_SIGN_TEMPLATES = 16


class _BlockVotes:
    """Votes for one block key (vote_set.go:646 blockVotes)."""

    __slots__ = ("peer_maj23", "bit_array", "votes", "sum")

    def __init__(self, peer_maj23: bool, num_validators: int):
        self.peer_maj23 = peer_maj23
        self.bit_array = BitArray(num_validators)
        self.votes: List[Optional[Vote]] = [None] * num_validators
        self.sum = 0

    def add_verified_vote(self, vote: Vote, voting_power: int) -> None:
        idx = vote.validator_index
        if self.votes[idx] is None:
            self.bit_array.set_index(idx, True)
            self.votes[idx] = vote
            self.sum += voting_power

    def get_by_index(self, idx: int) -> Optional[Vote]:
        return self.votes[idx]


class VoteSet:
    def __init__(self, chain_id: str, height: int, round: int,
                 signed_msg_type: int, val_set: ValidatorSet,
                 verify_backend: Optional[str] = None):
        if height == 0:
            raise ValueError("cannot make VoteSet for height == 0")
        if not is_vote_type_valid(signed_msg_type):
            raise ValueError(f"invalid vote type {signed_msg_type}")
        if val_set.size() > MAX_VOTES_COUNT:
            raise ValueError(
                f"validator set larger than MaxVotesCount {MAX_VOTES_COUNT}")
        self.chain_id = chain_id
        self.height = height
        self.round = round
        self.signed_msg_type = signed_msg_type
        self.val_set = val_set
        self.verify_backend = verify_backend
        n = val_set.size()
        self._lock = threading.RLock()
        self._votes_bit_array = BitArray(n)
        self._votes: List[Optional[Vote]] = [None] * n
        self._sum = 0
        self._maj23: Optional[BlockID] = None
        self._votes_by_block: Dict[bytes, _BlockVotes] = {}
        self._peer_maj23s: Dict[str, BlockID] = {}
        # sign-bytes templates of the block ids this set's votes brought,
        # keyed by the block id's whole content (BlockID.key() is not
        # injective across hash lengths); read and filled under _lock
        self._sign_templates: Dict[Tuple[bytes, int, bytes],
                                   Callable[[int], bytes]] = {}

    # -- accessors ----------------------------------------------------------

    def size(self) -> int:
        return self.val_set.size()

    def bit_array(self) -> BitArray:
        with self._lock:
            return self._votes_bit_array.copy()

    def bit_array_by_block_id(self, block_id: BlockID) -> Optional[BitArray]:
        with self._lock:
            bv = self._votes_by_block.get(block_id.key())
            return bv.bit_array.copy() if bv else None

    def get_by_index(self, idx: int) -> Optional[Vote]:
        with self._lock:
            if idx < 0 or idx >= len(self._votes):
                return None
            return self._votes[idx]

    def get_by_address(self, address: bytes) -> Optional[Vote]:
        with self._lock:
            idx, _ = self.val_set.get_by_address(address)
            return self._votes[idx] if idx >= 0 else None

    def has_two_thirds_majority(self) -> bool:
        with self._lock:
            return self._maj23 is not None

    def two_thirds_majority(self) -> Tuple[BlockID, bool]:
        with self._lock:
            if self._maj23 is not None:
                return self._maj23, True
            return BlockID(), False

    def has_two_thirds_any(self) -> bool:
        with self._lock:
            return self._sum > self.val_set.total_voting_power() * 2 // 3

    def has_all(self) -> bool:
        with self._lock:
            return self._sum == self.val_set.total_voting_power()

    def sum_voting_power(self) -> int:
        with self._lock:
            return self._sum

    # -- the hot path -------------------------------------------------------

    def add_vote(self, vote: Vote) -> bool:
        """Serial add (vote_set.go:145 AddVote). Returns True if the vote
        was added; raises VoteError subclasses on bad votes."""
        ok_list = self.add_votes([vote])
        return ok_list[0]

    def add_votes(self, votes: List[Vote]) -> List[bool]:
        """Batch add — validates all votes, verifies the survivors'
        signatures in ONE BatchVerifier dispatch, then applies bookkeeping.
        Per-vote errors follow the reference's addVote semantics:
        structurally-bad votes raise; a conflicting (equivocation) vote
        raises ErrVoteConflictingVotes AFTER processing the rest."""
        with self._lock, trace.span(
                "vote_set.add_votes", votes=len(votes),
                height=self.height, round=self.round):
            prepared = []  # (vote, val, conflicting|None)
            results = [False] * len(votes)
            first_err: Optional[Exception] = None
            conflict: Optional[ErrVoteConflictingVotes] = None
            bv = None
            with trace.span("vote_set.collect"):
                for i, vote in enumerate(votes):
                    try:
                        val, existing = self._pre_validate(vote)
                    except VoteError as e:
                        if first_err is None:
                            first_err = e
                        continue
                    if val is None:
                        continue  # benign duplicate; results[i] stays False
                    prepared.append((i, vote, val, existing))
                if prepared:
                    # pinned to a drain's worth or the whole set, so that
                    # whatever the drain held the flush meets a shape
                    # warm_validator_set compiled
                    bv = crypto_batch.new_batch_verifier(
                        self.verify_backend,
                        min_lanes=crypto_batch.vote_flush_lanes(
                            self.size(), len(prepared)))
                    # Fused tally: when every prepared vote is a fresh add
                    # from a distinct validator (the normal round: no
                    # conflicts, no replays), voting powers ride the batch
                    # and the device returns Σ power over the VALID lanes —
                    # the on-device replacement for vote_set.go:233-304's
                    # per-vote host sum. A mixed/conflicting batch rides
                    # the same step with zero powers (one compiled step a
                    # shape, never a second one at first sight of an
                    # equivocation) and is tallied per vote on the host.
                    fused = (
                        all(existing is None for *_r, existing in prepared)
                        and len({v.validator_index for _, v, *_r in prepared})
                        == len(prepared)
                    )
                    # _pre_validate held every vote to the set's type,
                    # height and round: only the block id and the timestamp
                    # vary, so a lane's sign bytes are its block id's
                    # template filled in with its timestamp
                    templates = self._sign_templates
                    hit = built = 0
                    for _, vote, val, _ in prepared:
                        bid = vote.block_id
                        key = (bid.hash, bid.parts_total, bid.parts_hash)
                        template = templates.get(key)
                        if template is None:
                            template = vote_sign_bytes_template(
                                self.chain_id, self.signed_msg_type,
                                self.height, self.round, bid)
                            built += 1
                            if len(templates) < _MAX_SIGN_TEMPLATES:
                                templates[key] = template
                        else:
                            hit += 1
                        bv.add(val.pub_key, template(vote.timestamp),
                               vote.signature,
                               power=val.voting_power if fused else 0)

            if prepared:
                if hit:
                    _metrics.consensus_vote_sign_templates.inc(
                        hit, event="hit")
                if built:
                    _metrics.consensus_vote_sign_templates.inc(
                        built, event="built")
                _metrics.consensus_vote_flush_lanes.observe(len(prepared))
                _, mask, dev_sum = bv.verify_tally()
                applied_power = 0
                with trace.span("vote_set.apply"):
                    for (i, vote, val, existing), ok in zip(prepared, mask):
                        if not ok:
                            _metrics.consensus_invalid_votes.inc()
                            err = VoteError(
                                "invalid signature from "
                                f"{vote.validator_address.hex()}")
                            if first_err is None:
                                first_err = err
                            continue
                        added, conflicting = self._add_verified(
                            vote, val, defer_sum=fused
                        )
                        if added and fused:
                            applied_power += val.voting_power
                        results[i] = added
                        if conflicting is not None:
                            # equivocation flag BEFORE the single-raise
                            # fold: every conflicting pair is ledgered even
                            # when several land in one batch
                            _valstats.on_equivocation(vote)
                            if conflict is None:
                                conflict = ErrVoteConflictingVotes(
                                    conflicting, vote)
                if fused:
                    # every valid lane was a fresh add, so the device sum IS
                    # the _sum delta; a divergence from the host bookkeeping
                    # means the device graph and the mask disagree — fail
                    # loudly rather than corrupt the tally
                    if dev_sum != applied_power:
                        raise RuntimeError(
                            f"device/host tally divergence: device "
                            f"{dev_sum} vs host {applied_power}")
                    self._sum += dev_sum

            if conflict is not None:
                # the batch was fully processed; expose what was added so
                # callers can still publish events for accepted votes
                conflict.results = results
                raise conflict
            if first_err is not None and not any(results):
                raise first_err
            return results

    def _pre_validate(self, vote: Vote):
        """The addVote checks before signature verification
        (vote_set.go:156-218). Returns (validator, conflicting_existing_vote)
        or (None, None) for benign exact duplicates."""
        if vote is None:
            raise VoteError("nil vote")
        idx = vote.validator_index
        if idx < 0:
            raise VoteError("index < 0")
        if not vote.validator_address:
            raise VoteError("empty address")
        if (vote.height != self.height or vote.round != self.round
                or vote.type != self.signed_msg_type):
            raise VoteError(
                f"expected {self.height}/{self.round}/{self.signed_msg_type},"
                f" got {vote.height}/{vote.round}/{vote.type}"
            )
        addr, val = self.val_set.get_by_index(idx)
        if val is None:
            raise VoteError(
                f"cannot find validator {idx} in valSet of size {self.size()}"
            )
        if addr != vote.validator_address:
            raise VoteError(
                f"vote.ValidatorAddress does not match address for index {idx}"
            )
        existing = self._votes[idx]
        if existing is not None:
            if existing.block_id == vote.block_id:
                if existing.signature == vote.signature:
                    return None, None  # exact duplicate, no-op
                raise VoteError("same block, different signature (non-deterministic?)")
            # conflicting block: allow through so the (verified) pair can be
            # surfaced as equivocation evidence
            return val, existing
        return val, None

    def _add_verified(self, vote: Vote, val, defer_sum: bool = False):
        """vote_set.go:233 addVerifiedVote (signature already checked).
        Returns (added, conflicting_vote_or_None). With ``defer_sum`` the
        total-power update is skipped — the caller applies the device-fused
        tally for the whole batch instead."""
        idx = vote.validator_index
        key = vote.block_id.key()
        conflicting = None

        existing = self._votes[idx]
        if existing is not None and existing.block_id == vote.block_id:
            # intra-batch duplicate: the copy was prepared while _votes[idx]
            # was still empty (only _pre_validate filters pre-existing
            # duplicates) — benign, NOT an equivocation
            return False, None
        if existing is not None:
            conflicting = existing
            # Replace in the main array only if this block already has maj23.
            if self._maj23 is not None and self._maj23.key() == key:
                self._votes[idx] = vote
                self._votes_bit_array.set_index(idx, True)
        else:
            self._votes[idx] = vote
            self._votes_bit_array.set_index(idx, True)
            if not defer_sum:
                self._sum += val.voting_power
            # per-validator forensics: arrival offset/rank for this
            # fresh vote (disabled: one attribute read)
            _valstats.on_vote(vote, val.voting_power)

        bv = self._votes_by_block.get(key)
        if bv is not None:
            if conflicting is not None and not bv.peer_maj23:
                # conflict and no peer claims this block is special: drop
                return False, conflicting
        else:
            if conflicting is not None:
                # not even tracking this blockKey: forget it
                return False, conflicting
            bv = _BlockVotes(peer_maj23=False,
                             num_validators=len(self._votes))
            self._votes_by_block[key] = bv

        old_sum = bv.sum
        quorum = self.val_set.total_voting_power() * 2 // 3 + 1
        bv.add_verified_vote(vote, val.voting_power)
        if old_sum < quorum <= bv.sum and self._maj23 is None:
            self._maj23 = BlockID(vote.block_id.hash,
                                  vote.block_id.parts_total,
                                  vote.block_id.parts_hash)
            # quorum-crossing timestamp for the per-height timeline: the
            # prevote/precommit 2/3 instant is exactly the per-round
            # timing the stall diagnostics need
            timeline.record(
                self.height,
                timeline.EVENT_PRECOMMIT_QUORUM
                if self.signed_msg_type == PRECOMMIT
                else timeline.EVENT_PREVOTE_QUORUM,
                round=self.round, power=bv.sum, quorum=quorum)
            trace.mark_height(
                self.height,
                "height.precommit_quorum"
                if self.signed_msg_type == PRECOMMIT
                else "height.prevote_quorum",
                round=self.round, power=bv.sum)
            if self._maj23.hash:
                # non-nil quorum: stamp every tx of the winning block
                # (noted at proposal completion) at its quorum stage
                from tmtpu.libs import txlat

                txlat.stamp_height(
                    self.height,
                    "precommit_q" if self.signed_msg_type == PRECOMMIT
                    else "prevote_q")
            # the vote that crossed the +2/3 names the slowest
            # quorum-completing validator (quorum.laggard event)
            _valstats.on_quorum(vote)
            # copy the winning block's votes over to the main array
            for i, v in enumerate(bv.votes):
                if v is not None:
                    self._votes[i] = v
        return True, conflicting

    # -- peer maj23 claims (vote_set.go:356 SetPeerMaj23) -------------------

    def set_peer_maj23(self, peer_id: str, block_id: BlockID) -> None:
        with self._lock:
            key = block_id.key()
            existing = self._peer_maj23s.get(peer_id)
            if existing is not None:
                if existing == block_id:
                    return
                raise VoteError(
                    f"setPeerMaj23: conflicting blockID from peer {peer_id}"
                )
            self._peer_maj23s[peer_id] = block_id
            bv = self._votes_by_block.get(key)
            if bv is not None:
                bv.peer_maj23 = True
            else:
                self._votes_by_block[key] = _BlockVotes(
                    peer_maj23=True, num_validators=len(self._votes)
                )

    # -- commit construction ------------------------------------------------

    def make_commit(self) -> Commit:
        """vote_set.go:612 MakeCommit — precommits only, needs maj23."""
        with self._lock:
            if self.signed_msg_type != PRECOMMIT:
                raise VoteError("cannot MakeCommit() unless VoteSet.Type is PRECOMMIT")
            if self._maj23 is None:
                raise VoteError("cannot MakeCommit() unless a blockhash has +2/3")
            sigs = []
            for i, v in enumerate(self._votes):
                if v is None:
                    sigs.append(CommitSig.absent())
                    continue
                if v.block_id == self._maj23:
                    flag = BLOCK_ID_FLAG_COMMIT
                elif v.block_id.is_zero():
                    flag = BLOCK_ID_FLAG_NIL
                else:
                    # a complete-but-different BlockID is excluded
                    # (vote_set.go:628-631: "if block ID exists but doesn't
                    # match, exclude sig")
                    sigs.append(CommitSig.absent())
                    continue
                sigs.append(CommitSig(flag, v.validator_address, v.timestamp,
                                      v.signature))
            return Commit(self.height, self.round, self._maj23, sigs)

    def __repr__(self):
        return (f"VoteSet{{H:{self.height} R:{self.round} "
                f"T:{self.signed_msg_type} +2/3:{self._maj23} "
                f"{self._votes_bit_array}}}")


def commit_to_vote_set(chain_id: str, commit: Commit,
                       val_set: ValidatorSet) -> VoteSet:
    """types/vote_set.go CommitToVoteSet — rebuild the precommit VoteSet a
    Commit was made from (crash recovery: reconstructLastCommit)."""
    vs = VoteSet(chain_id, commit.height, commit.round, PRECOMMIT, val_set)
    votes = []
    for idx, cs in enumerate(commit.signatures):
        if cs.is_absent():
            continue
        votes.append(Vote(
            type=PRECOMMIT, height=commit.height, round=commit.round,
            block_id=cs.block_id(commit.block_id), timestamp=cs.timestamp,
            validator_address=cs.validator_address, validator_index=idx,
            signature=cs.signature,
        ))
    added = vs.add_votes(votes)
    if not all(added):
        raise VoteError("failed to reconstruct last commit")
    return vs

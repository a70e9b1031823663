"""Regression tests for the round-2 validation fixes:

- intra-batch duplicate votes are NOT misreported as equivocations
  (the bug produced DuplicateVoteEvidence with identical block IDs)
- block evidence is verified through the pool during validation
  (reference state/execution.go:122 ValidateBlock -> evpool.CheckEvidence)
- weighted median block time (reference state/state.go:268 MedianTime,
  state/validation.go:114-143)
- Block.validate_basic binds the evidence list via evidence_hash
  (reference types/block.go ValidateBasic)
"""

import pytest

from tmtpu.state.state import median_time
from tmtpu.state.validation import BlockValidationError, validate_block
from tmtpu.types.block import Block, BlockID, Commit, CommitSig, \
    BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_ABSENT
from tmtpu.types.priv_validator import MockPV
from tmtpu.types.validator import Validator, ValidatorSet
from tmtpu.types.vote import PRECOMMIT, ErrVoteConflictingVotes, Vote
from tmtpu.types.vote_set import VoteSet

from tests.test_types import CHAIN_ID, mk_valset, mk_vote
from tests.test_valset_memo import _hits, _plain_hash, chain  # noqa: F401


# --- intra-batch duplicates --------------------------------------------------


def test_intra_batch_duplicate_is_not_equivocation():
    vals, pvs = mk_valset(4)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals)
    v = mk_vote(pvs[0], vals, 0)
    # the same vote twice in ONE batch: first adds, second is a benign no-op
    results = vs.add_votes([v, v])
    assert results == [True, False]
    assert vs.sum_voting_power() == 10


def test_intra_batch_duplicate_alongside_fresh_votes():
    vals, pvs = mk_valset(4)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals)
    v0 = mk_vote(pvs[0], vals, 0)
    v1 = mk_vote(pvs[1], vals, 1)
    results = vs.add_votes([v0, v1, v0])
    assert results == [True, True, False]
    assert vs.sum_voting_power() == 20


def test_real_equivocation_still_raises():
    vals, pvs = mk_valset(4)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals)
    a = mk_vote(pvs[0], vals, 0, block_id=BlockID(b"\x01" * 32, 1, b"\x02" * 32))
    b = mk_vote(pvs[0], vals, 0, block_id=BlockID(b"\x03" * 32, 1, b"\x04" * 32))
    vs.add_vote(a)
    with pytest.raises(ErrVoteConflictingVotes) as ei:
        vs.add_vote(b)
    assert ei.value.vote_a.block_id != ei.value.vote_b.block_id


# --- evidence misreport guard ------------------------------------------------


class _NoStateStore:
    def load(self):
        return None

    def load_validators(self, h):
        return None


def test_report_conflicting_votes_rejects_same_block_pair():
    from tmtpu.evidence.pool import EvidencePool
    from tmtpu.libs.db import MemDB

    vals, pvs = mk_valset(4)
    pool = EvidencePool(MemDB(), _NoStateStore(), None)
    v = mk_vote(pvs[0], vals, 0)
    # identical votes: must be silently dropped, never stored as evidence
    pool.report_conflicting_votes(v, v)
    assert pool.pending_evidence(1 << 20) == []


# --- median time -------------------------------------------------------------


def _commit_with_times(vals, times):
    sigs = []
    for i, v in enumerate(vals.validators):
        t = times.get(i)
        if t is None:
            sigs.append(CommitSig.absent())
        else:
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, t,
                                  b"\x01" * 64))
    return Commit(1, 0, BlockID(b"\x01" * 32, 1, b"\x02" * 32), sigs)


def test_median_time_weighted():
    pvs = [MockPV() for _ in range(3)]
    vals = ValidatorSet([
        Validator(pvs[0].get_pub_key(), 10),
        Validator(pvs[1].get_pub_key(), 10),
        Validator(pvs[2].get_pub_key(), 10),
    ])
    c = _commit_with_times(vals, {0: 100, 1: 200, 2: 300})
    # equal weights: median is the middle timestamp
    assert median_time(c, vals) == 200


def test_median_time_power_dominant():
    pvs = [MockPV() for _ in range(3)]
    vals = ValidatorSet([
        Validator(pvs[0].get_pub_key(), 100),
        Validator(pvs[1].get_pub_key(), 1),
        Validator(pvs[2].get_pub_key(), 1),
    ])
    # the sorted set puts the power-100 validator first; find its index
    big_idx = next(i for i, v in enumerate(vals.validators)
                   if v.voting_power == 100)
    times = {i: 1000 if i == big_idx else 1 for i in range(3)}
    # the dominant validator's timestamp wins the weighted median
    assert median_time(_commit_with_times(vals, times), vals) == 1000


def test_median_time_skips_absent():
    pvs = [MockPV() for _ in range(3)]
    vals = ValidatorSet([Validator(pv.get_pub_key(), 10) for pv in pvs])
    c = _commit_with_times(vals, {0: 100, 2: 500})
    # total power counted = 20, median budget 10 <= first weight 10 -> 100
    # (matches reference WeightedMedian: `if median <= weight { return }`)
    assert median_time(c, vals) == 100


# --- evidence hash binding ---------------------------------------------------


def test_validate_basic_checks_evidence_hash():
    from tmtpu.types.evidence import DuplicateVoteEvidence
    from tmtpu.types.tx import txs_hash
    from tmtpu.types.block import Header

    vals, pvs = mk_valset(4)
    a = mk_vote(pvs[0], vals, 0, block_id=BlockID(b"\x01" * 32, 1, b"\x02" * 32))
    b = mk_vote(pvs[0], vals, 0, block_id=BlockID(b"\x03" * 32, 1, b"\x04" * 32))
    ev = DuplicateVoteEvidence.new(a, b, block_time=0, val_set=vals)

    header = Header(
        chain_id=CHAIN_ID, height=1, time=1,
        validators_hash=b"\x05" * 32, next_validators_hash=b"\x05" * 32,
        consensus_hash=b"\x06" * 32,
        proposer_address=vals.validators[0].address,
    )
    blk = Block(header, txs=[], evidence=[ev])
    blk.fill_header()
    blk.validate_basic()  # consistent: ok

    # now smuggle extra evidence without updating the header hash
    blk2 = Block(header, txs=[], evidence=[])
    blk2.header.data_hash = txs_hash([])
    # header.evidence_hash still binds [ev], but the list is empty
    with pytest.raises(ValueError, match="EvidenceHash"):
        blk2.validate_basic()


# --- the header's set hashes against a set that keeps its hash ---------------


@pytest.mark.parametrize("which,message", [
    ("validators", "wrong Block.Header.ValidatorsHash"),
    ("next_validators", "wrong Block.Header.NextValidatorsHash")])
def test_validate_block_with_a_warm_memo_refuses_another_set(
        chain, which, message):
    for i in range(3):
        chain.apply([b"w%d=v" % i])
    block, _ = chain.make_block([b"x=y"])
    h0 = _hits("hash")
    validate_block(chain.state, block, verify_backend="cpu")
    assert _hits("hash") - h0 == 2         # both answered from the memo
    # a header naming a set that differs in one power
    other = getattr(chain.state, which).copy()
    other.validators[1].voting_power += 1
    field = "validators_hash" if which == "validators" \
        else "next_validators_hash"
    setattr(block.header, field, _plain_hash(other))
    block.fill_header()
    with pytest.raises(BlockValidationError, match=message):
        validate_block(chain.state, block, verify_backend="cpu")
    # and a state whose set moved under a header that names the old one
    block, _ = chain.make_block([b"x=y"])
    getattr(chain.state, which).validators[1].voting_power += 1
    with pytest.raises(BlockValidationError, match=message):
        validate_block(chain.state, block, verify_backend="cpu")

"""BlockExecutor.validate_block answers a repeat of the (state, block) that
last passed — the same two objects, reading as they did — without the
pure checks of state/validation.py (execution.go ValidateBlock is called at
prevote, at precommit's lock, at finalize and in ApplyBlock on the same
two). Anything else is validated in full: another Block object, a State
changed in place, a block that raised; the evidence pool is asked every
call; ``state_validate_block_total{path}`` moves once a call."""

import pytest

from tmtpu.evidence.pool import EvidenceError
from tmtpu.libs import metrics
from tmtpu.state import validation
from tmtpu.state.execution import BlockExecutionError
from tmtpu.state.validation import BlockValidationError
from tmtpu.types import commit_verify
from tmtpu.types.block import Block, BlockID, Commit, CommitSig
from tmtpu.types.evidence import DuplicateVoteEvidence

from tests.test_types import mk_valset, mk_vote
from tests.test_valset_memo import chain  # noqa: F401


def _paths():
    return dict(metrics.state_validate_block.summary_series())


def _moved(before):
    return {k: v - before.get(k, 0) for k, v in _paths().items()
            if v != before.get(k, 0)}


@pytest.fixture
def grown(chain):  # noqa: F811
    """The chain a few blocks on, so a block carries a LastCommit."""
    for i in range(3):
        chain.apply([b"k%d=v" % i])
    return chain


@pytest.fixture
def spies(grown, monkeypatch):
    """Calls of verify_commit and median_time, the two costly reads of a
    full validation, from the grown chain on."""
    calls = {"verify_commit": 0, "median_time": 0}
    real_verify, real_median = commit_verify.verify_commit, \
        validation.median_time

    def verify(*a, **kw):
        calls["verify_commit"] += 1
        return real_verify(*a, **kw)

    def median(*a, **kw):
        calls["median_time"] += 1
        return real_median(*a, **kw)
    monkeypatch.setattr(commit_verify, "verify_commit", verify)
    monkeypatch.setattr(validation, "median_time", median)
    return calls


def test_a_repeat_answers_without_the_commit_check_or_the_median(
        grown, spies):
    chain = grown
    block, _ = chain.make_block([b"x=y"])
    p0 = _paths()
    chain.exec.validate_block(chain.state, block)
    assert spies == {"verify_commit": 1, "median_time": 1}
    for _ in range(3):
        chain.exec.validate_block(chain.state, block)
    assert spies == {"verify_commit": 1, "median_time": 1}
    assert _moved(p0) == {"path=full": 1, "path=repeat": 3}


def test_another_block_object_with_the_header_and_a_flipped_signature_raises(
        grown):
    chain = grown
    block, _ = chain.make_block([b"x=y"])
    chain.exec.validate_block(chain.state, block)
    lc = block.last_commit
    sigs = [CommitSig(s.block_id_flag, s.validator_address, s.timestamp,
                      s.signature) for s in lc.signatures]
    bad = bytearray(sigs[1].signature)
    bad[0] ^= 1
    sigs[1].signature = bytes(bad)
    forged = Block(block.header, block.txs, block.evidence,
                   Commit(lc.height, lc.round, lc.block_id, sigs))
    p0 = _paths()
    with pytest.raises(Exception):
        chain.exec.validate_block(chain.state, forged)
    # the same header on a commit with its hash refitted: the signature
    # check itself refuses it
    forged.header.last_commit_hash = forged.last_commit.hash()
    refitted = Block(forged.header, block.txs, block.evidence,
                     forged.last_commit)
    with pytest.raises(BlockValidationError):
        chain.exec.validate_block(chain.state, refitted)
    assert _moved(p0) == {"path=full": 2}


def test_an_equal_block_decoded_anew_is_validated_in_full(grown, spies):
    chain = grown
    block, _ = chain.make_block([b"x=y"])
    chain.exec.validate_block(chain.state, block)
    twin = Block.decode(block.encode())
    assert twin.hash() == block.hash() and twin is not block
    p0 = _paths()
    chain.exec.validate_block(chain.state, twin)
    assert _moved(p0) == {"path=full": 1}
    assert spies["verify_commit"] == 2


@pytest.mark.parametrize("part", ["last_commit", "txs", "evidence"])
def test_a_block_whose_part_was_replaced_is_validated_in_full(grown, spies,
                                                              part):
    chain = grown
    block, _ = chain.make_block([b"x=y"])
    chain.exec.validate_block(chain.state, block)
    if part == "last_commit":
        lc = block.last_commit
        block.last_commit = Commit(lc.height, lc.round, lc.block_id,
                                   list(lc.signatures))
    else:
        setattr(block, part, list(getattr(block, part)))
    p0 = _paths()
    chain.exec.validate_block(chain.state, block)   # equal, so still valid
    assert _moved(p0) == {"path=full": 1}
    assert spies["verify_commit"] == 2


@pytest.mark.parametrize("change", ["app_hash", "last_block_id",
                                    "last_block_id_in_place",
                                    "last_results_hash", "validators",
                                    "a_copy"])
def test_a_state_changed_in_place_is_validated_in_full(grown, change):
    chain = grown
    block, _ = chain.make_block([b"x=y"])
    chain.exec.validate_block(chain.state, block)
    state = chain.state
    if change == "app_hash":
        state.app_hash = b"\x01" * 8
    elif change == "last_block_id":
        state.last_block_id = BlockID(b"\x02" * 32, 1, b"\x03" * 32)
    elif change == "last_block_id_in_place":
        state.last_block_id.parts_total += 1
    elif change == "last_results_hash":
        state.last_results_hash = b"\x04" * 32
    elif change == "validators":
        # the same content in another object: not the set that was read
        state.validators = state.validators.copy()
    else:
        state = state.copy()
    p0 = _paths()
    if change in ("validators", "a_copy"):
        chain.exec.validate_block(state, block)     # still valid
    else:
        with pytest.raises(BlockValidationError):
            chain.exec.validate_block(state, block)
    assert _moved(p0) == {"path=full": 1}


def test_a_block_that_raised_raises_again_and_leaves_the_slot(grown):
    chain = grown
    good, _ = chain.make_block([b"x=y"])
    chain.exec.validate_block(chain.state, good)
    slot = chain.exec._validated
    bad, _ = chain.make_block([b"x=y"])
    bad.header.app_hash = b"\x05" * 8
    p0 = _paths()
    for _ in range(2):
        with pytest.raises(BlockValidationError, match="AppHash"):
            chain.exec.validate_block(chain.state, bad)
        assert chain.exec._validated is slot
    chain.exec.validate_block(chain.state, good)
    assert _moved(p0) == {"path=full": 2, "path=repeat": 1}


class _Pool:
    """An evidence pool that counts its checks and refuses on demand."""

    def __init__(self):
        self.checks, self.refuse = 0, False

    def check_evidence(self, evidence):
        self.checks += 1
        if self.refuse:
            raise EvidenceError("refused on the second look")


def test_the_evidence_pool_is_asked_on_a_repeat(grown, spies):
    chain = grown
    vals, pvs = mk_valset(4)
    a = mk_vote(pvs[0], vals, 0, block_id=BlockID(b"\x01" * 32, 1,
                                                  b"\x02" * 32))
    b = mk_vote(pvs[0], vals, 0, block_id=BlockID(b"\x03" * 32, 1,
                                                  b"\x04" * 32))
    ev = DuplicateVoteEvidence.new(a, b, block_time=0, val_set=vals)
    s = chain.state
    height = s.last_block_height + 1
    header = s.make_block_header(
        height, validation.median_time(chain.last_commit, s.last_validators),
        [], chain.last_commit, [ev], s.validators.get_proposer().address)
    block = Block(header, [], [ev], chain.last_commit)
    block.fill_header()
    pool = _Pool()
    chain.exec.evidence_pool = pool
    p0 = _paths()
    chain.exec.validate_block(s, block)
    chain.exec.validate_block(s, block)
    assert pool.checks == 2 and spies["verify_commit"] == 1
    pool.refuse = True
    with pytest.raises(BlockExecutionError, match="invalid evidence"):
        chain.exec.validate_block(s, block)
    assert pool.checks == 3
    assert _moved(p0) == {"path=full": 1, "path=repeat": 2}


def test_the_counter_moves_once_a_call_by_its_path(chain):  # noqa: F811
    p0 = _paths()
    block, _ = chain.make_block([b"a=b"])       # the initial block
    chain.exec.validate_block(chain.state, block)
    assert _moved(p0) == {"path=full": 1}
    chain.exec.validate_block(chain.state, block)
    assert _moved(p0) == {"path=full": 1, "path=repeat": 1}
    other, _ = chain.make_block([b"c=d"])
    chain.exec.validate_block(chain.state, other)
    chain.exec.validate_block(chain.state, block)   # the slot is other's
    assert _moved(p0) == {"path=full": 3, "path=repeat": 1}

"""Types layer tests (model: types/validator_set_test.go,
types/vote_set_test.go, types/block_test.go in the reference)."""

import pytest

from tmtpu.crypto import ed25519
from tmtpu.libs.bits import BitArray
from tmtpu.types import pb
from tmtpu.types.block import (
    BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL,
    Block, BlockID, Commit, CommitSig, Header,
)
from tmtpu.types import commit_verify  # noqa: F401 - binds methods
from tmtpu.types.genesis import GenesisDoc, GenesisValidator
from tmtpu.types.part_set import PartSet
from tmtpu.types.priv_validator import MockPV
from tmtpu.types.validator import Validator, ValidatorSet
from tmtpu.types.vote import PRECOMMIT, PREVOTE, ErrVoteConflictingVotes, \
    Vote, VoteError, vote_sign_bytes_template
from tmtpu.types.vote_set import VoteSet

CHAIN_ID = "test_chain"


def mk_valset(n, power=10):
    pvs = [MockPV() for _ in range(n)]
    vals = ValidatorSet([Validator(pv.get_pub_key(), power) for pv in pvs])
    # map pv by address order in the sorted set
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    pvs_sorted = [by_addr[v.address] for v in vals.validators]
    return vals, pvs_sorted


def mk_vote(pv, vals, idx, height=1, round=0, type=PRECOMMIT,
            block_id=None, ts=1_700_000_000_000_000_000):
    v = Vote(
        type=type, height=height, round=round,
        block_id=block_id if block_id is not None else BlockID(b"\x01" * 32, 1, b"\x02" * 32),
        timestamp=ts + idx,
        validator_address=pv.get_pub_key().address(),
        validator_index=idx,
    )
    pv.sign_vote(CHAIN_ID, v)
    return v


# --- BitArray ---------------------------------------------------------------


def test_bit_array_ops():
    a = BitArray.from_bools([True, False, True, False, True])
    b = BitArray.from_bools([True, True, False, False, True])
    assert a.num_true_bits() == 3
    assert a.or_(b).num_true_bits() == 4
    assert a.and_(b).num_true_bits() == 2
    assert a.sub(b).true_indices() == [2]
    assert a.not_().true_indices() == [1, 3]
    assert str(a) == "x_x_x"
    assert BitArray.from_json(a.to_json()) == a
    big = BitArray(100)
    big.set_index(99, True)
    assert big.get_index(99) and big.num_true_bits() == 1


# --- Validator set ----------------------------------------------------------


def test_valset_ordering_and_proposer_rotation():
    pv1, pv2, pv3 = MockPV(), MockPV(), MockPV()
    vals = ValidatorSet([
        Validator(pv1.get_pub_key(), 1000),
        Validator(pv2.get_pub_key(), 300),
        Validator(pv3.get_pub_key(), 330),
    ])
    # sorted by power desc
    assert [v.voting_power for v in vals.validators] == [1000, 330, 300]
    assert vals.total_voting_power() == 1630
    # rotation frequency approximates voting power share
    counts = {}
    for _ in range(1630):
        p = vals.get_proposer()
        counts[p.address] = counts.get(p.address, 0) + 1
        vals.increment_proposer_priority(1)
    by_power = {v.address: v.voting_power for v in vals.validators}
    for addr, c in counts.items():
        assert abs(c - by_power[addr]) <= 2, (c, by_power[addr])


def test_valset_update_with_change_set():
    vals, _ = mk_valset(4, power=10)
    addr0 = vals.validators[0].address
    new_pv = MockPV()
    vals.update_with_change_set([
        Validator(vals.validators[0].pub_key, 25),        # update
        Validator(new_pv.get_pub_key(), 8),               # add
    ])
    assert vals.size() == 5
    _, v0 = vals.get_by_address(addr0)
    assert v0.voting_power == 25
    assert vals.total_voting_power() == 25 + 30 + 8
    # removal
    vals.update_with_change_set([Validator(new_pv.get_pub_key(), 0)])
    assert vals.size() == 4
    with pytest.raises(ValueError):
        ValidatorSet([]).increment_proposer_priority(1)


def test_valset_hash_changes_with_membership():
    vals, _ = mk_valset(3)
    h1 = vals.hash()
    vals.update_with_change_set([Validator(MockPV().get_pub_key(), 5)])
    assert vals.hash() != h1
    assert len(h1) == 32


# --- Vote sign bytes / verify ----------------------------------------------


def test_vote_sign_verify_roundtrip():
    vals, pvs = mk_valset(1)
    vote = mk_vote(pvs[0], vals, 0)
    vote.verify(CHAIN_ID, pvs[0].get_pub_key())
    vote.validate_basic()
    with pytest.raises(VoteError):
        vote.verify("other-chain", pvs[0].get_pub_key())
    # proto round trip
    assert Vote.from_proto(pb.Vote.decode(vote.to_proto().encode())) == vote


def test_nil_vote_sign_bytes_differ():
    vals, pvs = mk_valset(1)
    v1 = mk_vote(pvs[0], vals, 0)
    v2 = mk_vote(pvs[0], vals, 0, block_id=BlockID())
    assert v1.sign_bytes(CHAIN_ID) != v2.sign_bytes(CHAIN_ID)


# --- Sign bytes from a template ---------------------------------------------

BID = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
GO_ZERO_TIME = -62_135_596_800 * 10**9  # time.Time{}: a ten-byte varint

TIMESTAMPS = {
    "zero": 0,                                    # 2a 00
    "whole_seconds": 1_700_000_000 * 10**9,       # nanos 0
    "nanos_only": 123_456_789,                    # seconds 0
    "go_zero_time": GO_ZERO_TIME,
    "nanos_max": 1_700_000_000 * 10**9 + 999_999_999,
    "just_before_1970": -1,                       # seconds -1, nanos 999,999,999
    "seconds_and_nanos": 1_700_000_000 * 10**9 + 5,
}
CHAIN_IDS = {
    "empty": "",
    "15_chars": "test-chain-0015",
    "50_chars": "c" * 50,                         # the two-byte length prefix
}


def _vote_sign_bytes(chain_id, type, height, round, block_id, ts):
    return Vote(type, height, round, block_id, ts, b"\xaa" * 20,
                0).sign_bytes(chain_id)


@pytest.mark.parametrize("block_id", [BID, BlockID()], ids=["block", "nil"])
@pytest.mark.parametrize("chain_id", CHAIN_IDS.values(), ids=CHAIN_IDS.keys())
@pytest.mark.parametrize("ts", TIMESTAMPS.values(), ids=TIMESTAMPS.keys())
def test_template_equals_vote_sign_bytes(ts, chain_id, block_id):
    got = vote_sign_bytes_template(chain_id, PRECOMMIT, 7, 2, block_id)(ts)
    assert got == _vote_sign_bytes(chain_id, PRECOMMIT, 7, 2, block_id, ts)


@pytest.mark.parametrize("type", [PREVOTE, PRECOMMIT],
                         ids=["prevote", "precommit"])
@pytest.mark.parametrize("round", [0, 3])
@pytest.mark.parametrize("height", [0, 1, 2**40])
def test_template_height_round_type(height, round, type):
    template = vote_sign_bytes_template(CHAIN_ID, type, height, round, BID)
    for ts in TIMESTAMPS.values():
        assert template(ts) == _vote_sign_bytes(CHAIN_ID, type, height,
                                                round, BID, ts)


@pytest.mark.parametrize("chain_id,ts,want", [
    # types/vote_test.go TestVoteSignBytesTestVectors: precommit, height 1,
    # round 1, time.Time{}, no chain id
    ("", GO_ZERO_TIME,
     "2108021101000000000000001901000000000000002a0b088092b8c398feffffff01"),
    ("test_chain_id", 0,
     "2508021101000000000000001901000000000000002a00"
     "320d746573745f636861696e5f6964"),
], ids=["reference_vector", "timestamp_0_with_chain_id"])
def test_template_reference_vectors(chain_id, ts, want):
    got = vote_sign_bytes_template(chain_id, PRECOMMIT, 1, 1, BlockID())(ts)
    assert got.hex() == want
    assert got == _vote_sign_bytes(chain_id, PRECOMMIT, 1, 1, BlockID(), ts)


def _signed_commit(vals, pvs, height=1, round=0, absent=(), nil=()):
    """A commit signed vote by vote over ``Vote.sign_bytes``, timestamps a
    third of a second apart so seconds and nanos both vary."""
    sigs = []
    for i, pv in enumerate(pvs):
        if i in absent:
            sigs.append(CommitSig.absent())
            continue
        v = mk_vote(pv, vals, i, height=height, round=round,
                    block_id=BlockID() if i in nil else BID,
                    ts=1_700_000_000 * 10**9 + i * 333_333_333)
        sigs.append(CommitSig(
            BLOCK_ID_FLAG_NIL if i in nil else BLOCK_ID_FLAG_COMMIT,
            v.validator_address, v.timestamp, v.signature))
    return Commit(height, round, BID, sigs)


@pytest.mark.parametrize("chain_id", CHAIN_IDS.values(), ids=CHAIN_IDS.keys())
@pytest.mark.parametrize("height,round", [(1, 0), (1, 1), (2**40, 0),
                                          (2**40, 9)])
def test_commit_vote_sign_bytes_nil_beside_block(height, round, chain_id):
    vals, pvs = mk_valset(5)
    commit = _signed_commit(vals, pvs, height, round, absent=(1,), nil=(3,))
    sign_bytes = commit.vote_sign_bytes_for(chain_id)
    for idx, cs in enumerate(commit.signatures):
        want = _vote_sign_bytes(chain_id, PRECOMMIT, height, round,
                                cs.block_id(BID), cs.timestamp)
        assert sign_bytes(cs) == want
        assert commit.vote_sign_bytes(chain_id, idx) == want
    assert sign_bytes(commit.signatures[3]) != sign_bytes(
        commit.signatures[2])


# --- VoteSet ----------------------------------------------------------------


def test_vote_set_two_thirds_majority():
    vals, pvs = mk_valset(4)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals)
    bid = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
    for i in range(2):
        assert vs.add_vote(mk_vote(pvs[i], vals, i, block_id=bid))
    assert not vs.has_two_thirds_majority()
    assert vs.add_vote(mk_vote(pvs[2], vals, 2, block_id=bid))
    maj, ok = vs.two_thirds_majority()
    assert ok and maj == bid
    # exact duplicate is a no-op returning False
    assert not vs.add_vote(mk_vote(pvs[2], vals, 2, block_id=bid))
    commit = vs.make_commit()
    assert commit.height == 1
    assert sum(1 for s in commit.signatures if s.for_block()) == 3
    assert commit.signatures[3].is_absent()


def test_vote_set_batch_add_and_bad_votes():
    vals, pvs = mk_valset(6)
    vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE, vals)
    bid = BlockID(b"\x03" * 32, 2, b"\x04" * 32)
    votes = [mk_vote(pvs[i], vals, i, type=PREVOTE, block_id=bid)
             for i in range(6)]
    votes[2].signature = b"\x00" * 64  # corrupt one
    res = vs.add_votes(votes)
    assert res == [True, True, False, True, True, True]
    assert vs.has_two_thirds_any()


def test_vote_set_conflicting_vote_raises():
    vals, pvs = mk_valset(4)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals)
    bid_a = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
    bid_b = BlockID(b"\x05" * 32, 1, b"\x06" * 32)
    assert vs.add_vote(mk_vote(pvs[0], vals, 0, block_id=bid_a))
    with pytest.raises(ErrVoteConflictingVotes):
        vs.add_vote(mk_vote(pvs[0], vals, 0, block_id=bid_b))


def test_vote_set_conflicting_vote_counts_for_peer_claimed_block():
    # vote_set.go:261-283: a conflicting vote still tallies for a block a
    # peer claims has +2/3, and crossing quorum promotes votesByBlock into
    # the main array so MakeCommit includes it.
    vals, pvs = mk_valset(4)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals)
    bid_a = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
    bid_b = BlockID(b"\x05" * 32, 1, b"\x06" * 32)
    vs.set_peer_maj23("peer1", bid_a)
    assert vs.add_vote(mk_vote(pvs[0], vals, 0, block_id=bid_b))
    with pytest.raises(ErrVoteConflictingVotes):
        vs.add_vote(mk_vote(pvs[0], vals, 0, block_id=bid_a))
    assert vs.add_vote(mk_vote(pvs[1], vals, 1, block_id=bid_a))
    assert vs.add_vote(mk_vote(pvs[2], vals, 2, block_id=bid_a))
    maj, ok = vs.two_thirds_majority()
    assert ok and maj == bid_a
    commit = vs.make_commit()
    assert sum(1 for s in commit.signatures if s.for_block()) == 3


def test_vote_set_wrong_height_rejected():
    vals, pvs = mk_valset(2)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals)
    with pytest.raises(VoteError):
        vs.add_vote(mk_vote(pvs[0], vals, 0, height=2))


# --- Commit verification ----------------------------------------------------


def _make_commit(vals, pvs, bid, height=1, nil_idx=()):
    vs = VoteSet(CHAIN_ID, height, 0, PRECOMMIT, vals)
    for i, pv in enumerate(pvs):
        b = BlockID() if i in nil_idx else bid
        vs.add_vote(mk_vote(pv, vals, i, height=height, block_id=b))
    return vs.make_commit()


def test_verify_commit_ok_and_tampered():
    vals, pvs = mk_valset(5)
    bid = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
    commit = _make_commit(vals, pvs, bid, nil_idx=(4,))
    vals.verify_commit(CHAIN_ID, bid, 1, commit)
    vals.verify_commit_light(CHAIN_ID, bid, 1, commit)
    vals.verify_commit_light_trusting(CHAIN_ID, commit, 1, 3)
    # tamper a signature
    commit.signatures[1].signature = bytes(64)
    with pytest.raises(commit_verify.VerificationError):
        vals.verify_commit(CHAIN_ID, bid, 1, commit)


def test_verify_commit_insufficient_power():
    vals, pvs = mk_valset(4)
    bid = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
    commit = _make_commit(vals, pvs, bid)
    # flip two to nil -> only 2/4 power for block
    for i in (0, 1):
        commit.signatures[i].block_id_flag = BLOCK_ID_FLAG_NIL
    with pytest.raises(commit_verify.ErrNotEnoughVotingPowerSigned):
        vals.verify_commit_light(CHAIN_ID, bid, 1, commit)


def test_verify_commit_light_trusting_different_valset():
    # light client: trusted set overlaps the commit's set by address
    vals, pvs = mk_valset(4)
    bid = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
    commit = _make_commit(vals, pvs, bid)
    # trusting verify against the same set but trust level 2/3
    vals.verify_commit_light_trusting(CHAIN_ID, commit, 2, 3)


# the four entries, one calling convention: raise what the entry reports
def _entry_verify_commit(vals, commit, height=1):
    vals.verify_commit(CHAIN_ID, BID, height, commit, backend="cpu")


def _entry_light(vals, commit, height=1):
    vals.verify_commit_light(CHAIN_ID, BID, height, commit, backend="cpu")


def _entry_light_trusting(vals, commit, height=1):
    vals.verify_commit_light_trusting(CHAIN_ID, commit, 2, 3, backend="cpu")


def _entry_light_batch(vals, commit, height=1):
    err, = commit_verify.verify_commits_light_batch(
        [(vals, CHAIN_ID, BID, height, commit)], backend="cpu")
    if err is not None:
        raise err


ENTRIES = pytest.mark.parametrize("entry", [
    _entry_verify_commit, _entry_light, _entry_light_trusting,
    _entry_light_batch,
], ids=["verify_commit", "light", "light_trusting", "light_batch"])


@ENTRIES
def test_commit_entries_accept_block_nil_and_absent(entry):
    vals, pvs = mk_valset(7)
    entry(vals, _signed_commit(vals, pvs, absent=(2,), nil=(5,)))


@ENTRIES
def test_commit_entries_refuse_tampered_signature(entry):
    vals, pvs = mk_valset(7)
    commit = _signed_commit(vals, pvs, absent=(2,), nil=(5,))
    sig = commit.signatures[4].signature
    commit.signatures[4].signature = sig[:-1] + bytes([sig[-1] ^ 1])
    with pytest.raises(commit_verify.VerificationError) as e:
        entry(vals, commit)
    # verify_commit names the lane: validator 4 is the fourth signature
    # present (2 is absent), so #3
    assert str(e.value) == ("wrong signature (#3)"
                            if entry is _entry_verify_commit
                            else "wrong signature in commit")


@ENTRIES
def test_commit_entries_too_many_nil_votes(entry):
    vals, pvs = mk_valset(7)
    commit = _signed_commit(vals, pvs, absent=(2,), nil=(0, 1, 5))
    with pytest.raises(commit_verify.ErrNotEnoughVotingPowerSigned) as e:
        entry(vals, commit)
    assert (e.value.got, e.value.needed) == (30, 46)


@ENTRIES
def test_commit_entries_keep_nothing_between_calls(entry):
    vals, pvs = mk_valset(7)
    commit = _signed_commit(vals, pvs, absent=(2,), nil=(5,))
    entry(vals, commit)
    commit.signatures[4].timestamp += 1
    with pytest.raises(commit_verify.VerificationError) as e:
        entry(vals, commit)
    assert "wrong signature" in str(e.value)
    commit.signatures[4].timestamp -= 1
    entry(vals, commit)


def test_verify_commits_light_batch_one_bad_entry_in_a_run():
    vals, pvs = mk_valset(7)
    commits = [_signed_commit(vals, pvs, height=h, absent=(2,), nil=(5,))
               for h in (1, 2, 3, 4)]
    commits[1].signatures[6].signature = bytes(64)
    entries = [(vals, CHAIN_ID, BID, c.height, c) for c in commits]
    entries.append((vals, CHAIN_ID, BID, 6, commits[0]))
    entries.append((vals, CHAIN_ID, BID, 5, _signed_commit(
        vals, pvs, height=5, nil=(0, 1, 5))))
    errs = commit_verify.verify_commits_light_batch(entries, backend="cpu")
    assert [(type(e), str(e)) if e else None for e in errs] == [
        None,
        (commit_verify.VerificationError, "wrong signature in commit"),
        None,
        None,
        (commit_verify.VerificationError,
         "Invalid commit -- wrong height: 6 vs 1"),
        (commit_verify.ErrNotEnoughVotingPowerSigned,
         "invalid commit -- insufficient voting power: got 40, "
         "needed more than 46"),
    ]


# --- Header / Block / PartSet ----------------------------------------------


def _mk_header(vals):
    return Header(
        version_block=11, chain_id=CHAIN_ID, height=1,
        time=1_700_000_000_000_000_000,
        validators_hash=vals.hash(), next_validators_hash=vals.hash(),
        consensus_hash=b"\x01" * 32, app_hash=b"",
        last_results_hash=b"", evidence_hash=b"",
        last_commit_hash=b"", data_hash=b"",
        proposer_address=vals.validators[0].address,
    )


def test_header_hash_deterministic_and_sensitive():
    vals, _ = mk_valset(3)
    h = _mk_header(vals)
    h1 = h.hash()
    assert h1 is not None and len(h1) == 32
    h.height = 2
    assert h.hash() != h1


def test_block_roundtrip_and_partset():
    vals, pvs = mk_valset(4)
    header = _mk_header(vals)
    block = Block(header, txs=[b"tx1", b"tx2"])
    block.fill_header()
    data = block.encode()
    block2 = Block.decode(data)
    assert block2.header == block.header
    assert block2.txs == block.txs
    # part set round trip with proofs
    ps = PartSet.from_data(data, part_size=64)
    ps2 = PartSet.from_header(ps.header())
    for i in range(ps.total):
        assert ps2.add_part(ps.get_part(i))
    assert ps2.is_complete()
    assert ps2.assemble() == data
    # a corrupted part fails its merkle proof
    ps3 = PartSet.from_header(ps.header())
    bad = ps.get_part(0)
    bad.bytes = b"corrupt" + bad.bytes[7:]
    with pytest.raises(ValueError):
        ps3.add_part(bad)


def test_commit_hash_and_bitarray():
    vals, pvs = mk_valset(4)
    bid = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
    commit = _make_commit(vals, pvs, bid, nil_idx=(2,))
    assert len(commit.hash()) == 32
    ba = commit.bit_array()
    assert ba.num_true_bits() == 4  # nil vote still present, absent would be 0


# --- Genesis ---------------------------------------------------------------


def test_genesis_roundtrip(tmp_path):
    pvs = [MockPV() for _ in range(3)]
    doc = GenesisDoc(
        chain_id="gen-chain",
        validators=[GenesisValidator(pv.get_pub_key(), 10) for pv in pvs],
    )
    doc.validate_and_complete()
    p = tmp_path / "genesis.json"
    doc.save_as(str(p))
    doc2 = GenesisDoc.from_file(str(p))
    assert doc2.chain_id == doc.chain_id
    assert doc2.validator_set().hash() == doc.validator_set().hash()
    with pytest.raises(ValueError):
        GenesisDoc.from_json(doc.to_json().replace("gen-chain", ""))

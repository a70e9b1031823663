"""A vote set's sign bytes from one template per block id
(types/vote_set.py ``VoteSet.add_votes`` over
types/vote.py ``vote_sign_bytes_template``): every lane the set hands its
batch verifier carries, byte for byte, ``Vote.sign_bytes(chain_id)`` of its
vote; a block id never lends its bytes to another; equivocation, bad
signatures and the tally behave as before; and
``consensus_vote_sign_templates_total{event}`` counts hits and builds."""
import os

import pytest

from benchmarks.lib import readers
from benchmarks.lib.spec import BENCH_DIR, ROOT, load_json
from tmtpu.crypto import batch as crypto_batch
from tmtpu.libs import metrics
from tmtpu.types import commit_verify  # noqa: F401 - binds verify_commit
from tmtpu.types import pb
from tmtpu.types.block import BlockID
from tmtpu.types.priv_validator import MockPV
from tmtpu.types.validator import Validator, ValidatorSet
from tmtpu.types.vote import PRECOMMIT, PREVOTE, ErrVoteConflictingVotes, \
    Vote
from tmtpu.types.vote_set import VoteSet

CHAIN_ID = "sign-template-chain"
HEIGHT = 37
BID = BlockID(bytes(range(32)), 17, bytes(range(32, 64)))
BID2 = BlockID(b"\x33" * 32, 3, b"\x44" * 32)
NIL = BlockID()
T0 = 1_700_000_000 * 10**9 + 123_456_789


def valset(n, powers=None):
    pvs = [MockPV() for _ in range(n)]
    powers = powers or [10] * n
    vals = ValidatorSet([Validator(pv.get_pub_key(), p)
                         for pv, p in zip(pvs, powers)])
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    return vals, [by_addr[v.address] for v in vals.validators]


def signed(pvs, idx, type=PRECOMMIT, round=0, block_id=BID, timestamp=T0,
           height=HEIGHT):
    pv = pvs[idx]
    v = Vote(type, height, round, block_id, timestamp,
             pv.get_pub_key().address(), idx)
    pv.sign_vote(CHAIN_ID, v)
    return v


@pytest.fixture
def flushed(monkeypatch):
    """Every flush a vote set makes: the (msg, sig) of each lane it added,
    in order, verified by the serial CPU verifier."""
    flushes = []

    class Recording(crypto_batch.CPUBatchVerifier):
        def __init__(self, min_lanes=0):
            super().__init__(min_lanes)
            flushes.append([])

        def add(self, pub_key, msg, sig, power=0):
            flushes[-1].append((msg, sig))
            super().add(pub_key, msg, sig, power)

    monkeypatch.setattr(crypto_batch, "new_batch_verifier",
                        lambda backend=None, min_lanes=0: Recording(min_lanes))
    return flushes


def template_counts():
    s = metrics.consensus_vote_sign_templates.summary_series()
    return s.get("event=hit", 0), s.get("event=built", 0)


def moved(before):
    hit, built = template_counts()
    return hit - before[0], built - before[1]


# -- the bytes ----------------------------------------------------------------

TIMESTAMPS = {
    "zero": 0,
    "negative_one": -1,
    "before_1970": pb.GO_ZERO_NANOS + 1,
    "whole_seconds": 1_700_000_000 * 10**9,
    "nanos_only": 123_456,
    "near_2_63": 2**63 - 1,
    "ordinary": T0,
}


@pytest.mark.parametrize("ts", sorted(TIMESTAMPS))
@pytest.mark.parametrize("round", [0, 3])
@pytest.mark.parametrize("type", [PREVOTE, PRECOMMIT],
                         ids=["prevote", "precommit"])
def test_each_lane_carries_its_votes_sign_bytes(flushed, type, round, ts):
    """Five votes for the block and three nil votes in one flush, the
    case's timestamp on one of each and ordinary ones around it."""
    vals, pvs = valset(8)
    vs = VoteSet(CHAIN_ID, HEIGHT, round, type, vals)
    stamps = [T0 + 1_000 * i for i in range(8)]
    stamps[2] = stamps[6] = TIMESTAMPS[ts]
    votes = [signed(pvs, i, type, round, NIL if i >= 5 else BID, stamps[i])
             for i in range(8)]
    before = template_counts()
    assert vs.add_votes(votes) == [True] * 8
    assert flushed == [[(v.sign_bytes(CHAIN_ID), v.signature)
                        for v in votes]]
    assert moved(before) == (6, 2)
    assert vs.has_two_thirds_any() and not vs.has_two_thirds_majority()


def test_block_ids_whose_keys_collide_keep_their_own_bytes(flushed):
    """BlockID.key() concatenates: a hash one byte longer can swallow the
    first byte of the part count and give the same key."""
    a = BlockID(b"AB", 1, b"XYZ")
    b = BlockID(b"AB\x00", int.from_bytes(b"\x00\x00\x01X", "big"), b"YZ")
    assert a.key() == b.key() and a != b
    vals, pvs = valset(4)
    vs = VoteSet(CHAIN_ID, HEIGHT, 0, PREVOTE, vals)
    votes = [signed(pvs, i, PREVOTE, block_id=(a, b)[i % 2],
                    timestamp=T0 + i) for i in range(4)]
    before = template_counts()
    # each signature verifies only over its own block id's bytes
    assert vs.add_votes(votes) == [True] * 4
    msgs = [m for m, _ in flushed[0]]
    assert msgs == [v.sign_bytes(CHAIN_ID) for v in votes]
    for v, msg in zip(votes, msgs):
        swapped = Vote(v.type, v.height, v.round, b if v.block_id == a else a,
                       v.timestamp, v.validator_address, v.validator_index)
        assert msg != swapped.sign_bytes(CHAIN_ID)
    assert moved(before) == (2, 2)


def test_an_equivocating_pair_in_one_flush(flushed):
    vals, pvs = valset(4)
    vs = VoteSet(CHAIN_ID, HEIGHT, 0, PRECOMMIT, vals)
    va = signed(pvs, 0, block_id=BID, timestamp=T0)
    vb = signed(pvs, 0, block_id=BID2, timestamp=T0)
    other = signed(pvs, 1, block_id=BID, timestamp=T0 + 5)
    with pytest.raises(ErrVoteConflictingVotes) as ei:
        vs.add_votes([va, vb, other])
    msgs = [m for m, _ in flushed[0]]
    assert msgs == [va.sign_bytes(CHAIN_ID), vb.sign_bytes(CHAIN_ID),
                    other.sign_bytes(CHAIN_ID)]
    assert msgs[0] != msgs[1]
    assert ei.value.vote_a is va and ei.value.vote_b is vb
    assert ei.value.results == [True, False, True]
    assert vs.get_by_index(0) is va
    assert vs.sum_voting_power() == 20


@pytest.mark.parametrize("tampered", [0, 3, 5])
def test_a_tampered_signature_is_refused_and_the_tally_exact(flushed,
                                                             tampered):
    powers = [7, 11, 13, 17, 19, 23]
    vals, pvs = valset(6, powers)
    vs = VoteSet(CHAIN_ID, HEIGHT, 1, PREVOTE, vals)
    votes = [signed(pvs, i, PREVOTE, round=1, timestamp=T0 + 10 * i)
             for i in range(6)]
    votes[tampered].signature = bytes(64)
    want = [i != tampered for i in range(6)]
    assert vs.add_votes(votes) == want
    assert vs.sum_voting_power() == sum(
        v.voting_power for i, v in enumerate(vals.validators)
        if i != tampered)
    assert vs.get_by_index(tampered) is None
    # the set kept no trace of the refused lane: a good vote from the same
    # validator goes in afterwards, against the same template
    again = signed(pvs, tampered, PREVOTE, round=1, timestamp=T0 + 1)
    before = template_counts()
    assert vs.add_vote(again)
    assert moved(before) == (1, 0)
    assert vs.sum_voting_power() == sum(powers)
    assert vs.has_all()


def test_a_second_flush_reuses_the_sets_templates(flushed):
    vals, pvs = valset(10)
    vs = VoteSet(CHAIN_ID, HEIGHT, 0, PRECOMMIT, vals)
    first = [signed(pvs, i, block_id=NIL if i == 4 else BID,
                    timestamp=T0 + i) for i in range(5)]
    second = [signed(pvs, i, block_id=NIL if i == 9 else BID,
                     timestamp=T0 + i) for i in range(5, 10)]
    before = template_counts()
    assert vs.add_votes(first) == [True] * 5
    assert moved(before) == (3, 2)
    before = template_counts()
    assert vs.add_votes(second) == [True] * 5
    assert moved(before) == (5, 0)
    assert [m for m, _ in flushed[1]] == [v.sign_bytes(CHAIN_ID)
                                          for v in second]
    assert vs.two_thirds_majority() == (BID, True)
    commit = vs.make_commit()
    vals.verify_commit(CHAIN_ID, BID, HEIGHT, commit)


def test_a_set_of_one_validator_through_add_vote(flushed):
    vals, pvs = valset(1)
    for type in (PREVOTE, PRECOMMIT):
        vs = VoteSet(CHAIN_ID, HEIGHT, 0, type, vals)
        v = signed(pvs, 0, type, timestamp=T0)
        before = template_counts()
        assert vs.add_vote(v)
        assert moved(before) == (0, 1)
        assert flushed[-1] == [(v.sign_bytes(CHAIN_ID), v.signature)]
        assert vs.two_thirds_majority() == (BID, True)
        # an exact duplicate never reaches a flush
        assert not vs.add_vote(v)
        assert len(flushed) == (1 if type == PREVOTE else 2)


def test_more_block_ids_than_the_set_keeps(flushed):
    """Conflicting votes that each name a new block id still get their own
    bytes; past the kept templates each such lane builds its own."""
    vals, pvs = valset(20)
    vs = VoteSet(CHAIN_ID, HEIGHT, 0, PREVOTE, vals)
    ids = [BlockID(bytes([i]) * 32, i + 1, bytes([100 + i]) * 32)
           for i in range(20)]
    votes = [signed(pvs, i, PREVOTE, block_id=ids[i], timestamp=T0 + i)
             for i in range(20)]
    before = template_counts()
    assert vs.add_votes(votes) == [True] * 20
    assert [m for m, _ in flushed[0]] == [v.sign_bytes(CHAIN_ID)
                                          for v in votes]
    assert moved(before) == (0, 20)
    late = [signed(pvs, i, PREVOTE, block_id=ids[i - 1], timestamp=T0)
            for i in (1, 19)]
    before = template_counts()
    with pytest.raises(ErrVoteConflictingVotes):
        vs.add_votes(late)
    assert [m for m, _ in flushed[1]] == [v.sign_bytes(CHAIN_ID)
                                          for v in late]
    # ids[0] was kept, ids[18] came after the set was full
    assert moved(before) == (1, 1)


# -- the metric ---------------------------------------------------------------

NAME = "live_sign_template_hit_pct"
RECORDED = {
    "tendermint_consensus_vote_sign_templates_total": {
        "event=hit": 36_653.0, "event=built": 7.0},
    "tendermint_consensus_vote_flush_lanes": {
        "": {"count": 40.0, "sum": 36_660.0}},
}


def _metric():
    return load_json(os.path.join(BENCH_DIR, "metrics", NAME + ".json"))


def test_the_metric_reads_the_hit_share():
    got = readers.read_metric(_metric(), readers.Readings(
        counters={"program_counter": RECORDED}))
    assert abs(got - 100 * 36_653 / 36_660) < 1e-9


@pytest.mark.parametrize("registry", ["parent", "empty", "idle"])
def test_a_program_without_the_counter_leaves_the_metric_out(registry):
    parent = {k: v for k, v in RECORDED.items() if "sign_templates" not in k}
    table = {"parent": parent, "empty": {},
             "idle": dict(parent, **{
                 "tendermint_consensus_vote_sign_templates_total": {}})}
    assert readers.read_metric(_metric(), readers.Readings(
        counters={"program_counter": table[registry]})) is None


def test_the_entry_is_appended_and_agrees_with_the_file():
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = [m for m in bench["per_layer"] if m["name"] == NAME]
    mfile = _metric()
    assert [{k: v for k, v in e.items() if k != "workloads"}
            for e in entries] == [{k: mfile[k] for k in (
                "name", "unit", "better", "source", "layer", "moves")}]
    # the file's cell, and the staking cell that reads it beside
    assert entries[0]["workloads"] == mfile["workloads"] + [
        "staking10k.live-rounds"] == ["valset10k.live-rounds",
                                      "staking10k.live-rounds"]
    assert entries[0]["layer"] in {m["layer"] for m in bench["per_layer"]
                                   if m["name"] != NAME}

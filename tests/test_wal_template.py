"""A drain's vote records from a template, in one append
(consensus/wal.py ``vote_record_template`` / ``WAL.write_records``,
consensus/state.py ``_wal_write_msgs``): every byte on file is what the
reflective encoder gives for the same message and time, the records keep
their arrival order, and the node's own messages are fsync'd where the
record-at-a-time loop this replaced fsync'd them."""
import itertools
import os
import struct
import time
import zlib

import pytest

from tests.test_live_rounds import (  # noqa: F401  (fixtures)
    N_VAL, chain, genesis_of, live,
)
from tests.test_replay import _mk_node
from tests.test_wal_recovery import _clean_faults  # noqa: F401  (autouse)
from tmtpu.consensus.state import (
    BlockPartMessage, ConsensusState, MsgInfo, ProposalMessage, VoteMessage,
)
from tmtpu.consensus.types import STEP_PRECOMMIT
from tmtpu.consensus.wal import WAL, MsgInfoPB, WALMessagePB
from tmtpu.crypto import merkle
from tmtpu.e2e import flood_round
from tmtpu.libs import faultinject, metrics, protoio, trace
from tmtpu.types import pb
from tmtpu.types.block import BlockID
from tmtpu.types.genesis import GenesisDoc, GenesisValidator
from tmtpu.types.part_set import Part
from tmtpu.types.priv_validator import MockPV
from tmtpu.types.vote import PRECOMMIT, PREVOTE, Proposal, Vote

T0 = 1_760_000_000_123_456_789      # the patched clock's first reading
BID = BlockID(b"\x11" * 32, 17, b"\x22" * 32)
BID2 = BlockID(b"\x33" * 32, 3, b"\x44" * 32)
PEER = "ab" * 20                    # a node id: 40 hex characters


@pytest.fixture
def clock(monkeypatch):
    """``time.time_ns`` reads T0, T0 + step, ...: every record its own
    time, the same sequence for whoever writes the same messages."""
    def patch(start=T0, step=1_000_003):
        ticks = itertools.count(start, step)
        monkeypatch.setattr(time, "time_ns", lambda: next(ticks))
    patch()
    return patch


def vote(type=PRECOMMIT, height=12, round=0, block_id=BID,
         timestamp=1_700_000_000_987_654_321, address=b"\xaa" * 20, index=7,
         signature=b"\x5a" * 64):
    return Vote(type, height, round, block_id, timestamp, address, index,
                signature)


def bare_state(path, **wal_kw):
    """``_wal_write_msgs`` reads two attributes of the state machine."""
    cs = ConsensusState.__new__(ConsensusState)
    cs.wal = WAL(path, **wal_kw)
    cs.replay_mode = False
    return cs


def record(payload):
    return (struct.pack(">I", zlib.crc32(payload))
            + protoio.encode_uvarint(len(payload)) + payload)


def reflective_msg(mi):
    """The message as the record-at-a-time loop built it."""
    m = mi.msg
    if isinstance(m, VoteMessage):
        info = MsgInfoPB(peer_id=mi.peer_id, vote=m.vote.to_proto())
    elif isinstance(m, ProposalMessage):
        info = MsgInfoPB(peer_id=mi.peer_id, proposal=m.proposal.to_proto())
    else:
        info = MsgInfoPB(peer_id=mi.peer_id, block_part_height=m.height,
                         block_part_round=m.round,
                         block_part=m.part.to_proto())
    return WALMessagePB(time=pb.Timestamp.from_unix_nanos(time.time_ns()),
                        msg_info=info)


def parents_loop(wal, msgs):
    """consensus/state.py before the template: a record, a lock, a write
    and a rotation check a message, ``write_sync`` for the node's own."""
    for mi in msgs:
        if mi.peer_id == "":
            wal.write_sync(reflective_msg(mi))
        else:
            wal.write(reflective_msg(mi))


def votes_of(*pairs):
    return [MsgInfo(VoteMessage(v), peer) for peer, v in pairs]


def _mixed_drain():
    part = Part(0, b"\x07" * 300, merkle.Proof(1, 0, b"\x01" * 32, []))
    proposal = Proposal(12, 1, -1, BID, timestamp=T0 - 5,
                        signature=b"\x0b" * 64)
    return (
        votes_of(("aa" * 20, vote(PREVOTE, index=1)),
                 ("bb" * 20, vote(PREVOTE, index=2)),
                 ("aa" * 20, vote(PRECOMMIT, index=3)),
                 ("aa" * 20, vote(PREVOTE, index=4, block_id=BID2)))
        + [MsgInfo(ProposalMessage(proposal), "bb" * 20),
           MsgInfo(BlockPartMessage(12, 1, part), "aa" * 20)]
        + votes_of(("bb" * 20, vote(PRECOMMIT, index=5, block_id=BlockID())),
                   ("aa" * 20, vote(PREVOTE, index=6)),
                   ("", vote(PRECOMMIT, index=9)),
                   ("bb" * 20, vote(PREVOTE, index=8, round=1))))


DRAINS = {
    "prevote": lambda: votes_of((PEER, vote(PREVOTE))),
    "precommit": lambda: votes_of((PEER, vote(PRECOMMIT))),
    "nil_block_id": lambda: votes_of((PEER, vote(block_id=BlockID()))),
    "round_0": lambda: votes_of((PEER, vote(round=0))),
    "round_3": lambda: votes_of((PEER, vote(round=3))),
    "height_1": lambda: votes_of((PEER, vote(height=1))),
    "height_2_31": lambda: votes_of((PEER, vote(height=2**31 + 5))),
    "height_2_62": lambda: votes_of((PEER, vote(height=2**62 + 1))),
    "time_nanos_0": lambda: votes_of(
        (PEER, vote(timestamp=1_700_000_000 * 10**9))),
    "time_seconds_0": lambda: votes_of((PEER, vote(timestamp=123_456))),
    "time_0": lambda: votes_of((PEER, vote(timestamp=0))),
    "time_before_1970": lambda: votes_of(
        (PEER, vote(timestamp=pb.GO_ZERO_NANOS + 1))),
    "index_0": lambda: votes_of((PEER, vote(index=0))),
    "index_127": lambda: votes_of((PEER, vote(index=127))),
    "index_128": lambda: votes_of((PEER, vote(index=128))),
    "index_9999": lambda: votes_of((PEER, vote(index=9_999))),
    "index_16384": lambda: votes_of((PEER, vote(index=16_384))),
    "signature_empty": lambda: votes_of((PEER, vote(signature=b""))),
    "signature_64": lambda: votes_of((PEER, vote(signature=b"\xc3" * 64))),
    "address_empty": lambda: votes_of((PEER, vote(address=b""))),
    "peer_own": lambda: votes_of(("", vote())),
    "peer_40": lambda: votes_of((PEER, vote())),
    "peer_200": lambda: votes_of(("p" * 200, vote())),
    "thousand_of_one_group": lambda: votes_of(*(
        (PEER, vote(index=i, timestamp=1_700_000_000 * 10**9 + i * 977_001,
                    address=bytes([i % 251]) * 20,
                    signature=bytes([i % 253]) * 64))
        for i in range(1_000))),
    "two_peers_both_types_two_block_ids": _mixed_drain,
}


@pytest.mark.parametrize("case", sorted(DRAINS))
def test_every_record_is_the_reflective_encoders_bytes(case, tmp_path, clock):
    msgs = DRAINS[case]()
    path = str(tmp_path / "wal")
    cs = bare_state(path)
    n_template = cs._wal_write_msgs(msgs)
    cs.wal.close()
    clock()                       # the same readings for the reference
    want = [reflective_msg(mi) for mi in msgs]
    with open(path, "rb") as f:
        assert f.read() == b"".join(record(m.encode()) for m in want)
    assert list(WAL.iter_messages(path, strict=True)) == want
    assert n_template == sum(isinstance(mi.msg, VoteMessage) for mi in msgs)


def test_a_record_time_on_a_whole_second(tmp_path, clock):
    """The record's own Timestamp leaves its nanos off the wire too."""
    clock(start=1_760_000_000 * 10**9, step=10**9)
    msgs = votes_of((PEER, vote()), (PEER, vote(index=8)))
    cs = bare_state(str(tmp_path / "wal"))
    cs._wal_write_msgs(msgs)
    cs.wal.close()
    clock(start=1_760_000_000 * 10**9, step=10**9)
    with open(cs.wal.path, "rb") as f:
        assert f.read() == b"".join(record(reflective_msg(mi).encode())
                                    for mi in msgs)


def test_the_counters_move_once_a_drain_by_exact_counts(tmp_path):
    def series():
        return (dict(metrics.consensus_wal_records.summary_series()),
                metrics.consensus_wal_appends.summary_series().get("", 0))
    cs = bare_state(str(tmp_path / "wal"))
    recs0, appends0 = series()
    assert cs._wal_write_msgs(_mixed_drain()) == 8
    cs.wal.write_end_height(12)
    recs, appends = series()
    assert recs["path=template"] - recs0.get("path=template", 0) == 8
    # the proposal, the part, the end-height marker
    assert recs["path=reflective"] - recs0.get("path=reflective", 0) == 3
    # the run up to the own precommit, the run after it, the marker
    assert appends - appends0 == 3
    cs.wal.close()
    cs.replay_mode = True       # a replaying node writes nothing
    assert cs._wal_write_msgs(_mixed_drain()) == 0
    assert series() == (recs, appends)


# -- durability and order -----------------------------------------------------

@pytest.fixture
def fsyncs(monkeypatch):
    """Every ``os.fsync``: the bytes the file held when it was called."""
    seen = []
    real = os.fsync

    def fsync(fd):
        seen.append(os.fstat(fd).st_size)
        real(fd)
    monkeypatch.setattr(os, "fsync", fsync)
    return seen


def _one_validator_node(tmp_path):
    pv = MockPV()
    gen = GenesisDoc(chain_id="wal-template", genesis_time=T0,
                     validators=[GenesisValidator(pv.get_pub_key(), 10)])
    cs, _ = _mk_node(gen, pv, wal_path=str(tmp_path / "cs.wal" / "wal"))
    return cs


def _drain_with_an_own_vote_in_the_middle():
    return (votes_of(*((PEER, vote(PREVOTE, index=i)) for i in range(1, 6)))
            + votes_of(("", vote(PREVOTE, index=0)))
            + votes_of(*((PEER, vote(PRECOMMIT, index=i))
                         for i in range(1, 6))))


def test_fsyncs_as_often_over_the_same_bytes_and_before_handling(
        tmp_path, clock, fsyncs, monkeypatch):
    msgs = _drain_with_an_own_vote_in_the_middle()
    ref = WAL(str(tmp_path / "parent"))
    parents_loop(ref, msgs)
    want_fsyncs, want_size = list(fsyncs), ref._f.tell()
    ref.close()
    assert len(want_fsyncs) == 1 and 0 < want_fsyncs[0] < want_size
    cs = _one_validator_node(tmp_path)
    drains = iter([(msgs, []), None])
    monkeypatch.setattr(cs, "_drain_messages", lambda: next(drains))
    at_entry = []

    def handle(handled):
        at_entry.append((list(fsyncs), cs.wal._f.tell(), handled))
    monkeypatch.setattr(cs, "_handle_msgs", handle)
    spans0 = trace.span_totals().get("consensus.wal", (0, 0))[0]
    del fsyncs[:]
    clock()
    cs._receive_routine()
    # one span a drain; every record handed over, the own vote and what
    # came before it synced, when the first message is handled
    assert trace.span_totals()["consensus.wal"][0] - spans0 == 1
    assert at_entry == [(want_fsyncs, want_size, msgs)]
    cs.wal.close()
    with open(ref.path, "rb") as a, open(cs.wal.path, "rb") as b:
        assert a.read() == b.read()


@pytest.mark.parametrize("k", [0, 2, 7, 10])
def test_a_fault_at_record_k_leaves_k_records_and_halts_the_node(
        tmp_path, clock, monkeypatch, capsys, k):
    msgs = _drain_with_an_own_vote_in_the_middle()
    cs = _one_validator_node(tmp_path)
    drains = iter([(msgs, [])])
    monkeypatch.setattr(cs, "_drain_messages", lambda: next(drains))
    handled = []
    monkeypatch.setattr(cs, "_handle_msgs", handled.append)
    faultinject.script("wal.write", faultinject.ERROR, count=1, after=k)
    clock()
    cs._receive_routine()       # returns: the node halts, the WAL is kept
    assert "injected fault at site 'wal.write'" in capsys.readouterr().err
    assert handled == []
    assert faultinject.active() == {}            # fired once, at record k
    clock()
    want = [reflective_msg(mi) for mi in msgs[:k]]
    assert list(WAL.iter_messages(cs.wal.path, strict=True)) == want
    cs.wal.close()


def test_a_head_that_fills_mid_drain_rotates_at_a_record_boundary(
        tmp_path, clock):
    drains = [votes_of(*((PEER, vote(index=100 * d + i)) for i in range(20)))
              for d in range(5)]
    one_drain = sum(len(record(reflective_msg(mi).encode()))
                    for mi in drains[0])
    limit = one_drain + one_drain // 2      # passed inside the second drain
    path = str(tmp_path / "wal")
    cs = bare_state(path, head_size_limit=limit)
    clock()
    for msgs in drains:
        cs._wal_write_msgs(msgs)
        assert cs.wal._f.tell() < limit     # checked after every run
    cs.wal.close()
    files = WAL._group_files(path)
    assert len(files) == 2
    for p in files:
        # whole records only, and at most one run beyond the limit
        status = {}
        assert len(list(WAL._iter_one(p, strict=True, status=status))) == 40
        assert status["clean"]
        assert limit <= os.path.getsize(p) < limit + one_drain
    clock()
    want = [reflective_msg(mi) for msgs in drains for mi in msgs]
    status = {}
    assert list(WAL.iter_messages(path, strict=True, status=status)) == want
    assert status["clean"] and status["records"] == 100


def test_catchup_replay_from_a_height_the_new_path_wrote(chain, live,
                                                         tmp_path):
    """A height cut after the node's own precommit: the file is, record
    for record, what the reflective encoder gives for the same messages
    and times (the parent's file), and a restarted node replays it to the
    round state the live node was in."""
    cs = live.cs
    live.start()
    live.play_height(1, 10)
    live.wait_entered(2, 10)
    hd = chain.heights[1]
    live.relay.proposal(hd.proposal, hd.parts)
    live.relay.votes(hd.prevotes)
    own = chain.node
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not (
            cs.rs.height == 2 and cs.rs.votes.precommits(0) is not None
            and cs.rs.votes.precommits(0).get_by_index(own) is not None):
        time.sleep(0.02)
    cs.stop()
    rs = cs.rs
    assert (rs.height, rs.round, rs.step) == (2, 0, STEP_PRECOMMIT)

    msgs = list(WAL.iter_messages(cs.wal.path, strict=True))
    with open(cs.wal.path, "rb") as f:
        assert f.read() == b"".join(record(m.encode()) for m in msgs)
    last = msgs[-1].msg_info
    assert last.peer_id == "" and last.vote.type == PRECOMMIT \
        and last.vote.validator_index == own
    votes = [m.msg_info.vote for m in msgs
             if m.msg_info is not None and m.msg_info.vote is not None]
    assert len(votes) == 2 * N_VAL + N_VAL + 1   # height 1, then to here

    again = flood_round.build_node(
        str(tmp_path), genesis_of(chain), cs.priv_validator
    )["consensus"]
    assert again.rs.height == 2 and again.rs.step < STEP_PRECOMMIT
    again.catchup_replay(live_redrive=False)
    rs2 = again.rs
    assert (rs2.height, rs2.round, rs2.step) == (2, 0, STEP_PRECOMMIT)
    assert rs2.proposal_block.hash() == rs.proposal_block.hash() \
        == hd.block.hash
    assert rs2.locked_block.hash() == hd.block.hash and rs2.locked_round == 0
    for vtype in ("prevotes", "precommits"):
        a, b = (getattr(r.votes, vtype)(0) for r in (rs, rs2))
        assert [a.get_by_index(i) for i in range(N_VAL)] \
            == [b.get_by_index(i) for i in range(N_VAL)]
    assert rs2.votes.prevotes(0).has_two_thirds_majority()
    again.wal.close()

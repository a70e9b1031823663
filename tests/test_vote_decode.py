"""The vote channel's hand decoder (tmtpu/consensus/msgs.py VoteDecoder)
against the reflective walk it stands in for,
``Vote.from_proto(ConsensusMessagePB.decode(b).vote.vote)``: on every input
the hand path gives an equal ``Vote``, or says "not mine" and the reactor's
reflective path gives what it gave before, result or exception."""
import io
import random
import types

import pytest

from benchmarks.reference import rounds as rr
from tmtpu.consensus import msgs as cm
from tmtpu.consensus.reactor import (
    DATA_CHANNEL, VOTE_CHANNEL, ConsensusReactor, PeerState,
)
from tmtpu.libs import metrics, protoio
from tmtpu.types import pb
from tmtpu.types.block import BlockID
from tmtpu.types.vote import PRECOMMIT, PREVOTE, Vote

GO_ZERO_TIME_S = -62_135_596_800        # time.Time{} as a Timestamp
T0 = 1_700_000_000 * 10**9
BID = BlockID(bytes(range(32)), 17, bytes(range(32, 64)))
ADDR = bytes(range(100, 120))
SIG = bytes(range(64))


def a_vote(type=PRECOMMIT, height=37, round=0, block_id=BID,
           timestamp=T0 + 10**9 + 1000 * 9_998, address=ADDR, index=9_998,
           signature=SIG) -> Vote:
    return Vote(type, height, round, block_id, timestamp, address, index,
                signature)


def uvarint(n: int) -> bytes:
    return protoio.encode_uvarint(n)


def vote_fields(v: Vote) -> list:
    """pb.Vote's eight fields as the reflective encoder writes each (a
    zero scalar or empty byte string is b"")."""
    m, out = v.to_proto(), []
    for fn, name, spec in pb.Vote.FIELDS:
        w = io.BytesIO()
        protoio._encode_field(w, fn, spec, getattr(m, name))
        out.append(w.getvalue())
    return out


def envelope(body: bytes, in_vote_pb: bytes = b"", after: bytes = b"",
             before: bytes = b"") -> bytes:
    vote_pb = b"\x0a" + uvarint(len(body)) + body + in_vote_pb
    return before + b"\x32" + uvarint(len(vote_pb)) + vote_pb + after


def wire(v: Vote) -> bytes:
    return cm.ConsensusMessagePB(vote=cm.VotePB(vote=v.to_proto())).encode()


def exploded(v: Vote):
    """Every field with its type: an equal Vote of the same stuff."""
    b = v.block_id
    return [(type(x), x) for x in (
        v.type, v.height, v.round, b.hash, b.parts_total, b.parts_hash,
        v.timestamp, v.validator_address, v.validator_index, v.signature)]


def reflective(b: bytes):
    """What the reactor made of a vote-channel message before the hand
    decoder: ("vote", fields), ("nothing", None) for another oneof arm
    (``which()`` names the first arm set), ("raises", exception type)."""
    try:
        m = cm.ConsensusMessagePB.decode(b)
        if m.which() != "vote":
            return "nothing", None
        return "vote", exploded(Vote.from_proto(m.vote.vote))
    except Exception as e:
        return "raises", type(e)


# -- the shapes that are the hand decoder's ----------------------------------

def _reference_wire() -> bytes:
    """benchmarks/reference/rounds.py vote_wire, the cell's own bytes, for
    the last validator of 10,000."""
    v = rr.Vote(rr.PRECOMMIT, 37, 0, (BID.hash, BID.parts_total,
                                      BID.parts_hash),
                T0 + 10**9 + 1000 * 9_999, 9_999, SIG)
    return rr.vote_wire(types.SimpleNamespace(addrs={9_999: ADDR}), v)


HITS = {
    "prevote": wire(a_vote(type=PREVOTE)),
    "precommit": wire(a_vote(type=PRECOMMIT)),
    "round 0": wire(a_vote(round=0)),
    "round 3": wire(a_vote(round=3)),
    "nil block id": wire(a_vote(block_id=BlockID())),
    "index 0": wire(a_vote(index=0)),
    "empty address": wire(a_vote(address=b"")),
    "empty signature": wire(a_vote(signature=b"")),
    "timestamp 0": wire(a_vote(timestamp=0)),
    "nanos only": wire(a_vote(timestamp=999_999_999)),
    "go zero time": wire(a_vote(timestamp=GO_ZERO_TIME_S * 10**9)),
    "negative seconds and nanos": wire(a_vote(
        timestamp=GO_ZERO_TIME_S * 10**9 + 123)),
    "height 0 type 0": wire(a_vote(type=0, height=0)),
    "a large height": wire(a_vote(height=2**62)),
    "reference vote_wire 10k": _reference_wire(),
    "two-byte lengths spelled long": (
        lambda b: b"\x32" + bytes((b[1] | 0x80, 0)) + b[2:])(
            wire(a_vote(address=b"", signature=b"", block_id=BlockID()))),
}


@pytest.mark.parametrize("name", sorted(HITS))
def test_a_canonical_vote_is_decoded_by_hand(name):
    b = HITS[name]
    got = cm.VoteDecoder().decode(b)
    assert got is not None, "not a hit"
    assert ("vote", exploded(got)) == reflective(b)


def test_go_zero_time_is_a_ten_byte_varint():
    v = a_vote(timestamp=GO_ZERO_TIME_S * 10**9)
    assert b"\x08" + protoio.encode_varint(GO_ZERO_TIME_S) in wire(v)
    assert len(protoio.encode_varint(GO_ZERO_TIME_S)) == 10
    assert cm.VoteDecoder().decode(wire(v)).timestamp == v.timestamp


def test_a_steps_votes_share_one_block_id_object():
    d = cm.VoteDecoder()
    a = d.decode(wire(a_vote(index=1)))
    b = d.decode(wire(a_vote(index=2, timestamp=T0 + 5)))
    c = d.decode(wire(a_vote(index=3, type=PREVOTE)))
    assert a.block_id is b.block_id and a.block_id is not c.block_id
    assert a.block_id == c.block_id == BID and len(d.heads) == 2


# -- the shapes that are not -------------------------------------------------

def _not_mine() -> dict:
    f = vote_fields(a_vote(round=3))
    body = b"".join(f)
    unknown = b"\x78\x05"                       # field 15, varint
    ts_body = f[4][2:]
    vote_pb = b"\x0a" + uvarint(len(body)) + body
    return {
        "trace_ctx present": cm.ConsensusMessagePB(
            vote=cm.VotePB(vote=a_vote().to_proto()),
            trace_ctx=b"\x01" * 25).encode(),
        "trace_ctx first": envelope(body, before=b"\x52\x02ab"),
        "unknown field in the envelope": envelope(body, after=unknown),
        "unknown field in VotePB": envelope(body, in_vote_pb=unknown),
        "unknown field in the vote": envelope(body + unknown),
        "unknown field in the head": envelope(
            b"".join(f[:2]) + unknown + b"".join(f[2:])),
        "unknown field before the address": envelope(
            b"".join(f[:5]) + unknown + b"".join(f[5:])),
        "signature before index": envelope(
            b"".join(f[:5]) + f[5] + f[7] + f[6]),
        "index before address": envelope(b"".join(f[:5]) + f[6] + f[5] + f[7]),
        "address repeated": envelope(b"".join(f[:6]) + f[5] + f[6] + f[7]),
        "height repeated": envelope(f[0] + f[1] + f[1] + b"".join(f[2:])),
        "timestamp repeated": envelope(b"".join(f[:5]) + f[4]
                                       + b"".join(f[5:])),
        "round before height": envelope(f[0] + f[2] + f[1] + b"".join(f[3:])),
        "block id before type": envelope(f[3] + b"".join(f[:3])
                                         + b"".join(f[4:])),
        "no timestamp field": envelope(b"".join(f[:4]) + b"".join(f[5:])),
        "nanos before seconds": envelope(
            b"".join(f[:4]) + b"\x2a" + uvarint(len(ts_body))
            + ts_body[6:] + ts_body[:6] + b"".join(f[5:])),
        "unknown field in the timestamp": envelope(
            b"".join(f[:4]) + b"\x2a" + uvarint(len(ts_body) + 2)
            + ts_body + unknown + b"".join(f[5:])),
        "seconds repeated": envelope(
            b"".join(f[:4]) + b"\x2a" + uvarint(len(ts_body) + 6)
            + ts_body[:6] + ts_body + b"".join(f[5:])),
        "another oneof arm": cm.ConsensusMessagePB(has_vote=cm.HasVotePB(
            height=37, round=0, type=PRECOMMIT, index=5)).encode(),
        "a second oneof arm after the vote": envelope(
            body, after=b"\x3a\x02\x08\x25"),
        "an over-long varint in the index": envelope(
            b"".join(f[:6]) + b"\x38" + b"\xff" * 12 + b"\x01" + f[7]),
        "an over-long varint in the head": envelope(
            f[0] + b"\x10" + b"\xff" * 12 + b"\x01" + b"".join(f[2:])),
        "an over-long varint in the timestamp": envelope(
            b"".join(f[:4]) + b"\x2a\x0e\x08" + b"\xff" * 12 + b"\x01"
            + b"".join(f[5:])),
        "the envelope's length overshoots": b"\x32" + uvarint(
            len(vote_pb) + 9) + vote_pb,
        "the envelope's length falls short": b"\x32" + uvarint(
            len(vote_pb) - 2) + vote_pb,
        "VotePB's length overshoots": envelope(body)[:3] + b"\x0a"
        + uvarint(len(body) + 1) + body,
        "the signature's length overshoots": envelope(
            b"".join(f[:7]) + b"\x42\x41" + SIG),
        "a seconds varint across the timestamp's end": envelope(
            b"".join(f[:4]) + b"\x2a\x02\x08\xff" + b"".join(f[5:])),
        "a wire type that is not the field's": envelope(
            b"\x0a" + body[1:]),
        "a two-byte tag": envelope(b"\x88\x00" + body[1:]),
        "a bytearray": bytearray(wire(a_vote())),
        "nothing": b"",
    }


NOT_MINE = _not_mine()


@pytest.mark.parametrize("name", sorted(NOT_MINE))
def test_another_shape_is_left_to_the_reflective_decoder(name):
    """"Not mine", and what the reactor does with it is what it did before
    the hand decoder: the reflective vote handed over, nothing for another
    arm, or the reflective decoder's exception."""
    b = NOT_MINE[name]
    assert cm.VoteDecoder().decode(b) is None
    kind, want = reflective(bytes(b))
    r, cs, peer = a_reactor()
    if kind == "raises":
        with pytest.raises(want):
            r.receive(VOTE_CHANNEL, peer, b)
    else:
        r.receive(VOTE_CHANNEL, peer, b)
    assert [exploded(v) for v, _ in cs.handed] == \
        ([want] if kind == "vote" else [])


def test_every_truncation_of_a_message():
    b = wire(a_vote())
    assert len(b) == 188
    d = cm.VoteDecoder()
    assert d.decode(b) is not None
    for cut in range(len(b)):
        assert d.decode(b[:cut]) is None, cut
        assert_like_reflective(b[:cut])


def assert_like_reflective(b: bytes) -> None:
    """The reactor's outcome on ``b``, hand path or fallback, is the
    reflective decoder's: an equal Vote handed over, nothing, or the same
    exception type."""
    r, cs, peer = a_reactor()
    try:
        r.receive(VOTE_CHANNEL, peer, b)
        got = ("vote", exploded(cs.handed[0][0])) if cs.handed \
            else ("nothing", None)
    except Exception as e:
        got = ("raises", type(e))
    assert got == reflective(b)


def test_fuzz_mutations_and_splices_against_the_reflective_decoder():
    rng = random.Random(37)
    pool = [HITS[k] for k in sorted(HITS)] + [
        bytes(NOT_MINE[k]) for k in ("trace_ctx present", "another oneof arm",
                                     "unknown field in the vote")]
    d = cm.VoteDecoder()
    hits = 0
    for i in range(3_000):
        a = rng.choice(pool)
        if i % 3:
            m = bytearray(a)
            m[rng.randrange(len(m))] = rng.randrange(256)
            b = bytes(m)
        else:
            o = rng.choice(pool)
            b = a[:rng.randrange(len(a) + 1)] + o[rng.randrange(len(o) + 1):]
        got = d.decode(b)
        if got is not None:
            hits += 1
            assert ("vote", exploded(got)) == reflective(b), b.hex()
        assert_like_reflective(b)
    # the fuzz reaches both sides: a mutated signature byte is still a hit
    assert 300 < hits < 2_700 and len(d.heads) <= d.MAX_HEADS


def test_the_head_table_stays_under_its_cap():
    d = cm.VoteDecoder()
    for h in range(1, 1_001):
        v = a_vote(height=h)
        assert d.decode(wire(v)) == v
        assert len(d.heads) <= d.MAX_HEADS
    assert 0 < len(d.heads) <= d.MAX_HEADS
    # a head that does not decode is not kept
    before = dict(d.heads)
    f = vote_fields(a_vote(height=5_000))
    assert d.decode(envelope(f[0] + f[1] + f[2] + b"\x22\x03\x0a\x20\x01"
                             + b"".join(f[4:]))) is None
    assert d.heads == before


# -- the reactor ---------------------------------------------------------------

class StubState:
    """What ConsensusReactor asks of a ConsensusState on the vote channel."""

    def __init__(self, n_validators=10_000):
        self.config = None
        self.event_bus = None
        self.handed = []
        vals = types.SimpleNamespace(size=lambda: n_validators)
        self._rs = types.SimpleNamespace(validators=vals, height=37)

    def round_state_nolock(self):
        return self._rs

    def add_vote_msg(self, vote, peer_id=""):
        self.handed.append((vote, peer_id))


class StubPeer:
    node_id = "relay"

    def __init__(self):
        self._data = {}

    def get(self, key):
        return self._data.get(key)

    def set(self, key, value):
        self._data[key] = value


def a_reactor(wait_sync=False):
    cs = StubState()
    return ConsensusReactor(cs, wait_sync=wait_sync), cs, StubPeer()


def decode_counts():
    s = metrics.consensus_vote_decode.summary_series()
    return s.get("path=hand", 0), s.get("path=reflective", 0)


def marks(peer):
    ps = peer.get("consensus_peer_state")
    return {(kind, r): (ba.size(), list(ba.true_indices()))
            for kind, table in (("prevote", ps.prevotes),
                                ("precommit", ps.precommits))
            for r, ba in table.items()}


def traced(v: Vote) -> bytes:
    return cm.ConsensusMessagePB(vote=cm.VotePB(vote=v.to_proto()),
                                 trace_ctx=b"\x07" * 20).encode()


@pytest.mark.parametrize("path", ["hand", "reflective"])
def test_the_reactor_hands_over_the_same_vote_on_both_paths(path):
    encode = wire if path == "hand" else traced
    r, cs, peer = a_reactor()
    ps = PeerState()
    ps.height = 37
    peer.set("consensus_peer_state", ps)
    votes = [a_vote(type=t, index=i, timestamp=T0 + i)
             for t in (PREVOTE, PRECOMMIT) for i in (0, 7, 9_998)]
    hand0, refl0 = decode_counts()
    for v in votes:
        r.receive(VOTE_CHANNEL, peer, encode(v))
    assert [exploded(v) for v, _ in cs.handed] == [exploded(v) for v in votes]
    assert {p for _, p in cs.handed} == {"relay"}
    assert marks(peer) == {("prevote", 0): (10_000, [0, 7, 9_998]),
                           ("precommit", 0): (10_000, [0, 7, 9_998])}
    hand, refl = decode_counts()
    assert (hand - hand0, refl - refl0) == \
        ((6, 0) if path == "hand" else (0, 6))


def test_the_counter_reads_hand_and_reflective_as_sent():
    r, cs, peer = a_reactor()
    hand0, refl0 = decode_counts()
    sent = [wire(a_vote(index=i)) for i in range(40)] \
        + [traced(a_vote(index=50))] \
        + [NOT_MINE["another oneof arm"], NOT_MINE["index before address"]]
    for b in sent:
        r.receive(VOTE_CHANNEL, peer, b)
    # other channels are not counted
    r.receive(DATA_CHANNEL, peer, NOT_MINE["another oneof arm"])
    with pytest.raises(EOFError):
        r.receive(VOTE_CHANNEL, peer, wire(a_vote())[:100])
    hand, refl = decode_counts()
    assert (hand - hand0, refl - refl0) == (40, 4)
    assert len(cs.handed) == 42
    text = metrics.DEFAULT.render()
    assert 'tendermint_consensus_vote_decode_total{path="hand"}' in text
    assert 'tendermint_consensus_vote_decode_total{path="reflective"}' in text


@pytest.mark.parametrize("path", ["hand", "reflective"])
def test_wait_sync_hands_over_nothing_and_raises_nothing(path):
    r, cs, peer = a_reactor(wait_sync=True)
    r.receive(VOTE_CHANNEL, peer, (wire if path == "hand" else traced)(
        a_vote()))
    assert cs.handed == []
    ps = peer.get("consensus_peer_state")
    assert isinstance(ps, PeerState) and not ps.prevotes \
        and not ps.precommits
    # a malformed message raises from the reflective decoder, as before
    with pytest.raises(EOFError):
        r.receive(VOTE_CHANNEL, peer, wire(a_vote())[:-3])


def test_the_hand_path_is_the_cheaper_one(monkeypatch):
    """Not a timing: the hand path builds one Vote and no ProtoMessage."""
    built = []
    init = protoio.ProtoMessage.__init__

    def counting(self, **kw):
        built.append(type(self).__name__)
        init(self, **kw)

    d = cm.VoteDecoder()
    b = wire(a_vote())
    d.decode(b)                     # learns the head
    monkeypatch.setattr(protoio.ProtoMessage, "__init__", counting)
    assert d.decode(b) is not None and built == []
    cm.ConsensusMessagePB.decode(b)
    assert len(built) >= 10         # fourteen, with every msg! default

"""A Commit's protobuf wire form by hand (types/pb.py Commit, CommitSig):
the bytes and the fields are the reflective codec's for every input, the
canonical shape is encoded from and decoded into plain rows, every other
shape is left to the reflective decoder, whose result or exception
stands; ``types_commit_codec_total{dir,path}`` moves once a Commit."""

import random

import pytest

from tmtpu.blocksync.msgs import BlockResponsePB, BlocksyncMessagePB
from tmtpu.crypto.merkle import hash_from_byte_slices
from tmtpu.libs import metrics
from tmtpu.libs.protoio import ProtoMessage, encode_varint
from tmtpu.types import pb
from tmtpu.types.block import (BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT,
                               BLOCK_ID_FLAG_NIL, Block, BlockID, Commit,
                               CommitSig, Header)
from tmtpu.types.light_block import LightBlock, SignedHeader

from tests.test_types import mk_valset

INT64_MAX = (1 << 63) - 1
# unix nanos: 0, before 1970, whole seconds, and near int64's limits, both
# as nanoseconds and as the Timestamp's seconds
EDGE_TIMES = [0, -1, -1_000_000_000, -62135596800 * 1_000_000_000,
              1_700_000_000 * 1_000_000_000, 1, 999_999_999,
              INT64_MAX, -INT64_MAX - 1,
              INT64_MAX * 1_000_000_000 + 999_999_999,
              (-INT64_MAX - 1) * 1_000_000_000]


def mk_commit(n: int, seed: int = 0) -> Commit:
    r = random.Random(seed)
    sigs = []
    for i in range(n):
        kind = r.random()
        if kind < 0.2:
            sigs.append(CommitSig.absent())
            continue
        flag = BLOCK_ID_FLAG_COMMIT if kind < 0.8 else BLOCK_ID_FLAG_NIL
        ts = (EDGE_TIMES[i % len(EDGE_TIMES)] if r.random() < 0.3
              else 1_700_000_000 * 10**9 + r.randrange(3 * 10**9))
        sig = b"" if r.random() < 0.1 else r.randbytes(64)
        sigs.append(CommitSig(flag, r.randbytes(20), ts, sig))
    return Commit(r.randrange(1, 10**6), r.randrange(3),
                  BlockID(r.randbytes(32), 1 + r.randrange(4),
                          r.randbytes(32)), sigs)


def reflective_pb(c: Commit) -> pb.Commit:
    """The Commit as the reflective codec builds it: a CommitSig and a
    Timestamp object a signature."""
    return pb.Commit(
        height=c.height, round=c.round, block_id=c.block_id.to_proto(),
        signatures=[pb.CommitSig(
            block_id_flag=s.block_id_flag,
            validator_address=s.validator_address,
            timestamp=pb.Timestamp.from_unix_nanos(s.timestamp),
            signature=s.signature) for s in c.signatures])


def reflective_decode(buf: bytes) -> pb.Commit:
    return ProtoMessage.decode.__func__(pb.Commit, buf)


def reflective_commit(m: pb.Commit) -> Commit:
    return Commit(m.height, m.round, BlockID.from_proto(m.block_id),
                  [CommitSig(s.block_id_flag, s.validator_address,
                             s.timestamp.to_unix_nanos(), s.signature)
                   for s in m.signatures])


def codec_counts():
    return dict(metrics.types_commit_codec.summary_series())


def moved(before):
    return {k: v - before.get(k, 0) for k, v in codec_counts().items()
            if v != before.get(k, 0)}


def outcome(decode, buf):
    try:
        return ("ok", decode(buf))
    except Exception as e:  # noqa: BLE001 - the type is what is compared
        return ("raised", type(e))


@pytest.mark.parametrize("n", [1, 175, 10_000])
def test_encode_is_byte_identical_to_the_reflective_codec(n):
    c = mk_commit(n, seed=n)
    assert c.to_proto().encode() == reflective_pb(c).encode()


def test_encode_covers_every_edge_time_flag_and_signature():
    sigs = [CommitSig(flag, bytes(20), ts, sig)
            for flag in (BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL)
            for ts in EDGE_TIMES
            for sig in (b"", b"\x01" * 64, b"\x02" * 200)]
    sigs += [CommitSig.absent(), CommitSig(0, b"", 0, b""),
             CommitSig(-1, b"a", 5, b"s"), CommitSig(300, b"", 0, b"")]
    for height, round_ in [(0, 0), (1, 0), (-5, -1), (INT64_MAX, 7)]:
        c = Commit(height, round_, BlockID(), sigs)
        assert c.to_proto().encode() == reflective_pb(c).encode()
    assert Commit(0, 0, BlockID(), []).to_proto().encode() \
        == reflective_pb(Commit(0, 0, BlockID(), [])).encode() \
        == b"\x1a\x02\x12\x00"


def test_an_absent_signature_is_its_flag_and_an_empty_timestamp():
    c = Commit(1, 0, BlockID(), [CommitSig.absent()])
    assert pb.CommitSig.encode_rows(c._rows()) == [b"\x08\x01\x1a\x00"]
    assert c.to_proto().encode().endswith(b"\x22\x04\x08\x01\x1a\x00")


def _block(c: Commit) -> Block:
    h = Header(chain_id="c", height=c.height + 1, time=123_456_789_000,
               last_block_id=c.block_id, validators_hash=b"\x01" * 32,
               proposer_address=b"\x02" * 20)
    b = Block(h, [b"tx1", b"tx2"], last_commit=c)
    b.fill_header()
    return b


def _nest(kind: str, c: Commit, as_pb, vals):
    """The message ``kind`` around the commit ``c``, whose pb form is
    ``as_pb(c)``; a light block carries the set ``vals``."""
    block = _block(c)
    if kind in ("block", "blocksync"):
        m = block.to_proto()
        m.last_commit = as_pb(c)
        if kind == "blocksync":
            m = BlocksyncMessagePB(block_response=BlockResponsePB(block=m))
        return m
    sh = pb.SignedHeader(header=block.header.to_proto(), commit=as_pb(c))
    if kind == "signed_header":
        return sh
    return pb.LightBlock(signed_header=sh, validator_set=vals.to_proto())


NESTS = {"block": pb.Block, "signed_header": pb.SignedHeader,
         "light_block": pb.LightBlock, "blocksync": BlocksyncMessagePB}


@pytest.mark.parametrize("kind", sorted(NESTS))
def test_a_nesting_message_takes_the_hand_path_byte_for_byte(kind):
    c = mk_commit(175, seed=7)
    vals, _ = mk_valset(3)
    hand = _nest(kind, c, Commit.to_proto, vals).encode()
    assert hand == _nest(kind, c, reflective_pb, vals).encode()
    p0 = codec_counts()
    decoded = NESTS[kind].decode(hand)
    assert moved(p0) == {"dir=decode,path=hand": 1}
    inner = {"block": lambda m: m.last_commit,
             "blocksync": lambda m: m.block_response.block.last_commit,
             "signed_header": lambda m: m.commit,
             "light_block": lambda m: m.signed_header.commit}[kind](decoded)
    assert Commit.from_proto(inner) == c
    assert decoded.encode() == hand
    assert inner == reflective_pb(c)


def test_block_and_light_block_round_trip_through_the_hand_codec():
    c = mk_commit(175, seed=8)
    block = _block(c)
    assert Block.decode(block.encode()).last_commit == c
    vals, _ = mk_valset(4)
    lb = LightBlock(SignedHeader(block.header, c), vals)
    back = LightBlock.from_proto(pb.LightBlock.decode(lb.to_proto().encode()))
    assert back.commit == c
    assert back.to_proto().encode() == lb.to_proto().encode()


@pytest.mark.parametrize("n", [1, 175, 1_000])
def test_hash_is_the_merkle_root_of_the_reflective_leaves(n):
    c = mk_commit(n, seed=100 + n)
    leaves = [s.encode() for s in reflective_pb(c).signatures]
    assert c.hash() == hash_from_byte_slices(leaves)


@pytest.mark.parametrize("n", [1, 175, 10_000])
def test_hand_decode_equals_the_reflective_decode(n):
    c = mk_commit(n, seed=200 + n)
    buf = c.to_proto().encode()
    p0 = codec_counts()
    m = pb.Commit.decode(buf)
    assert moved(p0) == {"dir=decode,path=hand": 1}
    ref = reflective_decode(buf)
    assert Commit.from_proto(m) == reflective_commit(ref) == c
    assert m.encode() == buf                    # from its rows
    assert m == ref                             # the fields, built now
    assert m.encode() == buf                    # from the fields
    assert moved(p0) == {"dir=decode,path=hand": 1,
                         "dir=encode,path=hand": 1,
                         "dir=encode,path=reflective": 1}


def _canonical() -> bytes:
    """Three signatures of a commit at height 7, round 1: 08 07 10 01 ..."""
    c = mk_commit(3, seed=5)
    buf = Commit(7, 1, c.block_id, c.signatures).to_proto().encode()
    assert buf[:4] == b"\x08\x07\x10\x01"
    return buf


def _sig(ts_body: bytes, extra: bytes = b"") -> bytes:
    """A CommitSig element with the Timestamp body ``ts_body``."""
    body = b"\x08\x02\x12\x14" + bytes(20) + b"\x1a" \
        + bytes((len(ts_body),)) + ts_body + b"\x22\x01s" + extra
    return b"\x22" + bytes((len(body),)) + body


FALLBACKS = {
    "unknown_field_at_the_end": lambda b: b + b"\x48\x01",
    "unknown_field_in_a_signature": lambda b: b + _sig(b"\x08\x05",
                                                         b"\x28\x01"),
    "round_before_height": lambda b: b"\x10\x01\x08\x07" + b[4:],
    "block_id_after_a_signature": lambda b: b + b"\x1a\x00",
    "padded_height_varint": lambda b: b"\x08\x87\x00" + b[2:],
    "padded_length_varint": lambda b: b + b"\x22\x84\x00\x08\x01\x1a\x00",
    "nanos_out_of_range": lambda b: b + _sig(
        b"\x10" + encode_varint(1_000_000_000)),
    "negative_nanos": lambda b: b + _sig(b"\x10" + encode_varint(-1)),
    "signature_without_timestamp": lambda b: b + b"\x22\x02\x08\x01",
    "timestamp_fields_reordered": lambda b: b + _sig(b"\x10\x01\x08\x01"),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_another_shape_falls_back_to_the_reflective_result(case):
    buf = FALLBACKS[case](_canonical())
    ref = outcome(reflective_decode, buf)
    p0 = codec_counts()
    got = outcome(pb.Commit.decode, buf)
    assert moved(p0) == {"dir=decode,path=reflective": 1}
    assert got == ref
    if ref[0] == "ok":
        assert Commit.from_proto(got[1]) == reflective_commit(ref[1])


def test_every_truncation_raises_or_reads_as_the_reflective_decoder():
    buf = _canonical()
    seen = set()
    for cut in range(len(buf)):
        got = outcome(pb.Commit.decode, buf[:cut])
        assert got == outcome(reflective_decode, buf[:cut]), cut
        seen.add(got[0] if got[0] == "ok" else got[1])
    assert EOFError in seen and "ok" in seen


def test_random_byte_flips_read_as_the_reflective_decoder():
    buf = _canonical()
    r = random.Random(11)
    for _ in range(400):
        b = bytearray(buf)
        for _ in range(r.randrange(1, 4)):
            b[r.randrange(len(b))] = r.randrange(256)
        b = bytes(b)
        assert outcome(pb.Commit.decode, b) == \
            outcome(reflective_decode, b), b.hex()


def test_input_that_is_not_bytes_takes_the_reflective_decoder():
    buf = _canonical()
    p0 = codec_counts()
    m = pb.Commit.decode(memoryview(buf))
    assert moved(p0) == {"dir=decode,path=reflective": 1}
    assert m == reflective_decode(buf)


def test_a_commit_sig_edited_in_place_encodes_its_new_bytes():
    c = mk_commit(10, seed=9)
    first = c.to_proto()
    before = first.encode()
    cs = next(s for s in c.signatures if not s.is_absent())
    cs.signature = b"\xee" * 64
    cs.timestamp += 1
    after = c.to_proto().encode()
    assert after != before and after == reflective_pb(c).encode()
    # a pb.Commit taken before the edit holds what the fields held then
    assert first.encode() == before
    h = Commit(c.height, c.round, c.block_id, c.signatures).hash()
    cs.block_id_flag = BLOCK_ID_FLAG_NIL
    c3 = Commit(c.height, c.round, c.block_id, c.signatures)
    assert c3.hash() != h
    assert c3.hash() == hash_from_byte_slices(
        [s.encode() for s in reflective_pb(c3).signatures])


def test_fields_read_or_set_on_a_hand_commit_are_what_it_encodes():
    c = mk_commit(5, seed=12)
    m = c.to_proto()
    m.signatures.append(pb.CommitSig(block_id_flag=BLOCK_ID_FLAG_ABSENT))
    c.signatures.append(CommitSig.absent())
    assert m.encode() == reflective_pb(c).encode()
    m2 = c.to_proto()
    m2.signatures = []
    m2.height = 99
    assert m2.encode() == reflective_pb(Commit(99, c.round, c.block_id,
                                               [])).encode()


def test_the_counter_moves_once_a_commit_by_direction_and_path():
    c = mk_commit(175, seed=13)
    p0 = codec_counts()
    buf = c.to_proto().encode()
    assert moved(p0) == {"dir=encode,path=hand": 1}
    _block(c).encode()
    assert moved(p0) == {"dir=encode,path=hand": 2}
    pb.Commit.decode(buf)
    Block.decode(_block(c).encode())
    assert moved(p0) == {"dir=encode,path=hand": 3,
                         "dir=decode,path=hand": 2}
    reflective_pb(c).encode()
    pb.Commit.decode(buf + b"\x48\x01")
    c.hash()
    assert moved(p0) == {"dir=encode,path=hand": 3,
                         "dir=decode,path=hand": 2,
                         "dir=encode,path=reflective": 1,
                         "dir=decode,path=reflective": 1}

"""Sidecar wire-protocol tests: encode/decode round-trips for EVERY
message type (the sidecar analysis rule lints that this stays true),
each pinned to the bytes the wire carries, the bit-packed mask codec
and address parsing. The frame reader's fuzz and the live handshake are
tests/test_framed.py's, run there for both daemons."""

import io

import numpy as np
import pytest

from tmtpu.sidecar import protocol as proto

# one representative instance per wire message, exercising every field
# (repeated, nested, bytes, bool, string, 64-bit values)
SAMPLES = {
    proto.Hello: proto.Hello(
        version=proto.PROTOCOL_VERSION, client_id="node-7",
        features=["tally", "k1"]),
    proto.HelloAck: proto.HelloAck(
        version=proto.PROTOCOL_VERSION, server_id="daemon-1", backend="tpu",
        max_lanes=40960, max_frame_bytes=8 * 1024 * 1024),
    proto.VerifyRequest: proto.VerifyRequest(
        request_id=2**53, curve="ed25519", tally=True, deadline_ms=1500,
        lanes=[proto.Lane(pub_key=b"\x01" * 32, msg=b"vote-bytes",
                          sig=b"\x02" * 64, power=1000),
               proto.Lane(pub_key=b"\x03" * 32, msg=b"", sig=b"\x04" * 64,
                          power=0)]),
    proto.VerifyResponse: proto.VerifyResponse(
        request_id=2**53, status=proto.STATUS_OK, mask=b"\x05",
        lane_count=3, tallied=3000, dispatch_id=17, dispatch_lanes=4096,
        dispatch_clients=3, error=""),
    proto.Ping: proto.Ping(nonce=0xDEADBEEF),
    proto.Pong: proto.Pong(nonce=0xDEADBEEF, backend="cpu",
                           uptime_ms=123456),
    proto.StatsRequest: proto.StatsRequest(),
    proto.StatsResponse: proto.StatsResponse(stats_json=b'{"uptime_s": 1}'),
    proto.ErrorReply: proto.ErrorReply(
        request_id=9, code=proto.ERR_VERSION, message="speak v1"),
}


# every SAMPLES message as this tree's wire puts it: a frame that
# changes a single byte fails its pinned round-trip case
FRAMES = {
    proto.Hello: "1601080212066e6f64652d371a0574616c6c791a026b31",
    proto.HelloAck: "1b02080212086461656d6f6e2d311a037470752080c0022880808004",
    proto.VerifyRequest: (
        "f30103088080808080808010120765643235353139180120dc0b2a73"
        "0a200101010101010101010101010101010101010101010101010101"
        "010101010101120a766f74652d62797465731a400202020202020202"
        "02020202020202020202020202020202020202020202020202020202"
        "02020202020202020202020202020202020202020202020202020202"
        "20e8072a640a20030303030303030303030303030303030303030303"
        "03030303030303030303031a40040404040404040404040404040404"
        "04040404040404040404040404040404040404040404040404040404"
        "040404040404040404040404040404040404040404"),
    proto.VerifyResponse: "19040880808080808080101a0105200328b81730113880204003",
    proto.Ping: "070508effdb6f50d",
    proto.Pong: "100608effdb6f50d120363707518c0c407",
    proto.StatsRequest: "0107",
    proto.StatsResponse: "12080a0f7b22757074696d655f73223a20317d",
    proto.ErrorReply: "0f09080910011a08737065616b207631",
}


def test_every_message_type_has_a_sample():
    """The round-trip test below covers the full registry — a new wire
    message must add a sample here (check_sidecar.py enforces this)."""
    assert set(SAMPLES) == set(proto.MESSAGE_TYPES.values())


@pytest.mark.parametrize("cls,pinned", [
    pytest.param(cls, pinned,
                 id=cls.__name__ + ("-pinned" if pinned else ""))
    for cls in sorted(proto.MESSAGE_TYPES.values(), key=lambda c: c.__name__)
    for pinned in (False, True)])
def test_frame_round_trip(cls, pinned):
    msg = SAMPLES[cls]
    frame = proto.encode_frame(msg)
    if pinned:
        assert frame == bytes.fromhex(FRAMES[cls])
    # frame = uvarint(len(body)) || type_byte || payload
    rd = proto.FrameReader(io.BytesIO(frame))
    back = rd.read_msg()
    assert type(back) is cls
    assert back.encode() == msg.encode()
    # a second read on the drained stream is EOF, not garbage
    with pytest.raises(EOFError):
        rd.read_msg()


def test_mask_codec_round_trip():
    rng = np.random.default_rng(3)
    for n in (1, 7, 8, 9, 63, 64, 65, 1000):
        mask = [bool(b) for b in rng.integers(0, 2, n)]
        packed = proto.pack_mask(mask)
        assert len(packed) == (n + 7) // 8
        assert proto.unpack_mask(packed, n) == mask
    # LSB-first bit order is wire-visible: lane 0 is bit 0 of byte 0
    assert proto.pack_mask([True] + [False] * 7) == b"\x01"
    assert proto.pack_mask([False] * 8 + [True]) == b"\x00\x01"
    assert proto.pack_mask([]) == b""
    assert proto.unpack_mask(b"", 0) == []


def test_mask_too_short_rejected():
    with pytest.raises(proto.ProtocolError):
        proto.unpack_mask(b"\x01", 9)


def test_parse_addr():
    assert proto.parse_addr("unix:///tmp/x.sock") == ("unix", "/tmp/x.sock")
    assert proto.parse_addr("tcp://127.0.0.1:7777") == \
        ("tcp", ("127.0.0.1", 7777))
    for bad in ("", "unix://", "tcp://nohost", "tcp://:9", "http://x:1",
                "/tmp/x.sock"):
        with pytest.raises(ValueError):
            proto.parse_addr(bad)

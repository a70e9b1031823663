"""Lightserve wire-protocol tests: encode/decode round-trips for EVERY
message type (the analysis lightserve wire lint keeps this true), each
pinned to the bytes the wire carries, the namespace kept apart from the
sidecar's, the wrong-chain handshake and pipelined sessions. The frame
reader's fuzz and the rest of the handshake are tests/test_framed.py's,
run there for both daemons."""

import io
import socket
import threading

import pytest

from tmtpu.lightserve import protocol as proto

# one representative instance per wire message, exercising every field
# (repeated nested Hop, bytes, bool, string, 64-bit values)
SAMPLES = {
    proto.Hello: proto.Hello(
        version=proto.PROTOCOL_VERSION, client_id="wallet-7",
        chain_id="light-chain"),
    proto.HelloAck: proto.HelloAck(
        version=proto.PROTOCOL_VERSION, server_id="lightserve-1",
        chain_id="light-chain", anchor_height=1,
        anchor_hash=b"\x0a" * 32, latest_height=100_000,
        max_frame_bytes=1024 * 1024),
    proto.SyncRequest: proto.SyncRequest(
        request_id=2**53, trusted_height=17, trusted_hash=b"\x0b" * 32,
        target_height=100_000, now_ns=1_700_000_000_000_000_000),
    proto.Hop: proto.Hop(
        height=50_000, header_hash=b"\x0c" * 32,
        header_time=1_700_000_000_000_000_000),
    proto.SyncResponse: proto.SyncResponse(
        request_id=2**53, status=proto.STATUS_OK,
        hops=[proto.Hop(height=50_000, header_hash=b"\x0c" * 32,
                        header_time=1_699_000_000_000_000_000),
              proto.Hop(height=100_000, header_hash=b"\x0d" * 32,
                        header_time=1_700_000_000_000_000_000)],
        dispatches=4, cache_hit=True, dispatch_id=17, coalesced=12,
        error=""),
    proto.Ping: proto.Ping(nonce=0xDEADBEEF),
    proto.Pong: proto.Pong(nonce=0xDEADBEEF, latest_height=100_000,
                           uptime_ms=123456),
    proto.StatsRequest: proto.StatsRequest(),
    proto.StatsResponse: proto.StatsResponse(stats_json=b'{"facts": 9}'),
    proto.ErrorReply: proto.ErrorReply(
        request_id=9, code=proto.ERR_VERSION, message="speak v1"),
}


# every SAMPLES message as this tree's wire puts it: a frame that
# changes a single byte fails its pinned round-trip case
FRAMES = {
    proto.Hello: "1a010801120877616c6c65742d371a0b6c696768742d636861696e",
    proto.HelloAck: (
        "4a020801120c6c6967687473657276652d311a0b6c696768742d6368"
        "61696e20012a200a0a0a0a0a0a0a0a0a0a0a0a0a0a0a0a0a0a0a0a0a"
        "0a0a0a0a0a0a0a0a0a0a0a30a08d0638808040"),
    proto.SyncRequest: (
        "3c0308808080808080801010111a200b0b0b0b0b0b0b0b0b0b0b0b0b"
        "0b0b0b0b0b0b0b0b0b0b0b0b0b0b0b0b0b0b0b20a08d06288080a8b1"
        "e39fe7cb17"),
    proto.Hop: (
        "310a08d0860312200c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c"
        "0c0c0c0c0c0c0c0c0c0c0c0c188080a8b1e39fe7cb17"),
    proto.SyncResponse: (
        "76040880808080808080101a3008d0860312200c0c0c0c0c0c0c0c0c"
        "0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c0c1880808e8b"
        "f9ef83ca171a3008a08d0612200d0d0d0d0d0d0d0d0d0d0d0d0d0d0d"
        "0d0d0d0d0d0d0d0d0d0d0d0d0d0d0d0d0d188080a8b1e39fe7cb1720"
        "0428013011380c"),
    proto.Ping: "070508effdb6f50d",
    proto.Pong: "0f0608effdb6f50d10a08d0618c0c407",
    proto.StatsRequest: "0107",
    proto.StatsResponse: "0f080a0c7b226661637473223a20397d",
    proto.ErrorReply: "0f09080910011a08737065616b207631",
}


def test_every_message_type_has_a_sample():
    """The round-trip test below covers the full registry — a new wire
    message must add a sample here (the lightserve analysis rule
    enforces this)."""
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
    rd = proto.FrameReader(io.BytesIO(frame))
    back = rd.read_msg()
    assert type(back) is cls
    assert back.encode() == msg.encode()
    with pytest.raises(EOFError):
        rd.read_msg()


def test_registries_are_disjoint_namespaces():
    """The codec is shared with the sidecar but the registries are not:
    a lightserve frame must NOT decode as a sidecar message of the same
    type byte, and each registry is internally consistent."""
    from tmtpu.sidecar import protocol as sc

    assert proto.TYPE_BYTES == {c: t
                                for t, c in proto.MESSAGE_TYPES.items()}
    # type byte 3 is VerifyRequest there, SyncRequest here: a sidecar
    # reader either decodes it as its OWN message or rejects the frame —
    # it never yields a lightserve message
    frame = proto.encode_frame(SAMPLES[proto.SyncRequest])
    try:
        msg = sc.FrameReader(io.BytesIO(frame)).read_msg()
        assert not isinstance(msg, proto.SyncRequest)
    except proto.ProtocolError as _rejected:
        pass  # payload shape didn't even parse as the sidecar type


# --- live daemon -----------------------------------------------------------


@pytest.fixture(autouse=True, scope="module")
def _cpu_backend():
    from tmtpu.crypto import batch as crypto_batch

    old = crypto_batch._default_backend
    crypto_batch.set_default_backend("cpu")
    yield
    crypto_batch.set_default_backend(old)


def _server(tmp_path, n_heights=5):
    from tests.test_light import CHAIN_ID, WEEK_NS, ChainProvider, FabChain
    from tmtpu.light.client import TrustOptions
    from tmtpu.lightserve.server import LightserveServer

    chain = FabChain(n_heights)
    srv = LightserveServer(
        f"unix://{tmp_path}/ls.sock", ChainProvider(chain),
        TrustOptions(WEEK_NS, 1, chain.blocks[1].header.hash()),
        CHAIN_ID)
    srv.start()
    return srv


def _connect_raw(addr: str) -> socket.socket:
    kind, target = proto.parse_addr(addr)
    s = socket.socket(socket.AF_UNIX if kind == "unix" else socket.AF_INET,
                      socket.SOCK_STREAM)
    s.settimeout(10.0)
    s.connect(target)
    return s


def test_chain_mismatch_rejected(tmp_path):
    """A Hello naming a different chain is refused before any session —
    a proof for the wrong chain is worse than no proof."""
    srv = _server(tmp_path)
    try:
        s = _connect_raw(srv.addr)
        proto.write_frame(s.makefile("wb"),
                          proto.Hello(version=proto.PROTOCOL_VERSION,
                                      client_id="lost-wallet",
                                      chain_id="other-chain"))
        reply = proto.FrameReader(s.makefile("rb")).read_msg()
        assert isinstance(reply, proto.ErrorReply)
        assert reply.code == proto.ERR_PROTOCOL
        assert "other-chain" in reply.message
        s.close()
    finally:
        srv.stop()


def test_pipelined_sessions_on_one_connection(tmp_path):
    """Raw-socket pipelining: many SyncRequests written back-to-back on
    one connection all get answers with matching request ids — the
    demux shape the flood harness leans on."""
    srv = _server(tmp_path, n_heights=8)
    try:
        s = _connect_raw(srv.addr)
        wf = s.makefile("wb")
        proto.write_frame(wf, proto.Hello(version=proto.PROTOCOL_VERSION,
                                          client_id="pipeliner"))
        rd = proto.FrameReader(s.makefile("rb"))
        assert isinstance(rd.read_msg(), proto.HelloAck)
        anchor = srv.trust_options
        n = 32
        for rid in range(1, n + 1):
            proto.write_frame(wf, proto.SyncRequest(
                request_id=rid, trusted_height=1,
                trusted_hash=anchor.hash, target_height=8))
        got = set()
        lock = threading.Lock()
        for _ in range(n):
            reply = rd.read_msg()
            assert isinstance(reply, proto.SyncResponse)
            assert reply.status == proto.STATUS_OK
            with lock:
                got.add(reply.request_id)
        assert got == set(range(1, n + 1))
        s.close()
    finally:
        srv.stop()

"""The framed-daemon core (tmtpu/libs/framed), once for both daemons.

Every case runs against the sidecar and the lightserve namespace, server
and client: the frame reader and its cap, truncation and fuzz, the
handshake (non-Hello first frame, garbage, unsupported version, version
skew in both directions), an oversized frame refused before decode, the
client's waiter table after a failed send and after a connect race,
drain and stop, and the health listener."""

import io
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from tests import test_lightserve_protocol as ls_tests
from tests import test_sidecar_protocol as sc_tests
from tmtpu.libs.framed import protocol as framed
from tmtpu.libs.framed.client import DaemonUnavailable
from tmtpu.lightserve import protocol as ls_proto
from tmtpu.lightserve.client import LightserveClient, LightserveOverloaded
from tmtpu.lightserve.server import LightserveServer
from tmtpu.sidecar import protocol as sc_proto
from tmtpu.sidecar.client import SidecarClient, SidecarOverloaded
from tmtpu.sidecar.server import SidecarServer


@pytest.fixture(autouse=True, scope="module")
def _cpu_backend():
    from tmtpu.crypto import batch as crypto_batch

    old = crypto_batch._default_backend
    crypto_batch.set_default_backend("cpu")
    yield
    crypto_batch.set_default_backend(old)


def _sidecar_server(tmp_path, **kw):
    srv = SidecarServer(f"unix://{tmp_path}/sc.sock", backend="cpu", **kw)
    srv.start()
    return srv


def _lightserve_server(tmp_path, **kw):
    from tests.test_light import CHAIN_ID, WEEK_NS, ChainProvider, FabChain
    from tmtpu.light.client import TrustOptions

    chain = FabChain(5)
    srv = LightserveServer(
        f"unix://{tmp_path}/ls.sock", ChainProvider(chain),
        TrustOptions(WEEK_NS, 1, chain.blocks[1].header.hash()),
        CHAIN_ID, **kw)
    srv.start()
    return srv


def _sidecar_submit(client):
    # never verified: the request waits in the queue until it stops
    return client.verify("ed25519", [(b"\x01" * 32, b"m", b"\x02" * 64, 1)])


def _lightserve_submit(client):
    # a cold target (only the anchor at 1 is cached) rides the queue
    return client.sync_submit(1, b"\x00" * 32, 5).result()


def _sidecar_ack(ack):
    assert ack.max_lanes > 0


def _lightserve_ack(ack):
    assert ack.chain_id == "light-chain"
    assert ack.anchor_height == 1
    assert ack.latest_height >= 1


class Namespace:
    def __init__(self, name, proto, samples, big, flips, seeds, server,
                 client, submit, overloaded, check_ack):
        self.name = name
        self.proto = proto
        self.samples = samples
        self.big = big                # the largest sample's class
        self.flips = flips            # classes the bit-flip fuzz mutates
        self.seeds = seeds            # (byte-soup seed, bit-flip seed)
        self.server = server
        self.client = client
        self.submit = submit
        self.overloaded = overloaded
        self.check_ack = check_ack


NAMESPACES = {
    "sidecar": Namespace(
        "sidecar", sc_proto, sc_tests.SAMPLES, sc_proto.VerifyRequest,
        (sc_proto.VerifyRequest, sc_proto.VerifyResponse, sc_proto.Hello),
        (20260806, 7), _sidecar_server,
        lambda addr: SidecarClient(addr, client_id="core-test"),
        _sidecar_submit, SidecarOverloaded, _sidecar_ack),
    "lightserve": Namespace(
        "lightserve", ls_proto, ls_tests.SAMPLES, ls_proto.SyncResponse,
        (ls_proto.SyncRequest, ls_proto.SyncResponse, ls_proto.HelloAck),
        (20260808, 11), _lightserve_server,
        lambda addr: LightserveClient(addr, client_id="core-test",
                                      chain_id="light-chain"),
        _lightserve_submit, LightserveOverloaded, _lightserve_ack),
}


@pytest.fixture(params=sorted(NAMESPACES))
def ns(request):
    return NAMESPACES[request.param]


def _connect_raw(addr: str) -> socket.socket:
    kind, target = framed.parse_addr(addr)
    s = socket.socket(socket.AF_UNIX if kind == "unix" else socket.AF_INET,
                      socket.SOCK_STREAM)
    s.settimeout(10.0)
    s.connect(target)
    return s


def _wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not cond():
        time.sleep(0.02)
    assert cond()


# --- the frame --------------------------------------------------------------


def test_stream_of_frames_in_order(ns):
    proto = ns.proto
    buf = io.BytesIO()
    for cls in proto.MESSAGE_TYPES.values():
        proto.write_frame(buf, ns.samples[cls])
    buf.seek(0)
    rd = proto.FrameReader(buf)
    for cls in proto.MESSAGE_TYPES.values():
        assert type(rd.read_msg()) is cls


def test_decode_frame_rejects_empty_and_unknown_type(ns):
    proto = ns.proto
    with pytest.raises(proto.ProtocolError):
        proto.decode_frame(b"")
    for tb in (0, max(proto.MESSAGE_TYPES) + 1, 0x7F, 0xFF):
        assert tb not in proto.MESSAGE_TYPES
        with pytest.raises(proto.ProtocolError):
            proto.decode_frame(bytes([tb]) + b"\x01\x02")


def test_truncated_frames_raise_cleanly(ns):
    """Every proper prefix of a valid frame must surface EOFError (frame
    cut mid-flight) or ProtocolError (decodable length, bad payload) —
    never an attribute/assertion escape from the decoder."""
    frame = ns.proto.encode_frame(ns.samples[ns.big])
    for cut in range(len(frame)):
        rd = ns.proto.FrameReader(io.BytesIO(frame[:cut]))
        with pytest.raises((EOFError, framed.ProtocolError)):
            rd.read_msg()


def test_oversized_frame_rejected_before_decode(ns):
    proto = ns.proto
    frame = proto.encode_frame(ns.samples[ns.big])
    rd = proto.FrameReader(io.BytesIO(frame), max_frame_bytes=8)
    with pytest.raises(proto.ProtocolError):
        rd.read_msg()
    # a length prefix claiming gigabytes is rejected from the prefix
    # alone — the reader must not try to allocate or drain the payload
    huge = framed.encode_uvarint(1 << 40) + b"\x01"
    rd = proto.FrameReader(io.BytesIO(huge))
    with pytest.raises(proto.ProtocolError):
        rd.read_msg()


def test_fuzz_random_byte_soup(ns):
    """Random blobs into the frame reader: clean rejection (ProtocolError
    / EOFError) or a successful decode of some message — nothing else."""
    rng = np.random.default_rng(ns.seeds[0])
    blobs = [b"", b"\x00", b"\xff" * 16]
    for _ in range(300):
        blobs.append(rng.integers(
            0, 256, int(rng.integers(1, 200)), dtype=np.uint8).tobytes())
    for blob in blobs:
        rd = ns.proto.FrameReader(io.BytesIO(blob), max_frame_bytes=4096)
        try:
            for _ in range(4):
                rd.read_msg()
        except (EOFError, framed.ProtocolError):
            pass


def test_fuzz_bit_flips_in_valid_frames(ns):
    """Single-byte corruptions of real frames either still decode (the
    flip landed in a value) or raise ProtocolError/EOFError."""
    rng = np.random.default_rng(ns.seeds[1])
    for cls in ns.flips:
        frame = bytearray(ns.proto.encode_frame(ns.samples[cls]))
        for _ in range(80):
            pos = int(rng.integers(0, len(frame)))
            mut = bytes(frame[:pos]) + bytes(
                [int(rng.integers(0, 256))]) + bytes(frame[pos + 1:])
            rd = ns.proto.FrameReader(io.BytesIO(mut), max_frame_bytes=4096)
            try:
                rd.read_msg()
            except (EOFError, framed.ProtocolError):
                pass


# --- the handshake ----------------------------------------------------------


def test_version_mismatch_rejected(ns, tmp_path):
    """A Hello with the wrong version gets ErrorReply(ERR_VERSION) and a
    closed connection; the right version gets HelloAck on a fresh one."""
    proto = ns.proto
    srv = ns.server(tmp_path)
    try:
        s = _connect_raw(srv.addr)
        proto.write_frame(s.makefile("wb"),
                          proto.Hello(version=proto.PROTOCOL_VERSION + 1,
                                      client_id="time-traveler"))
        rd = proto.FrameReader(s.makefile("rb"))
        reply = rd.read_msg()
        assert isinstance(reply, proto.ErrorReply)
        assert reply.code == proto.ERR_VERSION
        with pytest.raises(EOFError):  # server closed the connection
            rd.read_msg()
        s.close()

        s = _connect_raw(srv.addr)
        proto.write_frame(s.makefile("wb"),
                          proto.Hello(version=proto.PROTOCOL_VERSION,
                                      client_id="contemporary"))
        ack = proto.FrameReader(s.makefile("rb")).read_msg()
        assert isinstance(ack, proto.HelloAck)
        assert ack.version == proto.PROTOCOL_VERSION
        ns.check_ack(ack)
        s.close()
    finally:
        srv.stop()


def test_non_hello_first_message_rejected(ns, tmp_path):
    proto = ns.proto
    srv = ns.server(tmp_path)
    try:
        s = _connect_raw(srv.addr)
        proto.write_frame(s.makefile("wb"), proto.Ping(nonce=1))
        reply = proto.FrameReader(s.makefile("rb")).read_msg()
        assert isinstance(reply, proto.ErrorReply)
        assert reply.code == proto.ERR_PROTOCOL
        s.close()
    finally:
        srv.stop()


def test_garbage_first_frame_rejected(ns, tmp_path):
    proto = ns.proto
    srv = ns.server(tmp_path)
    try:
        s = _connect_raw(srv.addr)
        s.sendall(framed.encode_uvarint(3) + b"\xee\x01\x02")
        reply = proto.FrameReader(s.makefile("rb")).read_msg()
        assert isinstance(reply, proto.ErrorReply)
        assert reply.code == proto.ERR_PROTOCOL
        s.close()
    finally:
        srv.stop()


def test_server_serves_every_supported_version(ns, tmp_path):
    """Version skew, old client on a new daemon: a Hello at any version
    the namespace still speaks is acked at that version, not refused."""
    proto = ns.proto
    srv = ns.server(tmp_path)
    try:
        for version in proto.SUPPORTED_VERSIONS:
            s = _connect_raw(srv.addr)
            proto.write_frame(s.makefile("wb"), proto.Hello(
                version=version, client_id=f"speaks-v{version}"))
            ack = proto.FrameReader(s.makefile("rb")).read_msg()
            assert isinstance(ack, proto.HelloAck)
            assert ack.version == min(version, proto.PROTOCOL_VERSION)
            s.close()
    finally:
        srv.stop()


class _OldDaemon:
    """A daemon that knows no negotiation: it acks a Hello at exactly
    ``accepts`` and answers any other version ERR_VERSION and a closed
    connection. Records the version of every Hello it saw."""

    def __init__(self, proto, path, accepts):
        self.addr = f"unix://{path}"
        self.seen = []
        self._srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._srv.bind(str(path))
        self._srv.listen(4)
        self._held = []
        self._thread = threading.Thread(
            target=self._serve, args=(proto, accepts), daemon=True)
        self._thread.start()

    def _serve(self, proto, accepts):
        while True:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            hello = proto.FrameReader(conn.makefile("rb")).read_msg()
            self.seen.append(hello.version)
            if hello.version != accepts:
                proto.write_frame(conn.makefile("wb"), proto.ErrorReply(
                    code=proto.ERR_VERSION, message="unsupported version"))
                conn.close()
                continue
            proto.write_frame(conn.makefile("wb"), proto.HelloAck(
                version=accepts, server_id="old-daemon"))
            self._held.append(conn)    # the client stays connected

    def stop(self):
        for c in self._held:
            c.close()
        self._srv.shutdown(socket.SHUT_RDWR)   # wakes the accept()
        self._srv.close()
        self._thread.join(timeout=5)


def test_client_retries_at_the_oldest_version(ns, tmp_path):
    """Version skew, new client on an old daemon: refused with
    ERR_VERSION, a client that speaks more than one version reconnects
    once at its oldest; one that speaks a single version gives up."""
    proto = ns.proto
    oldest = min(proto.SUPPORTED_VERSIONS)
    tried = [proto.PROTOCOL_VERSION] + (
        [oldest] if proto.PROTOCOL_VERSION > oldest else [])
    daemon = _OldDaemon(proto, tmp_path / "old.sock", accepts=oldest)
    client = ns.client(daemon.addr)
    try:
        client._ensure_connected()
        assert daemon.seen == tried
        assert client.hello_ack.version == oldest
    finally:
        client.close()
        daemon.stop()
    daemon = _OldDaemon(proto, tmp_path / "older.sock", accepts=0)
    client = ns.client(daemon.addr)
    try:
        with pytest.raises(DaemonUnavailable, match="rejected handshake"):
            client._ensure_connected()
        assert daemon.seen == tried
    finally:
        client.close()
        daemon.stop()


def test_oversized_frame_refused_by_the_server(ns, tmp_path):
    """A length prefix past the daemon's cap is refused ERR_PROTOCOL from
    the prefix alone — the payload is never read — and counted."""
    proto = ns.proto
    srv = ns.server(tmp_path)
    before = sum(srv.protocol_errors.summary_series().values())
    try:
        s = _connect_raw(srv.addr)
        proto.write_frame(s.makefile("wb"), proto.Hello(
            version=proto.PROTOCOL_VERSION, client_id="too-big"))
        rd = proto.FrameReader(s.makefile("rb"))
        assert isinstance(rd.read_msg(), proto.HelloAck)
        s.sendall(framed.encode_uvarint(proto.DEFAULT_MAX_FRAME_BYTES + 1)
                  + b"\x03")
        reply = rd.read_msg()
        assert isinstance(reply, proto.ErrorReply)
        assert reply.code == proto.ERR_PROTOCOL
        assert "too large" in reply.message
        with pytest.raises(EOFError):  # framing lost: connection closed
            rd.read_msg()
        s.close()
        after = sum(srv.protocol_errors.summary_series().values())
        assert after == before + 1
    finally:
        srv.stop()


# --- the client's waiter table ----------------------------------------------


class _GoneSocket:
    def sendall(self, data):
        raise BrokenPipeError("peer went away")

    def close(self):
        pass


def test_waiter_table_empty_after_a_failed_send(ns):
    client = ns.client("unix:///nonexistent/core-test.sock")
    client._sock = _GoneSocket()
    with pytest.raises(DaemonUnavailable, match="send failed"):
        client._send(7, framed.Ping(nonce=7))
    assert client._waiters == {}
    assert client._sock is None        # the failed send tore it down


def test_waiter_table_empty_after_a_connect_race(ns):
    """The connection went away between connect and send: the send
    raises, and the waiter it registered does not stay behind."""
    client = ns.client("unix:///nonexistent/core-test.sock")
    with pytest.raises(DaemonUnavailable, match="not connected"):
        client._send(8, framed.Ping(nonce=8))
    assert client._waiters == {}


# --- drain and stop ---------------------------------------------------------


def test_drain_refuses_new_work_and_stop_answers_shutting_down(ns, tmp_path):
    """While a drain waits on queued work, a new request is answered
    OVERLOADED (the penalty-free verdict); the queued request, when the
    queue stops under it, is answered SHUTTING_DOWN."""
    srv = ns.server(tmp_path)
    srv.coalescer.scheduler.gather_wait_s = lambda pending: 30.0
    client = ns.client(srv.addr)
    outcome = {}

    def queued():
        try:
            ns.submit(client)
        except Exception as exc:  # noqa: BLE001 — the verdict under test
            outcome["error"] = exc

    t = threading.Thread(target=queued)
    try:
        t.start()
        _wait_for(lambda: srv.coalescer.queued() == 1)
        assert not srv.drain(timeout=0.3)    # the queue still holds it
        with pytest.raises(ns.overloaded):
            ns.submit(client)
        srv.coalescer.stop()
        t.join(timeout=10)
        assert not t.is_alive()
        err = outcome["error"]
        assert isinstance(err, DaemonUnavailable)
        assert "shutting_down" in str(err)
    finally:
        client.close()
        srv.stop()


# --- the health listener ----------------------------------------------------


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url):
    try:
        r = urllib.request.urlopen(url, timeout=30)
        return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_health_endpoint(ns, tmp_path):
    import json

    port = _free_port()
    srv = ns.server(tmp_path, health_laddr=f"127.0.0.1:{port}")
    base = f"http://127.0.0.1:{port}"
    try:
        status, body = _get(f"{base}/healthz")
        doc = json.loads(body)
        assert status == 200 and doc["healthy"] is True
        assert doc["server_id"] == srv.server_id
        assert doc["draining"] is False
        status, body = _get(f"{base}/metrics")
        assert status == 200
        assert f"tendermint_{ns.name}_server_connections".encode() in body
        status, _ = _get(f"{base}/nope")
        assert status == 404
    finally:
        srv.stop()

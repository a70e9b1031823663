"""The framed-daemon client: one multiplexed connection, many requests.

One background reader thread demultiplexes replies to waiters by
request id; callers block on their own waiter with their own deadline,
so a slow joint dispatch never heads-of-line-blocks a Ping. Reconnects
are lazy (the next request attempts one) with a flat backoff window, so
a dead daemon costs one failed ``connect()`` per window, not one per
request.

A daemon's client subclasses :class:`FramedClient` and sets, as class
attributes, its namespace module (``proto``), its name prefix, the
exception it raises when the daemon cannot answer, and the two metrics
the connection keeps (``reconnects``, ``up``); it supplies its ``Hello``
through :meth:`FramedClient._hello` and builds its own requests on
:meth:`FramedClient._send` / :meth:`FramedClient._await`.
"""

from __future__ import annotations

import itertools
import json
import os
import socket
import threading
import time
from typing import Dict, Optional

from tmtpu.libs.framed.protocol import (
    ERR_VERSION,
    ErrorReply,
    Ping,
    ProtocolError,
    StatsRequest,
    StatsResponse,
    parse_addr,
)


class DaemonUnavailable(Exception):
    """Daemon unreachable / dead connection / deadline / hard error."""


class Waiter:
    __slots__ = ("event", "reply", "error")

    def __init__(self):
        self.event = threading.Event()
        self.reply = None
        self.error: Optional[Exception] = None


class FramedClient:
    proto = None                  # the daemon's namespace module
    prefix = "framed"             # messages and the reader thread's name
    unavailable = DaemonUnavailable
    reconnects = None             # counter: connection attempts
    up = None                     # gauge: 1 while connected

    def __init__(self, addr: str, *, client_id: str,
                 connect_timeout_s: float, request_deadline_s: float,
                 retry_backoff_s: float, max_frame_bytes: int):
        self.addr = addr
        self.client_id = client_id or f"pid-{os.getpid()}"
        self._connect_timeout_s = connect_timeout_s
        self._request_deadline_s = request_deadline_s
        self._retry_backoff_s = retry_backoff_s
        self._max_frame_bytes = max_frame_bytes
        self._sock: Optional[socket.socket] = None
        self._wlock = threading.Lock()
        self._conn_lock = threading.Lock()
        self._waiters: Dict[int, Waiter] = {}
        self._waiters_lock = threading.Lock()
        self._seq = itertools.count(1)
        self._last_connect_fail = 0.0
        self.hello_ack = None

    def _hello(self, version: int):
        """The daemon's first frame: its namespace's Hello at
        ``version``."""
        raise NotImplementedError

    # --- connection management ---

    def _ensure_connected(self) -> None:
        if self._sock is not None:
            return
        with self._conn_lock:
            if self._sock is not None:
                return
            now = time.monotonic()
            if now - self._last_connect_fail < self._retry_backoff_s:
                raise self.unavailable(
                    f"{self.prefix} {self.addr}: in connect backoff")
            try:
                self._connect_locked()
            except (OSError, ProtocolError, EOFError, ValueError) as exc:
                self._last_connect_fail = time.monotonic()
                raise self.unavailable(
                    f"{self.prefix} {self.addr}: {exc}") from exc

    def _dial(self, version: int):
        """Connect and send Hello at ``version``; returns the socket, its
        reader and the daemon's first reply."""
        kind, target = parse_addr(self.addr)
        sock = socket.socket(
            socket.AF_UNIX if kind == "unix" else socket.AF_INET,
            socket.SOCK_STREAM)
        try:
            sock.settimeout(self._connect_timeout_s)
            sock.connect(target)
            reader = self.proto.FrameReader(sock.makefile("rb"),
                                            self._max_frame_bytes)
            sock.sendall(self.proto.encode_frame(self._hello(version)))
            return sock, reader, reader.read_msg()
        except BaseException:
            sock.close()
            raise

    def _connect_locked(self) -> None:
        proto = self.proto
        self.reconnects.inc()
        sock, reader, ack = self._dial(proto.PROTOCOL_VERSION)
        oldest = min(proto.SUPPORTED_VERSIONS)
        if isinstance(ack, ErrorReply) and ack.code == ERR_VERSION and \
                proto.PROTOCOL_VERSION > oldest:
            # version-skew tolerance: an old daemon hard-rejects a newer
            # Hello (it knew no negotiation) and closes the connection,
            # so retry once, on a new one, at the oldest version we speak
            sock.close()
            sock, reader, ack = self._dial(oldest)
        if not isinstance(ack, proto.HelloAck):
            sock.close()
            if isinstance(ack, ErrorReply):
                raise self.unavailable(
                    f"{self.prefix} rejected handshake (code {ack.code}): "
                    f"{ack.message}")
            raise ProtocolError(
                f"expected HelloAck, got {type(ack).__name__}")
        sock.settimeout(None)  # reader thread blocks; waiters time out
        self.hello_ack = ack
        self._sock = sock
        self.up.set(1.0)
        threading.Thread(target=self._read_loop, args=(reader, sock),
                         name=f"{self.prefix}-client-read",
                         daemon=True).start()

    def close(self) -> None:
        with self._conn_lock:
            self._teardown(self.unavailable("client closed"))

    def _teardown(self, err: Exception) -> None:
        sock, self._sock = self._sock, None
        self.hello_ack = None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
            self.up.set(0.0)
        with self._waiters_lock:
            waiters, self._waiters = self._waiters, {}
        for w in waiters.values():
            w.error = err
            w.event.set()

    def _read_loop(self, reader, sock: socket.socket) -> None:
        try:
            while True:
                msg = reader.read_msg()
                rid = getattr(msg, "request_id",
                              getattr(msg, "nonce", 0))
                if isinstance(msg, ErrorReply) and rid == 0:
                    raise self.unavailable(
                        f"{self.prefix} connection error {msg.code}: "
                        f"{msg.message}")
                with self._waiters_lock:
                    w = self._waiters.pop(rid, None)
                if w is not None:
                    w.reply = msg
                    w.event.set()
                # unmatched reply: waiter already timed out — drop it
        except (EOFError, OSError, ProtocolError, DaemonUnavailable) as exc:
            with self._conn_lock:
                if self._sock is sock:
                    self._teardown(self.unavailable(
                        f"{self.prefix} connection lost: {exc}"))

    # --- request primitives ---

    def _send(self, rid: int, msg) -> Waiter:
        w = Waiter()
        with self._waiters_lock:
            self._waiters[rid] = w
        sock = None
        try:
            data = self.proto.encode_frame(msg)
            sock = self._sock
            if sock is None:
                raise self.unavailable(f"{self.prefix} not connected")
            with self._wlock:
                sock.sendall(data)
            return w
        except BaseException as exc:
            # EVERY failure path must unregister the waiter, including
            # the sock-is-None raise above (a connect race would
            # otherwise leak the entry until teardown)
            with self._waiters_lock:
                self._waiters.pop(rid, None)
            if isinstance(exc, OSError):
                with self._conn_lock:
                    if self._sock is sock:
                        self._teardown(self.unavailable(str(exc)))
                raise self.unavailable(
                    f"{self.prefix} send failed: {exc}") from exc
            raise

    def _await(self, rid: int, w: Waiter, deadline_s: float):
        if not w.event.wait(deadline_s):
            with self._waiters_lock:
                self._waiters.pop(rid, None)
            raise self.unavailable(
                f"{self.prefix} request deadline ({deadline_s:.3f}s) "
                f"exceeded")
        if w.error is not None:
            raise self.unavailable(str(w.error)) from w.error
        return w.reply

    def _roundtrip(self, rid: int, msg, deadline_s: Optional[float],
                   expect: type):
        """Send, wait out the deadline (the client's default when None)
        and insist on a reply of class ``expect``."""
        reply = self._await(rid, self._send(rid, msg),
                            deadline_s or self._request_deadline_s)
        if not isinstance(reply, expect):
            raise self.unavailable(
                f"unexpected reply {type(reply).__name__}")
        return reply

    # --- public API ---

    def ping(self, deadline_s: Optional[float] = None):
        self._ensure_connected()
        nonce = next(self._seq)
        return self._roundtrip(nonce, Ping(nonce=nonce), deadline_s,
                               self.proto.Pong)

    def stats(self, deadline_s: Optional[float] = None) -> Dict:
        """Daemon introspection snapshot. StatsResponse carries no id,
        so stats calls serialize on request id 0 — fine for a debug
        endpoint."""
        self._ensure_connected()
        reply = self._roundtrip(0, StatsRequest(), deadline_s,
                                StatsResponse)
        return json.loads(reply.stats_json.decode())

"""The framed-daemon server: listener, connections, handshake, frame loop.

One thread accepts; one thread per connection runs the handshake and
then the frame loop. The client's ``Hello`` comes first; the server
answers ``HelloAck`` at the NEGOTIATED version (the lower of the two,
within the namespace's ``SUPPORTED_VERSIONS``), or ``ErrorReply``
(``ERR_VERSION``) and a closed connection. Anything else first is a
protocol error. The loop decodes each frame under the
``<prefix>.conn.decode`` span (the wait for the next frame gets none),
answers ``Ping`` and ``StatsRequest`` itself and hands every other
message to the daemon's handler, looked up by class in ``_routes``. A
malformed frame is answered ``ERR_PROTOCOL`` and closes the connection:
framing is lost and the stream cannot recover.

A daemon subclasses :class:`FramedServer`, sets its namespace module,
name prefix and metric objects (``connections`` gauge, ``requests`` and
``protocol_errors`` counters) as class attributes, assigns its
:class:`~tmtpu.libs.framed.gather.GatherQueue` to ``coalescer``, adds
its routes, and supplies the hooks below: the ``HelloAck``, the
``Pong``, the health verdict, and anything it starts or stops beside the
queue.

``drain`` then ``stop`` is a graceful shutdown. The optional health
listener serves ``/healthz`` (200/503 by the daemon's verdict, with its
snapshot), ``/metrics`` (Prometheus text) and whatever else the daemon
routes.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from tmtpu.libs import trace
from tmtpu.libs.framed.protocol import (
    ERR_PROTOCOL,
    ERR_VERSION,
    ErrorReply,
    Ping,
    ProtocolError,
    StatsRequest,
    StatsResponse,
    parse_addr,
)


def _shut(sock: socket.socket) -> None:
    # shutdown() before close(): close() alone does not wake a thread
    # blocked in accept() or recv() on the socket
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


class FramedServer:
    proto = None                  # the daemon's namespace module
    prefix = "framed"             # thread and span names
    LISTEN_BACKLOG = 64
    connections = None            # gauge: connections held
    requests = None               # counter{type}: messages handled
    protocol_errors = None        # counter{kind}: frames refused

    def __init__(self, addr: str, *, max_frame_bytes: int,
                 health_laddr: str, server_id: str):
        self.addr = addr
        self._kind, self._target = parse_addr(addr)
        self._max_frame_bytes = max_frame_bytes
        self._health_laddr = health_laddr
        self.server_id = server_id or f"{self.prefix}-{os.getpid()}"
        self.coalescer = None         # the daemon's GatherQueue
        # message class -> (requests_total type label, handler), the
        # handler called as handler(client_id, msg, send)
        self._routes: Dict[type, Tuple[str, Callable]] = {
            Ping: ("ping", self._on_ping),
            StatsRequest: ("stats", self._on_stats)}
        self._listener: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._health_httpd = None
        self._health_thread: Optional[threading.Thread] = None
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        self._running = False
        self._draining = False
        self._started_at = 0.0

    # --- what the daemon supplies ---

    def _hello_ack(self, hello, version: int):
        """The HelloAck for an accepted ``hello`` at the negotiated
        ``version``, or an ErrorReply that refuses it."""
        raise NotImplementedError

    def _pong(self, nonce: int, uptime_ms: int):
        raise NotImplementedError

    def _health(self) -> Tuple[bool, Dict]:
        """``/healthz``: the verdict and the document beside it."""
        raise NotImplementedError

    def _http_get(self, path: str) -> Optional[Tuple[int, Dict]]:
        """Any further health-listener route: (status, JSON document),
        or None for 404."""
        return None

    def _start_engine(self) -> None:
        self.coalescer.start()

    def _stop_engine(self) -> None:
        self.coalescer.stop()

    # --- lifecycle ---

    def start(self) -> None:
        if self._running:
            return
        if self._kind == "unix":
            try:
                os.unlink(self._target)
            except FileNotFoundError:
                pass
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.bind(self._target)
        else:
            host, port = self._target
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind((host, port))
            if port == 0:
                # ephemeral-port bind: rewrite addr so clients/tests can
                # read the real endpoint back off server.addr
                port = sock.getsockname()[1]
                self._target = (host, port)
                self.addr = f"tcp://{host}:{port}"
        sock.listen(self.LISTEN_BACKLOG)
        self._listener = sock
        self._running = True
        self._started_at = time.monotonic()
        self._start_engine()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name=f"{self.prefix}-accept",
            daemon=True)
        self._accept_thread.start()
        if self._health_laddr:
            self._start_health_http()

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful-shutdown phase one (the SIGTERM path): stop taking
        new work, finish what's in flight. Closes the listener; the
        daemon's handlers answer every later request STATUS_OVERLOADED
        (clients treat ONLY overload as penalty-free fallback — a drain
        must not cost every connected client a breaker-worth of
        errors). Blocks until the queue has dispatched and answered
        everything, or the timeout passes (returns False). Ping/Stats
        keep working throughout. Call stop() afterwards."""
        self._draining = True
        listener, self._listener = self._listener, None
        if listener is not None:
            _shut(listener)
        return self.coalescer.drain(timeout)

    def stop(self) -> None:
        self._running = False
        listener, self._listener = self._listener, None
        if listener is not None:
            _shut(listener)
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            _shut(c)
        self._stop_engine()
        if self._health_httpd is not None:
            try:
                self._health_httpd.shutdown()
                self._health_httpd.server_close()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
            self._health_httpd = None
        ht = self._health_thread
        if ht is not None and ht is not threading.current_thread():
            ht.join(timeout=2.0)
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5.0)
            self._accept_thread = None
        if self._kind == "unix":
            try:
                os.unlink(self._target)
            except OSError:
                pass

    def snapshot(self) -> Dict:
        with self._conns_lock:
            n_conns = len(self._conns)
        return {
            "server_id": self.server_id,
            "addr": self.addr,
            "draining": self._draining,
            "uptime_s": round(max(0.0, time.monotonic() -
                                  self._started_at), 3),
            "connections": n_conns,
            "coalescer": self.coalescer.snapshot(),
        }

    # --- connection handling ---

    def _accept_loop(self) -> None:
        while self._running:
            listener = self._listener
            if listener is None:
                return
            try:
                conn, _peer = listener.accept()
            except OSError:
                return  # listener closed
            with self._conns_lock:
                self._conns.add(conn)
                self.connections.set(len(self._conns))
            threading.Thread(target=self._serve_conn, args=(conn,),
                             name=f"{self.prefix}-conn",
                             daemon=True).start()

    def _drop_conn(self, conn) -> None:
        with self._conns_lock:
            self._conns.discard(conn)
            self.connections.set(len(self._conns))
        _shut(conn)

    def _serve_conn(self, conn: socket.socket) -> None:
        proto = self.proto
        wlock = threading.Lock()

        def send(msg) -> None:
            # a frame already encoded (the verify reply's) goes as it is.
            # A failed send means the client is gone: the dispatch already
            # happened, and the frame loop's next read ends the connection
            data = msg if isinstance(msg, bytes) else proto.encode_frame(msg)
            try:
                with wlock:
                    conn.sendall(data)
            except OSError:
                pass

        def refuse(kind: str, code: int, message: str) -> None:
            self.protocol_errors.inc(kind=kind)
            send(ErrorReply(code=code, message=message))

        reader = proto.FrameReader(conn.makefile("rb"),
                                   self._max_frame_bytes)
        try:
            first = reader.read_msg()
            if not isinstance(first, proto.Hello):
                refuse("no-hello", ERR_PROTOCOL,
                       f"expected Hello, got {type(first).__name__}")
                return
            if first.version not in proto.SUPPORTED_VERSIONS:
                refuse("version-mismatch", ERR_VERSION,
                       f"protocol version {first.version} not in "
                       f"server-supported {list(proto.SUPPORTED_VERSIONS)}")
                return
            # version-skew tolerance: serve old clients at their version
            # (they skip newer optional fields as unknown anyway, but the
            # ack tells THEM not to send any)
            ack = self._hello_ack(
                first, min(first.version, proto.PROTOCOL_VERSION))
            if isinstance(ack, ErrorReply):
                send(ack)
                return
            client_id = first.client_id or "anon"
            self.requests.inc(type="hello")
            send(ack)
            routes = self._routes
            decode_span = f"{self.prefix}.conn.decode"
            while self._running:
                # the wait for the client's next frame gets no span
                body = reader.read_body()
                with trace.span(decode_span, bytes=len(body)):
                    msg = reader.decode(body)
                route = routes.get(type(msg))
                if route is not None:
                    self.requests.inc(type=route[0])
                    route[1](client_id, msg, send)
                else:
                    refuse("unexpected-type", ERR_PROTOCOL,
                           f"unexpected {type(msg).__name__}")
        except ProtocolError as exc:
            # framing is lost; the stream cannot recover
            refuse("bad-frame", ERR_PROTOCOL, str(exc))
        except (EOFError, OSError):
            pass  # peer went away
        finally:
            self._drop_conn(conn)

    def _on_ping(self, client_id: str, msg, send) -> None:
        send(self._pong(msg.nonce, int((time.monotonic() -
                                        self._started_at) * 1000)))

    def _on_stats(self, client_id: str, msg, send) -> None:
        send(StatsResponse(stats_json=json.dumps(self.snapshot()).encode()))

    # --- health HTTP ---

    def _start_health_http(self) -> None:
        import http.server

        server = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def do_GET(self):
                ctype = "application/json"
                if self.path.startswith("/healthz"):
                    healthy, doc = server._health()
                    status = 200 if healthy else 503
                    body = json.dumps({"healthy": healthy, **doc}).encode()
                elif self.path.startswith("/metrics"):
                    from tmtpu.libs import metrics as _m

                    status, body = 200, _m.render_prometheus().encode()
                    ctype = "text/plain; version=0.0.4"
                else:
                    got = server._http_get(self.path)
                    if got is None:
                        status, body = 404, b"not found\n"
                        ctype = "text/plain"
                    else:
                        status, body = got[0], json.dumps(got[1]).encode()
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        host, _sep, port = self._health_laddr.rpartition(":")
        httpd = http.server.ThreadingHTTPServer(
            (host or "127.0.0.1", int(port)), Handler)
        self._health_httpd = httpd
        self._health_thread = threading.Thread(
            target=httpd.serve_forever, name=f"{self.prefix}-health",
            daemon=True)
        self._health_thread.start()

"""The sidecar daemon: socket listener, protocol loop, verify engine.

One daemon process owns the JAX device for a whole host — the ONLY
process on the chip: its clients never open JAX. It compiles the
Pallas verify kernels once
(``warm()`` forces the compiles at startup instead of on the first
client's request) and serves every node process through the
cross-client coalescer, so N validators pay each shape's compile once
instead of N times, and their lanes merge into joint dispatches.

The verify engine is :func:`tmtpu.crypto.batch.new_batch_verifier` —
the daemon inherits the whole in-process stack for free: the
daemon-wide sigcache (a signature verified for node A is a cache hit
when node B re-proves it), the ``crypto.tpu`` breaker with serial
fallback, per-batch deadlines, and the batch metric set. A sidecar
daemon never returns a wrong mask: device failure degrades to the
engine's exact serial re-verify, and engine failure degrades to an
error verdict the client treats as "no answer, verify locally".

Introspection: ``Ping``/``StatsRequest`` on the protocol socket, plus
an optional HTTP listener (``health_laddr``) serving ``/healthz``
(JSON snapshot, 200/503 by backend-breaker state), ``/metrics``
(Prometheus text) for curl/scrapers that don't speak the frame
protocol, and ``/debug/profile?seconds=N`` (a ``jax.profiler`` trace of
the daemon, the program's spans in it).

Run it: ``python -m tmtpu sidecar --addr unix:///tmp/tmtpu-sidecar.sock``
(cmd/__main__.py), point nodes at it with ``crypto.backend=sidecar``.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import Dict, List, Optional, Tuple

from tmtpu.crypto import batch as crypto_batch
from tmtpu.crypto import encoding as _enc  # noqa: F401 — registers all
# curve key types in KEY_TYPES (the daemon validates request curves
# against that registry before anything else imports the curve modules)
from tmtpu.crypto.keys import KEY_TYPES
from tmtpu.libs import breaker as _bk
from tmtpu.libs import trace
from tmtpu.sidecar import protocol as proto
from tmtpu.sidecar.coalescer import Coalescer, Overloaded
from tmtpu.tpu import compat

_FAILURE_STATUS = {
    "expired": proto.STATUS_OVERLOADED,
    "engine": proto.STATUS_BACKEND_DOWN,
    "stopped": proto.STATUS_SHUTTING_DOWN,
}


class SidecarServer:
    def __init__(self, addr: str, *,
                 backend: str = "auto",
                 max_queue_lanes: int = 65536,
                 max_lanes_per_dispatch: int = 40960,
                 max_frame_bytes: int = proto.DEFAULT_MAX_FRAME_BYTES,
                 request_deadline_s: float = 30.0,
                 health_laddr: str = "",
                 server_id: str = "",
                 mesh_devices: Optional[int] = None,
                 shard_min_lanes: Optional[int] = None,
                 profile_dir: str = ""):
        self.addr = addr
        self._kind, self._target = proto.parse_addr(addr)
        if backend not in ("auto", "cpu", "tpu"):
            raise ValueError(
                f"sidecar daemon backend must be auto/cpu/tpu, got "
                f"{backend!r} (a daemon serving 'sidecar' would recurse)")
        self._backend = backend
        # daemon-side mesh knobs: the daemon owns every chip on the
        # host, so its [sidecar] overrides win over [crypto] here
        self._mesh_devices = mesh_devices
        self._shard_min_lanes = shard_min_lanes
        self._max_lanes_per_dispatch = max_lanes_per_dispatch
        self._max_frame_bytes = max_frame_bytes
        self._default_deadline_s = request_deadline_s
        self._health_laddr = health_laddr
        # where GET /debug/profile writes (cmd_sidecar: <home>/data/profile)
        self._profile_dir = profile_dir
        self._profile_lock = threading.Lock()
        self.server_id = server_id or f"sidecar-{os.getpid()}"
        self.coalescer = Coalescer(
            self._engine_verify,
            max_queue_lanes=max_queue_lanes,
            max_lanes_per_dispatch=max_lanes_per_dispatch)
        self._listener: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._health_httpd = None
        self._health_thread: Optional[threading.Thread] = None
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        self._running = False
        self._draining = False
        self._started_at = 0.0
        self._warmed = False
        # (curve, lanes, tally, seconds) per warm() flush
        self.warmed_shapes: List[tuple] = []

    # --- verify engine ---

    def _engine_verify(self, curve: str, items: List[tuple],
                       tally: bool) -> Tuple[List[bool], int]:
        """Coalescer dispatch target: raw (pk_bytes, msg, sig, power)
        lanes → PubKey objects → one in-process batch verify."""
        pk_cls = KEY_TYPES[curve][0]
        bv = crypto_batch.new_batch_verifier(self._backend)
        for pk_b, msg, sig, power in items:
            bv.add(pk_cls(pk_b), msg, sig, power)
        if tally:
            _all_ok, mask, tallied = bv.verify_tally()
        else:
            _all_ok, mask = bv.verify()
            tallied = 0
        return mask, tallied

    def _device_engine(self) -> bool:
        """Does the engine dispatch to JAX (vs the serial CPU verifier)?
        ``auto`` answers by the probe, so an open ``crypto.tpu`` breaker
        reads as the CPU engine it currently is."""
        return self._backend == "tpu" or (
            self._backend == "auto" and crypto_batch._tpu_available())

    def backend_name(self) -> str:
        """What ``HelloAck``/``Pong``/``/healthz`` report: ``cpu`` for
        the serial engine, ``tpu`` only when the device engine's JAX
        platform is a TPU, and ``xla:<platform>`` for the device graph
        emulated on anything else (``JAX_PLATFORMS=cpu``)."""
        if not self._device_engine():
            return "cpu"
        platform = compat.device_platform()
        return "tpu" if platform == "tpu" else f"xla:{platform}"

    def warm(self) -> float:
        """Prove the engine with one self-signed batch and, on the
        device engine, compile every ed25519 shape up to the dispatch
        cap (crypto/batch.py ``warm_daemon``) NOW, so no client request
        waits out a first-sight compile past its deadline. Returns the
        warm-up wall seconds."""
        from tmtpu.crypto import ed25519 as _ed

        t0 = time.perf_counter()
        priv = _ed.gen_priv_key()
        pk = priv.pub_key()
        lanes = max(crypto_batch._TPU_MIN_BATCH, 8)
        items = []
        for i in range(lanes):
            msg = b"sidecar-warm-%d" % i
            items.append((pk.bytes(), msg, priv.sign(msg), 1))
        mask, _ = self._engine_verify("ed25519", items, tally=False)
        if not all(mask):
            raise RuntimeError("sidecar warm-up verify returned invalid "
                               "for self-signed lanes")
        if self._device_engine():
            self.warmed_shapes = crypto_batch.warm_daemon(
                self._max_lanes_per_dispatch)
        self._warmed = True
        return time.perf_counter() - t0

    def profile(self, seconds: float) -> str:
        """Run ``jax.profiler`` over this process for ``seconds`` (at
        most 60) and return the directory that holds the trace. The
        program's spans (libs/trace) are in it beside the device's
        operations, on one clock. One profile at a time; only the device
        engine has JAX open to profile."""
        if not self._profile_dir:
            raise RuntimeError("the daemon was started without a profile "
                               "directory")
        if not self._device_engine():
            raise RuntimeError("the engine is the serial CPU verifier: "
                               "jax.profiler has nothing to trace")
        if not self._profile_lock.acquire(blocking=False):
            raise RuntimeError("a profile is already running")
        try:
            import jax
            from jax.profiler import ProfileOptions

            os.makedirs(self._profile_dir, exist_ok=True)
            opts = ProfileOptions()
            opts.python_tracer_level = 0    # it slows every Python call
            opts.host_tracer_level = 2      # TraceAnnotations: our spans
            jax.profiler.start_trace(self._profile_dir,
                                     profiler_options=opts)
            try:
                time.sleep(min(max(seconds, 0.0), 60.0))
            finally:
                jax.profiler.stop_trace()
        finally:
            self._profile_lock.release()
        return self._profile_dir

    # --- lifecycle ---

    def start(self) -> None:
        if self._running:
            return
        if self._kind == "unix":
            path = self._target
            try:
                os.unlink(path)
            except FileNotFoundError:
                pass
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.bind(path)
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
        sock.listen(64)
        self._listener = sock
        self._running = True
        self._started_at = time.monotonic()
        if self._mesh_devices is not None or \
                self._shard_min_lanes is not None:
            from tmtpu.tpu import mesh_dispatch as _mesh

            _mesh.set_overrides(mesh_devices=self._mesh_devices,
                                shard_min_lanes=self._shard_min_lanes)
        self.coalescer.start()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="sidecar-accept", daemon=True)
        self._accept_thread.start()
        if self._health_laddr:
            self._start_health_http()

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful-shutdown phase one (the SIGTERM path): stop taking
        new work, finish what's in flight. Closes the listener, answers
        every subsequent VerifyRequest with STATUS_OVERLOADED (clients
        treat ONLY overload as penalty-free fallback — a drain must not
        cost every connected node a breaker-worth of errors), and blocks
        until the coalescer has dispatched its queue and answered every
        in-flight joint batch, or the timeout passes (returns False).
        Ping/Stats keep working throughout. Call stop() afterwards."""
        self._draining = True
        listener, self._listener = self._listener, None
        if listener is not None:
            try:
                listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                listener.close()
            except OSError:
                pass
        return self.coalescer.drain(timeout)

    def stop(self) -> None:
        self._running = False
        if self._listener is not None:
            # shutdown() before close(): close() alone does not wake a
            # thread blocked in accept(), which would leave stop() eating
            # the full accept-thread join timeout
            try:
                self._listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._listener.close()
            except OSError:
                pass
            self._listener = None
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        self.coalescer.stop()
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
        from tmtpu.libs import metrics as _m

        with self._conns_lock:
            n_conns = len(self._conns)
        return {
            "server_id": self.server_id,
            "addr": self.addr,
            "backend": self.backend_name(),
            # as JAX reports it; empty for the serial engine (no JAX)
            "device": (compat.device_info() if self._device_engine()
                       else {}),
            # engine dispatches by the batch metric set's own labels
            # (curve, platform, impl): whether lanes ran the Pallas
            # kernel on a TPU or something slower, and how many
            "dispatched": _m.crypto_verify_latency.summary_series(),
            "dispatched_lanes": _m.crypto_batch_size.summary_series(),
            "cpu_fallback": _m.crypto_cpu_fallback.summary_series(),
            "warmed": self._warmed,
            "warmed_shapes": [
                {"curve": c, "lanes": b, "tally": t,
                 "seconds": round(sec, 3)}
                for c, b, t, sec in self.warmed_shapes],
            "draining": self._draining,
            "uptime_s": round(max(0.0, time.monotonic() -
                                  self._started_at), 3),
            "connections": n_conns,
            "coalescer": self.coalescer.snapshot(),
            "mesh": __import__(
                "tmtpu.tpu.mesh_dispatch",
                fromlist=["snapshot"]).snapshot(),
            "breakers": _bk.snapshot_all(),
            "sigcache": __import__(
                "tmtpu.crypto.sigcache", fromlist=["stats"]).stats(),
        }

    # --- connection handling ---

    def _accept_loop(self) -> None:
        from tmtpu.libs import metrics as _m

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
                _m.sidecar_server_connections.set(len(self._conns))
            threading.Thread(target=self._serve_conn, args=(conn,),
                             name="sidecar-conn", daemon=True).start()

    def _drop_conn(self, conn) -> None:
        from tmtpu.libs import metrics as _m

        with self._conns_lock:
            self._conns.discard(conn)
            _m.sidecar_server_connections.set(len(self._conns))
        try:
            conn.close()
        except OSError:
            pass

    def _serve_conn(self, conn: socket.socket) -> None:
        from tmtpu.libs import metrics as _m

        rfile = conn.makefile("rb")
        wlock = threading.Lock()

        def send(msg) -> None:
            # a frame already encoded (the verify reply's) goes as it is
            data = msg if isinstance(msg, bytes) else proto.encode_frame(msg)
            with wlock:
                conn.sendall(data)

        reader = proto.FrameReader(rfile, self._max_frame_bytes)
        try:
            # handshake: Hello first, version within SUPPORTED_VERSIONS
            try:
                first = reader.read_msg()
            except proto.ProtocolError as exc:
                _m.sidecar_server_protocol_errors.inc(kind="bad-frame")
                try:
                    send(proto.ErrorReply(code=proto.ERR_PROTOCOL,
                                          message=str(exc)))
                except OSError:
                    pass
                return
            if not isinstance(first, proto.Hello):
                _m.sidecar_server_protocol_errors.inc(kind="no-hello")
                send(proto.ErrorReply(
                    code=proto.ERR_PROTOCOL,
                    message=f"expected Hello, got "
                            f"{type(first).__name__}"))
                return
            if first.version not in proto.SUPPORTED_VERSIONS:
                _m.sidecar_server_protocol_errors.inc(
                    kind="version-mismatch")
                send(proto.ErrorReply(
                    code=proto.ERR_VERSION,
                    message=f"protocol version {first.version} not in "
                            f"server-supported "
                            f"{list(proto.SUPPORTED_VERSIONS)}"))
                return
            # version-skew tolerance: serve old clients at their version
            # (they never see v2-only optional fields anyway — unknown
            # fields are skipped — but the ack tells THEM not to send any)
            negotiated = min(first.version, proto.PROTOCOL_VERSION)
            client_id = first.client_id or "anon"
            _m.sidecar_server_requests.inc(type="hello")
            send(proto.HelloAck(
                version=negotiated,
                server_id=self.server_id,
                backend=self.backend_name(),
                max_lanes=self._max_lanes_per_dispatch,
                max_frame_bytes=self._max_frame_bytes))
            while self._running:
                items = None
                try:
                    # the wait for the client's next frame gets no span
                    body = reader.read_body()
                    with trace.span("sidecar.conn.decode",
                                    bytes=len(body)):
                        msg = reader.decode(body)
                        if isinstance(msg, proto.VerifyRequest):
                            items = [(ln.pub_key, ln.msg, ln.sig, ln.power)
                                     for ln in msg.lanes]
                except proto.ProtocolError as exc:
                    _m.sidecar_server_protocol_errors.inc(kind="bad-frame")
                    try:
                        send(proto.ErrorReply(code=proto.ERR_PROTOCOL,
                                              message=str(exc)))
                    except OSError:
                        pass
                    return  # framing is lost; the stream cannot recover
                if isinstance(msg, proto.VerifyRequest):
                    _m.sidecar_server_requests.inc(type="verify")
                    self._handle_verify(client_id, msg, items, send)
                elif isinstance(msg, proto.Ping):
                    _m.sidecar_server_requests.inc(type="ping")
                    send(proto.Pong(
                        nonce=msg.nonce, backend=self.backend_name(),
                        uptime_ms=int((time.monotonic() -
                                       self._started_at) * 1000)))
                elif isinstance(msg, proto.StatsRequest):
                    _m.sidecar_server_requests.inc(type="stats")
                    send(proto.StatsResponse(stats_json=json.dumps(
                        self.snapshot()).encode()))
                else:
                    _m.sidecar_server_protocol_errors.inc(
                        kind="unexpected-type")
                    send(proto.ErrorReply(
                        code=proto.ERR_PROTOCOL,
                        message=f"unexpected {type(msg).__name__}"))
        except (EOFError, OSError, BrokenPipeError):
            pass  # peer went away
        finally:
            self._drop_conn(conn)

    def _handle_verify(self, client_id: str, req: proto.VerifyRequest,
                       items: List[tuple], send) -> None:
        def reject(status: int, error: str) -> None:
            send(proto.VerifyResponse(
                request_id=req.request_id, status=status,
                lane_count=len(req.lanes), error=error))

        if self._draining:
            # OVERLOADED, not SHUTTING_DOWN: the client's overload path
            # falls back in-process without charging its breaker
            reject(proto.STATUS_OVERLOADED, "daemon draining for shutdown")
            return
        if req.curve not in KEY_TYPES:
            reject(proto.STATUS_BAD_REQUEST,
                   f"unknown curve {req.curve!r}")
            return
        if not req.lanes:
            reject(proto.STATUS_BAD_REQUEST, "zero lanes")
            return
        if len(req.lanes) > self._max_lanes_per_dispatch:
            reject(proto.STATUS_OVERLOADED,
                   f"{len(req.lanes)} lanes exceeds per-request cap "
                   f"{self._max_lanes_per_dispatch}")
            return
        deadline_s = (req.deadline_ms / 1000.0 if req.deadline_ms
                      else self._default_deadline_s)
        # v2 piggybacked trace context: strict decode, garbage ⇒ untraced
        # (never rejected — the context is advisory, not load-bearing)
        trace_ctx = None
        if req.trace_ctx:
            from tmtpu.libs import metrics as _m

            trace_ctx = trace.adopt(bytes(req.trace_ctx))
            if trace_ctx is None:
                _m.trace_context_invalid.inc(transport="sidecar")
            else:
                _m.trace_context_rx.inc(transport="sidecar")
        try:
            pending = self.coalescer.submit(
                client_id, req.curve, items, req.tally,
                deadline_s=deadline_s, trace_ctx=trace_ctx)
        except Overloaded as exc:
            reject(proto.STATUS_OVERLOADED, str(exc))
            return

        def finish() -> None:
            # grace over the request deadline: the coalescer answers
            # expiry itself; this wait only guards a wedged dispatch
            if not pending.wait(deadline_s + 5.0):
                try:
                    reject(proto.STATUS_BACKEND_DOWN,
                           "dispatch wedged past deadline")
                except OSError:
                    pass
                return
            if pending.mask is None:
                status = _FAILURE_STATUS.get(
                    pending.failure, proto.STATUS_BACKEND_DOWN)
                try:
                    reject(status, pending.error or "verify failed")
                except OSError:
                    pass
                return
            with trace.span("sidecar.conn.encode",
                            lanes=len(pending.mask)):
                frame = proto.encode_frame(proto.VerifyResponse(
                    request_id=req.request_id,
                    status=proto.STATUS_OK,
                    mask=proto.pack_mask(pending.mask),
                    lane_count=len(pending.mask),
                    tallied=pending.tallied,
                    dispatch_id=pending.dispatch_id,
                    dispatch_lanes=pending.dispatch_lanes,
                    dispatch_clients=pending.dispatch_clients,
                    dispatch_traces=pending.dispatch_traces))
            try:
                send(frame)
            except OSError:
                pass  # client gone; the dispatch already happened

        # answer off-thread so the connection keeps reading — one client
        # can pipeline many request_ids and they coalesce with each other
        threading.Thread(target=finish, name="sidecar-reply",
                         daemon=True).start()

    # --- health HTTP ---

    def _start_health_http(self) -> None:
        import http.server

        server = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def do_GET(self):
                if self.path.startswith("/healthz"):
                    snap = server.snapshot()
                    br = snap["breakers"].get(
                        crypto_batch.BREAKER_NAME, {})
                    healthy = br.get("state", "closed") != "open"
                    body = json.dumps(
                        {"healthy": healthy, **snap}).encode()
                    self.send_response(200 if healthy else 503)
                    ctype = "application/json"
                elif self.path.startswith("/metrics"):
                    from tmtpu.libs import metrics as _m

                    body = _m.render_prometheus().encode()
                    self.send_response(200)
                    ctype = "text/plain; version=0.0.4"
                elif self.path.startswith("/debug/profile"):
                    from urllib.parse import parse_qs, urlparse

                    query = parse_qs(urlparse(self.path).query)
                    try:
                        seconds = float(query.get("seconds", ["5"])[0])
                        body = json.dumps({
                            "seconds": seconds,
                            "dir": server.profile(seconds)}).encode()
                        self.send_response(200)
                    except (ValueError, RuntimeError) as exc:
                        body = json.dumps({"error": str(exc)}).encode()
                        self.send_response(409)
                    ctype = "application/json"
                else:
                    body = b"not found\n"
                    self.send_response(404)
                    ctype = "text/plain"
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        host, _sep, port = self._health_laddr.rpartition(":")
        httpd = http.server.ThreadingHTTPServer(
            (host or "127.0.0.1", int(port)), Handler)
        self._health_httpd = httpd
        self._health_thread = threading.Thread(
            target=httpd.serve_forever, name="sidecar-health",
            daemon=True)
        self._health_thread.start()

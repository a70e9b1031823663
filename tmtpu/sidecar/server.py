"""The sidecar daemon: the verify engine behind the framed server.

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

The socket side, ``Ping``/``StatsRequest`` and the health listener are
:class:`tmtpu.libs.framed.server.FramedServer`'s; here ``/healthz`` is
200/503 by the backend breaker, and ``/debug/profile?seconds=N`` is a
``jax.profiler`` trace of the daemon, the program's spans in it.

Run it: ``python -m tmtpu sidecar --addr unix:///tmp/tmtpu-sidecar.sock``
(cmd/__main__.py), point nodes at it with ``crypto.backend=sidecar``.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from tmtpu.crypto import batch as crypto_batch
from tmtpu.crypto import encoding as _enc  # noqa: F401 — registers all
# curve key types in KEY_TYPES (the daemon validates request curves
# against that registry before anything else imports the curve modules)
from tmtpu.crypto.keys import KEY_TYPES
from tmtpu.libs import breaker as _bk
from tmtpu.libs import metrics as _m
from tmtpu.libs import trace
from tmtpu.libs.framed.gather import failure_status
from tmtpu.libs.framed.server import FramedServer
from tmtpu.sidecar import protocol as proto
from tmtpu.sidecar.coalescer import Coalescer, Overloaded
from tmtpu.tpu import compat


class SidecarServer(FramedServer):
    proto = proto
    prefix = "sidecar"
    connections = _m.sidecar_server_connections
    requests = _m.sidecar_server_requests
    protocol_errors = _m.sidecar_server_protocol_errors

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
        self._default_deadline_s = request_deadline_s
        super().__init__(addr, max_frame_bytes=max_frame_bytes,
                         health_laddr=health_laddr, server_id=server_id)
        # where GET /debug/profile writes (cmd_sidecar: <home>/data/profile)
        self._profile_dir = profile_dir
        self._profile_lock = threading.Lock()
        self.coalescer = Coalescer(
            self._engine_verify,
            max_queue_lanes=max_queue_lanes,
            max_lanes_per_dispatch=max_lanes_per_dispatch)
        self._routes[proto.VerifyRequest] = ("verify", self._handle_verify)
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

    # --- what the framed core asks of the daemon ---

    def _start_engine(self) -> None:
        if self._mesh_devices is not None or \
                self._shard_min_lanes is not None:
            from tmtpu.tpu import mesh_dispatch as _mesh

            _mesh.set_overrides(mesh_devices=self._mesh_devices,
                                shard_min_lanes=self._shard_min_lanes)
        self.coalescer.start()

    def _hello_ack(self, hello: proto.Hello, version: int):
        return proto.HelloAck(
            version=version, server_id=self.server_id,
            backend=self.backend_name(),
            max_lanes=self._max_lanes_per_dispatch,
            max_frame_bytes=self._max_frame_bytes)

    def _pong(self, nonce: int, uptime_ms: int) -> proto.Pong:
        return proto.Pong(nonce=nonce, backend=self.backend_name(),
                          uptime_ms=uptime_ms)

    def _health(self) -> Tuple[bool, Dict]:
        snap = self.snapshot()
        br = snap["breakers"].get(crypto_batch.BREAKER_NAME, {})
        return br.get("state", "closed") != "open", snap

    def _http_get(self, path: str) -> Optional[Tuple[int, Dict]]:
        if not path.startswith("/debug/profile"):
            return None
        from urllib.parse import parse_qs, urlparse

        query = parse_qs(urlparse(path).query)
        try:
            seconds = float(query.get("seconds", ["5"])[0])
            return 200, {"seconds": seconds, "dir": self.profile(seconds)}
        except (ValueError, RuntimeError) as exc:
            return 409, {"error": str(exc)}

    def snapshot(self) -> Dict:
        return {
            **super().snapshot(),
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
            "mesh": __import__(
                "tmtpu.tpu.mesh_dispatch",
                fromlist=["snapshot"]).snapshot(),
            "breakers": _bk.snapshot_all(),
            "sigcache": __import__(
                "tmtpu.crypto.sigcache", fromlist=["stats"]).stats(),
        }

    def _handle_verify(self, client_id: str, req: proto.VerifyRequest,
                       send) -> None:
        items = [(ln.pub_key, ln.msg, ln.sig, ln.power) for ln in req.lanes]

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
                reject(proto.STATUS_BACKEND_DOWN,
                       "dispatch wedged past deadline")
                return
            if pending.mask is None:
                reject(failure_status(pending.failure,
                                      proto.STATUS_BACKEND_DOWN),
                       pending.error or "verify failed")
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
            send(frame)

        # answer off-thread so the connection keeps reading — one client
        # can pipeline many request_ids and they coalesce with each other
        threading.Thread(target=finish, name="sidecar-reply",
                         daemon=True).start()

"""BatchVerifier — the framework's batch-first signature verification API.

The reference has NO batch verifier (SURVEY.md: every signature goes through
crypto.PubKey.VerifySignature one at a time — crypto/crypto.go:25). This
interface is the new hot-path primitive every upper layer is written
against (VoteSet, VerifyCommit*, light client, evidence):

    bv = new_batch_verifier()          # picks TPU when available
    for pk, msg, sig in ...: bv.add(pk, msg, sig)
    all_ok, mask = bv.verify()

Backends:
- ``cpu``: serial per-signature verify through the PubKey objects (OpenSSL
  under the hood) — the fallback and the small-batch fast path;
- ``tpu``: groups items per curve into device batches — one
  ``tmtpu.tpu.dispatch.device_verify`` per curve of its table (ed25519,
  sr25519, secp256k1) present, so mixed-curve sets get one device
  dispatch a curve. Per-lane semantics are identical to serial
  verification (no probabilistic batch equation), so the returned mask
  is exact for mixed valid/invalid batches.

Backend selection: ``set_default_backend`` / config ``crypto.backend``.
``auto`` means the device backend only when JAX's first device is a TPU
(``jax.devices()[0].platform == "tpu"``) and the serial CPU backend on
any other platform — XLA:CPU running the curve graph is an emulation
for tests, never a deployment. The probe runs under the ``crypto.tpu``
circuit breaker: a probe that raises or hangs is retried after backoff
(libs/breaker.py, docs/RESILIENCE.md), a probe that answers with another
platform is final. An explicit ``tpu`` is checked once at launch
(tmtpu.tpu.compat.require_tpu). A ``sidecar`` node never opens JAX: the
daemon owns the chip, and one chip serves one process.

Verify-once hot path (crypto/sigcache.py): before any lane is assigned,
every (pubkey, msg, sig) triple is checked against the process-wide
verified-signature cache — a cached triple never occupies a lane, and
identical in-flight triples within one batch collapse onto a single
lane (one verify, N results). Successful verifications are inserted on
the way out, so a precommit verified at vote ingestion costs ZERO
dispatches when verify_commit re-checks it during the next height's
ApplyBlock, and blocksync/light-client re-verification of already-seen
commits short-circuits the same way. Cache hits never touch the
breaker: only real device round-trips advance ``half_open → closed``.

Adaptive flush scheduling: the module-level ``SCHEDULER`` tracks lane
arrival rate (EWMA over ``add()`` calls) and device dispatch RTT (EWMA
over timed ``_dispatch`` round-trips) and picks a flush size between
min-latency (dispatch what you have) and max-amortization (wait one RTT
worth of arrivals): ``target_lanes = clamp(rate × rtt)``. The consensus
receive loop consults ``gather_wait_s`` to decide whether a few extra
milliseconds of draining buys a materially fuller batch; the breaker
and per-batch deadline machinery are unchanged.
"""

from __future__ import annotations

import functools
import os
import threading
import time as _time_mod
from typing import Dict, List, Optional, Tuple

from tmtpu.crypto import keys, sigcache
from tmtpu.crypto.keys import PubKey
from tmtpu.libs import breaker as _bk
from tmtpu.libs import trace

ED25519 = "ed25519"
SR25519 = "sr25519"
SECP256K1 = "secp256k1"

# below this, device dispatch overhead beats CPU serial (env-overridable so
# small-validator integration tests can force the device path)
_TPU_MIN_BATCH = int(os.environ.get("TMTPU_TPU_MIN_BATCH", "8"))

# the consensus receive loop's peer queue holds this many messages and one
# drain takes no more (consensus/state.py): the widest flush a vote set
# meets short of a whole commit
DRAIN_LANES = 1000

_default_backend = os.environ.get("TMTPU_CRYPTO_BACKEND", "auto")
_probe_lock = threading.Lock()
# memo of the last ANSWERED device probe: True = JAX's first device is a
# TPU, False = JAX answered with another platform (final — ``auto``
# stays on the CPU backend), None = not yet probed / the probe raised or
# timed out → re-probe when the breaker next allows it. Tests
# monkeypatch this to True to force the device code path.
_tpu_usable: Optional[bool] = None

# the breaker governing every device touch from this module; one name so
# probe failures and batch failures share the same failure budget
BREAKER_NAME = "crypto.tpu"

# the breaker governing the sidecar round-trip path: connection failures,
# request deadlines, and hard daemon errors share one failure budget, so
# a dead daemon costs a few failed round-trips and then every batch rides
# in-process until the backoff elapses and a half-open probe reconnects.
# Overload backpressure (an explicitly HEALTHY daemon saying "not now")
# never counts against it.
SIDECAR_BREAKER_NAME = "crypto.sidecar"

# sidecar client wiring: configure_sidecar() fills this from config (and
# Node.__init__ calls it before the first verifier is built); the client
# object is built lazily on first use so importing this module never
# touches a socket. Tests monkeypatch "addr" / reset "client".
_sidecar_lock = threading.Lock()
_sidecar_state: Dict = {
    "addr": "",
    "home": "",
    "client": None,
    "connect_timeout_s": 2.0,
    "request_deadline_s": 10.0,
    "retry_backoff_s": 1.0,
    "max_frame_bytes": 8 * 1024 * 1024,
}

# defaults mirror config/config.py CryptoConfig; Node.__init__ overwrites
# via configure() before the first verifier is built
_probe_timeout_s = 20.0
_batch_deadline_s = 120.0


def _tpu_breaker() -> "_bk.CircuitBreaker":
    return _bk.get(BREAKER_NAME)


class AdaptiveFlushScheduler:
    """Pick the flush size between min-latency and max-amortization.

    Two EWMAs: lane ARRIVAL RATE (updated by every ``BatchVerifier.add``)
    and device dispatch RTT (updated by every successful timed device
    round-trip in ``_dispatch`` — serial fallbacks and cache hits do not
    count, they carry no device latency signal). The optimal batch under
    a fixed per-dispatch cost is the number of lanes that arrive during
    one RTT: fewer and the dispatch overhead dominates, more and queue
    latency dominates. So ``target_lanes = clamp(rate × rtt, min, max)``
    and ``gather_wait_s(pending)`` answers "is it worth draining a few
    more ms before flushing?" — capped at ``max_wait_s`` so consensus
    latency is bounded, and ZERO until both EWMAs have real samples
    (CPU-only nodes and fresh processes keep the legacy flush-now
    behavior)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._alpha = 0.2
        self._rate = 0.0          # lanes/s
        self._rtt = 0.0           # seconds per device round-trip
        self._last_arrival: Optional[float] = None
        self.enabled = True
        self.min_lanes = _TPU_MIN_BATCH
        self.max_lanes = 4096
        self.max_wait_s = 0.008

    def note_arrivals(self, n: int = 1) -> None:
        now = _time_mod.monotonic()
        with self._lock:
            last, self._last_arrival = self._last_arrival, now
            if last is None:
                return
            dt = now - last
            if dt <= 0:
                return
            # arrivals more than ~1s apart mean an idle gap, not a rate
            # sample — consensus rounds are sub-second; skip them so one
            # quiet stretch does not zero the EWMA
            if dt > 1.0:
                return
            inst = n / dt
            a = self._alpha
            self._rate = inst if self._rate <= 0 else \
                (1 - a) * self._rate + a * inst

    def note_dispatch(self, lanes: int, seconds: float) -> None:
        if seconds <= 0:
            return
        # compilation outliers (first XLA trace per bucket shape) would
        # poison the steady-state RTT; clamp the sample
        seconds = min(seconds, 2.0)
        with self._lock:
            a = self._alpha
            self._rtt = seconds if self._rtt <= 0 else \
                (1 - a) * self._rtt + a * seconds

    def snapshot(self) -> Dict:
        with self._lock:
            return {"rate_lanes_per_s": round(self._rate, 3),
                    "rtt_s": round(self._rtt, 6),
                    "enabled": self.enabled,
                    "target_lanes": self._target_locked()}

    def _target_locked(self) -> int:
        if not self.enabled or self._rtt <= 0 or self._rate <= 0:
            return self.min_lanes
        return int(max(self.min_lanes,
                       min(self.max_lanes, self._rate * self._rtt)))

    def target_lanes(self) -> int:
        with self._lock:
            t = self._target_locked()
        from tmtpu.libs import metrics as _m

        _m.crypto_flush_target_lanes.set(t)
        return t

    def gather_wait_s(self, pending: int) -> float:
        """Seconds the drain loop may linger to fill ``pending`` toward
        the target before flushing. 0.0 when adaptive data is absent,
        the target is already met, or the scheduler is disabled."""
        with self._lock:
            if (not self.enabled or self._rtt <= 0 or self._rate <= 0):
                return 0.0
            target = self._target_locked()
            rate = self._rate
        if pending >= target:
            return 0.0
        return min((target - pending) / rate, self.max_wait_s)

    def reset(self) -> None:
        with self._lock:
            self._rate = 0.0
            self._rtt = 0.0
            self._last_arrival = None


SCHEDULER = AdaptiveFlushScheduler()


def configure(crypto_cfg) -> None:
    """Apply a config/config.py ``CryptoConfig``: probe + per-batch
    deadlines for this module, thresholds/backoff for the ``crypto.tpu``
    breaker, ``sigcache_*`` knobs for the verified-signature cache, and
    the adaptive flush window. Safe to call again on config reload."""
    global _probe_timeout_s, _batch_deadline_s
    _probe_timeout_s = crypto_cfg.probe_timeout_ns / 1e9
    _batch_deadline_s = crypto_cfg.batch_deadline_ns / 1e9
    _bk.configure(
        BREAKER_NAME,
        failure_threshold=crypto_cfg.breaker_failure_threshold,
        backoff_base_s=crypto_cfg.breaker_backoff_base_ns / 1e9,
        backoff_max_s=crypto_cfg.breaker_backoff_max_ns / 1e9,
        half_open_probes=crypto_cfg.breaker_half_open_probes)
    sigcache.configure(
        getattr(crypto_cfg, "sigcache_max_entries",
                sigcache.DEFAULT_MAX_ENTRIES),
        getattr(crypto_cfg, "sigcache_shards", sigcache.DEFAULT_SHARDS),
        getattr(crypto_cfg, "sigcache_enable", True))
    SCHEDULER.enabled = getattr(crypto_cfg, "adaptive_flush", True)
    SCHEDULER.max_wait_s = getattr(
        crypto_cfg, "flush_max_wait_ns", 8_000_000) / 1e9
    SCHEDULER.max_lanes = getattr(crypto_cfg, "flush_max_lanes", 4096)
    from tmtpu.tpu import mesh_dispatch as _mesh

    _mesh.configure(crypto_cfg)


def probe_timeout_s() -> float:
    """The device-probe deadline. The env var is read at CALL time (it
    was import-time before, which froze the value for the process) so
    tests and operators can override without re-importing; config
    (via ``configure``) provides the base value."""
    raw = os.environ.get("TMTPU_TPU_PROBE_TIMEOUT", "")
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    return _probe_timeout_s


def batch_deadline_s() -> float:
    """Per-batch deadline on device dispatch (<= 0 disables). Same
    call-time env override pattern as ``probe_timeout_s``."""
    raw = os.environ.get("TMTPU_TPU_BATCH_DEADLINE", "")
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    return _batch_deadline_s


def set_default_backend(backend: str) -> None:
    global _default_backend, _tpu_usable
    if backend not in ("auto", "cpu", "tpu", "sidecar"):
        raise ValueError(f"unknown crypto backend {backend!r}")
    _default_backend = backend
    if backend != "auto":
        _tpu_usable = None


def configure_sidecar(sidecar_cfg, home: str = "") -> None:
    """Apply a config/config.py ``SidecarConfig`` to the client side:
    address resolution inputs, connection/request timeouts, and the
    ``crypto.sidecar`` breaker thresholds (backoff shape is shared with
    the crypto breaker config via ``configure``). Drops any existing
    client so a config reload reconnects with the new parameters."""
    with _sidecar_lock:
        old = _sidecar_state.get("client")
        _sidecar_state.update(
            addr=sidecar_cfg.addr,
            home=home,
            client=None,
            connect_timeout_s=sidecar_cfg.connect_timeout_ns / 1e9,
            request_deadline_s=sidecar_cfg.request_deadline_ns / 1e9,
            retry_backoff_s=sidecar_cfg.retry_backoff_ns / 1e9,
            max_frame_bytes=sidecar_cfg.max_frame_bytes)
    if old is not None:
        old.close()
    _bk.configure(
        SIDECAR_BREAKER_NAME,
        failure_threshold=sidecar_cfg.breaker_failure_threshold)


def _sidecar_client():
    """The process-wide sidecar client, built lazily from the configured
    (or env/home-derived) address; None when no address resolves."""
    from tmtpu.sidecar import client as _sc

    with _sidecar_lock:
        c = _sidecar_state["client"]
        if c is not None:
            return c
        addr = _sidecar_state["addr"] or _sc.default_addr(
            _sidecar_state["home"])
        if not addr:
            return None
        c = _sc.SidecarClient(
            addr,
            connect_timeout_s=_sidecar_state["connect_timeout_s"],
            request_deadline_s=_sidecar_state["request_deadline_s"],
            retry_backoff_s=_sidecar_state["retry_backoff_s"],
            max_frame_bytes=_sidecar_state["max_frame_bytes"])
        _sidecar_state["client"] = c
        return c


def reset_sidecar_client() -> None:
    """Drop the cached client (tests; config/addr changes)."""
    with _sidecar_lock:
        old, _sidecar_state["client"] = _sidecar_state["client"], None
    if old is not None:
        old.close()


def note_platform(platform: str) -> None:
    """Publish the JAX platform the device path runs on:
    ``crypto_tpu_backend_up`` is 1 only on a real TPU, whichever route
    (the ``auto`` probe, an explicit ``tpu`` at launch) learned it."""
    from tmtpu.libs import metrics as _m

    _m.crypto_tpu_backend_up.set(1.0 if platform == "tpu" else 0.0)


def _tpu_available() -> bool:
    """The ``auto`` probe: is JAX's first device a TPU? Runs under the
    ``crypto.tpu`` breaker with a hard timeout — backend init can hang
    (a chip another process holds, a wedged runtime), and consensus must
    degrade to the CPU path rather than stall. An ANSWER is cached
    either way: ``tpu`` selects the device backend, any other platform
    selects the CPU backend for the life of the process (CPU devices are
    not a device backend). A probe that raises or times out counts
    against the breaker and is retried on the next call until the
    breaker opens, after which callers get CPU immediately until the
    backoff elapses and a half-open probe runs. Every attempt, timeout,
    and the up/down verdict land in the crypto metric set
    (docs/OBSERVABILITY.md)."""
    global _tpu_usable
    if _tpu_usable is False:
        return False
    br = _tpu_breaker()
    if not br.allow():
        return False
    if _tpu_usable:
        return True
    with _probe_lock:
        if _tpu_usable is not None:
            return _tpu_usable
        from tmtpu.libs import metrics as _m
        from tmtpu.tpu import compat

        _m.crypto_device_probe_attempts.inc()
        try:
            platform = _bk.call_with_deadline(compat.device_platform,
                                              probe_timeout_s())
        except _bk.DeadlineExceeded as e:
            _m.crypto_device_probe_timeouts.inc()
            br.record_failure(e)
            platform = None
        except Exception as e:  # noqa: BLE001 — import/init failure
            br.record_failure(e)
            platform = None
        if platform is None:
            _m.crypto_tpu_backend_up.set(0.0)
            _m.crypto_cpu_fallback.inc(curve="any", reason="probe-failed")
            return False
        br.record_success()
        note_platform(platform)
        _tpu_usable = platform == "tpu"
        return _tpu_usable


def start_backend(backend: str, who: str) -> Dict:
    """Launch-time device set-up for a process whose verify engine is
    ``backend`` (node start, ``tmtpu sidecar``, ``tmtpu lightserve``).

    ``cpu`` and ``sidecar`` never import JAX — a sidecar node must not
    be able to open the chip its daemon owns. ``tpu`` and ``auto`` place
    the compile cache before the first JAX use; an explicit ``tpu``
    then exits the process (SystemExit naming the platform found)
    unless JAX found a TPU or ``JAX_PLATFORMS=cpu`` asked for the
    emulation; ``auto`` resolves by the probe. Logs once, and returns,
    what the process will verify on: resolved backend, platform,
    ``device_kind``, device count, cache directory and whether the
    native host-prep library is bound."""
    from tmtpu import native
    from tmtpu.libs import log

    info: Dict = {"backend": backend, "platform": "none", "kind": "",
                  "count": 0, "cache_dir": "",
                  "native": native.load() is not None}
    if backend in ("tpu", "auto"):
        from tmtpu.tpu import compat

        info["cache_dir"] = compat.setup_compile_cache()
        if backend == "tpu":
            info.update(compat.require_tpu(who))
            note_platform(info["platform"])
        elif _tpu_available():
            info.update(compat.device_info(), backend="tpu")
        else:
            info["backend"] = "cpu"
    log.default_logger().with_fields(module="crypto").info(
        "verify backend", who=who, **info)
    return info


def _warm_sizes(max_lanes: int) -> List[int]:
    """Flush sizes that between them land in every padded shape flushes
    of up to ``max_lanes`` lanes use under the production bucket policy
    (tmtpu.tpu.dispatch._pad_to_bucket): the smallest lane count of each
    bucket, then ``max_lanes`` itself — so the widest flush is warmed
    exactly as it will route (mesh or single device)."""
    from tmtpu.tpu import dispatch as _disp

    sizes: List[int] = []
    n = _TPU_MIN_BATCH
    while n <= max_lanes:
        sizes.append(n)
        n = _disp._pad_to_bucket(n) + 1
    if sizes and sizes[-1] != max_lanes:
        sizes.append(max_lanes)
    return sizes


def vote_flush_lanes(validators: int, lanes: int) -> int:
    """The ``min_lanes`` of a vote set's flush of ``lanes`` lanes, for a
    set of ``validators``: a drain's worth (``DRAIN_LANES``, or the set
    if that is smaller) where the flush fits it, else the whole set — so
    whatever a drain held, a vote flush meets one of two device shapes,
    those ``warm_validator_set`` compiles (``run_shape``'s reasoning, for
    votes). 0 for a set under ``_TPU_MIN_BATCH``: nothing is pinned or
    warmed, and its flushes verify serially as small batches."""
    if validators < _TPU_MIN_BATCH:
        return 0
    drain = min(validators, DRAIN_LANES)
    return drain if lanes <= drain else max(validators, lanes)


def _warm(curve: str, sizes: List[int], tally: bool
          ) -> List[Tuple[str, int, bool, float]]:
    """One flush per size of a self-signed ``curve`` lane replicated,
    sent through the same per-curve dispatch (breaker, deadline, mesh
    routing) as production and below the sigcache, so it compiles
    exactly what production will run: a verifier pinned to ``n`` lanes
    meets the shape this flush of ``n`` lanes does. Returns
    ``[(curve, lanes, tally, seconds)]``; a device failure is the
    breakers' to count (the lanes re-verify serially), never fatal."""
    from tmtpu.crypto import ed25519 as _ed
    from tmtpu.crypto import secp256k1 as _k1
    from tmtpu.crypto import sr25519 as _sr

    priv = {ED25519: _ed.gen_priv_key, SR25519: _sr.gen_priv_key,
            SECP256K1: _k1.gen_priv_key}[curve]()
    msg = b"tmtpu-warm-" + curve.encode()
    lane = (priv.pub_key(), msg, priv.sign(msg), 1)
    out = []
    for n in sizes:
        t0 = _time_mod.perf_counter()
        mask, _t = TPUBatchVerifier()._verify_pending([lane] * n, tally)
        if not all(mask):
            raise RuntimeError(
                f"warm-up verify returned invalid for {n} copies of a "
                f"self-signed {curve} lane")
        out.append((curve, n, tally, _time_mod.perf_counter() - t0))
    return out


def warm_validator_set(val_set) -> List[Tuple[str, int, bool, float]]:
    """Compile, before consensus starts, the device shapes this validator
    set's vote flushes meet (``vote_flush_lanes``: a drain's worth and
    the whole set, which is also a whole commit's through
    ``verify_commit``), so no first-sight trace + lower + compile (tens
    of seconds per shape) lands on the consensus thread inside
    ``batch_deadline``. A vote flush splits by curve under its one pin,
    so every curve of the set is warmed at both; ed25519 warms the fused
    verify+tally step VoteSet uses, the other curves their mask step. A
    set under ``_TPU_MIN_BATCH`` pins nothing and warms nothing."""
    n = len(val_set.validators)
    if not vote_flush_lanes(n, 1):
        return []
    sizes = sorted({vote_flush_lanes(n, 1), vote_flush_lanes(n, n)})
    curves = {v.pub_key.type_value() for v in val_set.validators}
    out = []
    for curve in sorted(curves & {ED25519, SR25519, SECP256K1}):
        out += _warm(curve, sizes, tally=curve == ED25519)
    return out


# the daemon cannot know its clients' validator sets; it warms the first
# four device shapes (256 ... 2048 lanes), which cover a mempool gather
# plus a mid-sized validator set's votes
_DAEMON_WARM_LANES = 2048


def warm_daemon(max_lanes_per_dispatch: int
                ) -> List[Tuple[str, int, bool, float]]:
    """The sidecar daemon's start-up compile: both ed25519 steps (mask,
    and fused verify+tally — the coalescer runs a joint dispatch with
    tally when any member asked for it) at every shape up to the
    daemon's dispatch cap or ``_DAEMON_WARM_LANES``, whichever is
    smaller. A daemon whose ``[sidecar] max_lanes_per_dispatch`` is at
    most that is therefore warm for every ed25519 shape it can
    dispatch; above it, the first sight of a wider shape still compiles
    while its clients wait out ``request_deadline`` and verify locally."""
    sizes = _warm_sizes(min(max_lanes_per_dispatch, _DAEMON_WARM_LANES))
    return _warm(ED25519, sizes, tally=False) + \
        _warm(ED25519, sizes, tally=True)


def warm_pinned(min_lanes: int, backend: Optional[str] = None
                ) -> List[Tuple[str, int, bool, float]]:
    """Compile the one shape the ed25519 mask flushes of a verifier made
    with ``new_batch_verifier(backend, min_lanes=min_lanes)`` meet,
    whatever their length, if ``backend`` resolves to the device; a cpu
    or sidecar process has nothing to compile and gets ``[]``. The
    blocksync reactor calls it before it asks for the first block, so
    that no run's first-sight trace + lower + compile lands on the sync
    thread."""
    if _resolve_backend(backend) != "tpu":
        return []
    return _warm(ED25519, [min_lanes], tally=False)


class BatchVerifier(keys.BatchVerifier):
    """Accumulate (pubkey, msg, sig[, power]) items, then verify at once.

    ``verify``/``verify_tally`` run the verify-once resolve: every lane
    is checked against the process-wide sigcache first (a hit costs no
    lane), identical in-flight triples collapse onto one lane with their
    powers folded so the fused device tally still counts every member,
    and only the deduped miss list reaches the backend hook
    ``_verify_pending``. Successful lanes are inserted into the cache on
    the way out. ``self.cache_stats`` carries the per-flush breakdown
    (lanes/hits/dedup/dispatched) for callers and the timeline.

    ``min_lanes`` pins the device shape of this verifier's flushes:
    whatever the sigcache leaves of one pads as if it held that many
    lanes, so a caller whose flushes vary in length meets the one shape
    it warmed (``warm_pinned``, ``warm_validator_set``) — and, having
    compiled it, takes the device however few lanes are left: the
    small-batch exit is for flushes nobody pinned. The serial and sidecar
    backends have no shape and ignore it."""

    def __init__(self, min_lanes: int = 0):
        self.min_lanes = int(min_lanes)
        self._items: List[Tuple[PubKey, bytes, bytes, int]] = []
        self.cache_stats: Dict = {"lanes": 0, "hits": 0, "dedup": 0,
                                  "dispatched": 0}

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes,
            power: int = 0) -> None:
        self._items.append((pub_key, bytes(msg), bytes(sig), int(power)))
        SCHEDULER.note_arrivals(1)

    def count(self) -> int:
        return len(self._items)

    def __len__(self) -> int:
        return len(self._items)

    def _verify_pending(self, items: List[Tuple[PubKey, bytes, bytes, int]],
                        tally: bool) -> Tuple[List[bool], int]:
        """Backend hook: verify the deduped cache-miss lanes. Returns
        (mask over ``items``, tallied power of valid lanes)."""
        raise NotImplementedError

    def _resolve(self, tally: bool) -> Tuple[bool, List[bool], int]:
        with trace.span("batch.resolve") as sp:
            out = self._resolve_stages(tally)
            sp.set(**self.cache_stats)   # lanes, hits, dedup, dispatched
        return out

    def _resolve_stages(self, tally: bool) -> Tuple[bool, List[bool], int]:
        """The resolve, one span a stage (never one a lane) and one call
        to the sigcache a stage (never one a lane): ``batch.keys`` the
        flush's keys, ``batch.lookup`` the hits and the dedup grouping of
        the misses, ``batch.fold`` the miss list with folded powers, then
        the backend hook (its own spans), ``batch.insert`` the valid
        lanes' inserts and the mask."""
        items = self._items
        n = len(items)
        cache = sigcache.DEFAULT
        if not cache.enabled():
            # cache off: no keys, no dedup — byte-for-byte the legacy
            # behavior (tests that count device calls rely on this)
            mask, tallied = self._verify_pending(items, tally)
            self.cache_stats = {"lanes": n, "hits": 0, "dedup": 0,
                                "dispatched": n}
            return all(mask), mask, tallied
        with trace.span("batch.keys"):
            ks = sigcache.cache_keys(items)
        pending: List[int] = []       # representative index per unique miss
        # every index sharing a triple, by its position in ``pending`` —
        # only for a triple the flush holds more than once
        members: Dict[int, List[int]] = {}
        with trace.span("batch.lookup"):
            mask = cache.contains_many(ks)
            hits = mask.count(True)
            tallied = sum(items[i][3] for i, hit in enumerate(mask)
                          if hit) if hits else 0
            group_of: Dict[bytes, int] = {}
            for i, k in enumerate(ks):
                if mask[i]:
                    continue
                pos = group_of.get(k)
                if pos is None:
                    group_of[k] = len(pending)
                    pending.append(i)
                else:
                    members.setdefault(pos, [pending[pos]]).append(i)
        dedup = n - hits - len(pending)
        if pending:
            with trace.span("batch.fold"):
                sub_items = [items[i] for i in pending]
                for pos, group in members.items():
                    pk, msg, sig, _p = sub_items[pos]
                    # fold dup-group powers into the unique lane so the
                    # fused device tally counts every member exactly once
                    sub_items[pos] = (pk, msg, sig,
                                      sum(items[j][3] for j in group))
            sub_mask, sub_tallied = self._verify_pending(sub_items, tally)
            tallied += sub_tallied
            with trace.span("batch.insert"):
                cache.add_many([ks[i] for i, ok in zip(pending, sub_mask)
                                if ok])
                for i, ok in zip(pending, sub_mask):
                    mask[i] = bool(ok)
                for pos, group in members.items():
                    for j in group:
                        mask[j] = mask[pending[pos]]
        if dedup:
            from tmtpu.libs import metrics as _m

            _m.crypto_sigcache_dedup_lanes.inc(dedup)
        self.cache_stats = {"lanes": n, "hits": hits, "dedup": dedup,
                            "dispatched": len(pending)}
        if n and (hits or dedup):
            from tmtpu.libs import timeline as _tl

            _tl.record_sigcache(lanes=n, hits=hits, dedup=dedup,
                                dispatched=len(pending))
        return all(mask), mask, tallied

    def verify(self) -> Tuple[bool, List[bool]]:
        all_ok, mask, _ = self._resolve(tally=False)
        return all_ok, mask

    def verify_tally(self) -> Tuple[bool, List[bool], int]:
        """Fused verify + power tally. Cache hits contribute their power
        host-side; the device sum covers only dispatched lanes, so the
        total still equals the sum over every valid input lane."""
        return self._resolve(tally=True)


class CPUBatchVerifier(BatchVerifier):
    def _verify_pending(self, items, tally) -> Tuple[List[bool], int]:
        """ed25519 lanes go through ONE native batched-libcrypto call
        (tmtpu/native ed25519_verify_batch — python-cryptography's
        per-call overhead roughly halves the serial rate); everything
        else, and any lane when the native library is unavailable,
        verifies per item in Python."""
        import time

        from tmtpu.libs import metrics as _m

        t0 = time.perf_counter()
        mask = [False] * len(items)
        ed_idx = [i for i, (pk, _, sig, _) in enumerate(items)
                  if pk.type_value() == ED25519 and len(sig) == 64]
        done = set()
        impl = "serial"
        with trace.span("crypto.cpu_batch_verify", lanes=len(items)):
            if len(ed_idx) >= 2:
                try:
                    from tmtpu import native

                    ok = native.ed25519_verify_batch(
                        [items[i][0].bytes() for i in ed_idx],
                        [items[i][1] for i in ed_idx],
                        [items[i][2] for i in ed_idx])
                except Exception:  # noqa: BLE001 — never break verification
                    ok = None
                if ok is not None:
                    impl = "native"
                    for i, v in zip(ed_idx, ok):
                        mask[i] = v
                    done = set(ed_idx)
            for i, (pk, msg, sig, _) in enumerate(items):
                if i not in done:
                    mask[i] = pk.verify_signature(msg, sig)
        dt = time.perf_counter() - t0
        by_curve: dict = {}
        for pk, _msg, _sig, _p in items:
            c = pk.type_value()
            by_curve[c] = by_curve.get(c, 0) + 1
        for c, n in by_curve.items():
            _m.observe_crypto_batch(c, "cpu",
                                    impl if c == ED25519 else "serial",
                                    n, 0, dt)
        from tmtpu.libs import timeline as _tl

        _tl.record_flush(backend="cpu", lanes=len(items),
                         ok=sum(mask), seconds=round(dt, 6))
        tallied = sum(it[3] for it, ok in zip(items, mask) if ok)
        return mask, tallied


class TPUBatchVerifier(BatchVerifier):
    @staticmethod
    def _split(items, curves):
        """Partition the lanes: per curve in ``curves`` (the dispatch
        table) the device-eligible lanes as ``(idx, pks, msgs, sigs,
        powers)`` — a mixed-curve valset gets one device batch a curve —
        and the indexes of the rest, which verify serially."""
        groups: Dict[str, Tuple[list, list, list, list, list]] = {}
        cpu_idx: List[int] = []
        # a flush is mostly one curve: its five lists stay in locals and
        # the table is asked only where the key type changes
        cur = idx = pks = msgs = sigs = powers = None
        for i, (pk, msg, sig, power) in enumerate(items):
            curve = pk.type_value()
            if curve != cur:
                cur = curve
                idx, pks, msgs, sigs, powers = groups.setdefault(
                    curve, ([], [], [], [], [])) if curve in curves \
                    else (None,) * 5
            if idx is not None and len(sig) == 64:
                idx.append(i)
                pks.append(pk.bytes())
                msgs.append(msg)
                sigs.append(sig)
                powers.append(power)
            else:
                cpu_idx.append(i)
        return groups, cpu_idx

    def _verify_pending(self, items, tally) -> Tuple[List[bool], int]:
        """One device flush a curve present (``tpu/dispatch.py
        device_verify``), each under the ``crypto.tpu`` breaker and the
        per-batch deadline: for ``tally`` a curve with a fused step
        returns the psum of its valid lanes' powers with the mask, the
        others' powers are summed on the host; lanes of a key type the
        table does not hold, and groups below ``_TPU_MIN_BATCH`` of a
        verifier without a pinned shape, verify serially."""
        from tmtpu.libs import metrics as _m
        from tmtpu.tpu import dispatch as _disp

        t0 = _time_mod.perf_counter()
        with trace.span("batch.split"):
            groups, cpu_idx = self._split(items, _disp.CURVES)
        mask: List[bool] = [False] * len(items)
        tallied = 0
        br = _tpu_breaker()
        deadline = batch_deadline_s()

        def _serial(idx_list, curve, reason):
            # the exact serial path: lanes the table holds no row for,
            # and lanes whose device batch failed or was never attempted
            # (open breaker, small batch)
            nonlocal tallied
            _m.crypto_cpu_fallback.inc(len(idx_list), curve=curve,
                                       reason=reason)
            with trace.span("batch.serial", lanes=len(idx_list),
                            reason=reason):
                for i in idx_list:
                    pk, msg, sig, power = items[i]
                    mask[i] = pk.verify_signature(msg, sig)
                    if mask[i]:
                        tallied += power

        def _dispatch(curve, idx_list, thunk):
            """One per-curve device batch under the breaker and the
            per-batch deadline. Any failure — hung dispatch past the
            deadline, device/runtime error — records against the
            breaker and re-verifies exactly these lanes serially, so
            the flush always returns an exact mask. Successful
            round-trips feed the adaptive flush scheduler's RTT
            estimate (cache hits and serial fallbacks never do).
            ``batch.dispatch`` is the wait on the device path: the
            worker's ``crypto.batch_verify*`` spans are its children."""
            nonlocal tallied
            failed = None
            with trace.span("batch.dispatch", curve=curve,
                            lanes=len(idx_list)) as sp:
                if not br.allow():
                    failed = "breaker-open"
                else:
                    d0 = _time_mod.perf_counter()
                    try:
                        dev_mask, dev_sum = _bk.call_with_deadline(
                            thunk, deadline)
                    except _bk.DeadlineExceeded as e:
                        _m.crypto_batch_deadline_exceeded.inc(curve=curve)
                        br.record_failure(e)
                        failed = "deadline"
                    except Exception as e:  # noqa: BLE001 — a broken
                        # device path must never take down verification
                        br.record_failure(e)
                        failed = "device-error"
                    else:
                        br.record_success()
                        SCHEDULER.note_dispatch(
                            len(idx_list), _time_mod.perf_counter() - d0)
                if failed:
                    sp.set(failed=failed)
            if failed:
                _serial(idx_list, curve, failed)
                return
            with trace.span("batch.apply"):
                for j, i in enumerate(idx_list):
                    mask[i] = bool(dev_mask[j])
                if dev_sum is None:  # no fused tally step: the host sums
                    dev_sum = sum(items[i][3] for i in idx_list if mask[i])
                tallied += dev_sum

        if cpu_idx:
            _serial(cpu_idx, "other", "unsupported")
        if groups:
            _m.crypto_flush_curves.observe(len(groups))
        # in the table's order, not the flush's: whichever lane a mixed
        # flush starts with, a process traces its kernels in one order (the
        # compile cache's keys were seen to differ with the kernel traced
        # first: PERF.md section 6, PR 38)
        for curve in _disp.CURVES:
            if curve not in groups:
                continue
            idx, pks, msgs, sigs, powers = groups[curve]
            if len(idx) < _TPU_MIN_BATCH and not self.min_lanes:
                # below this, dispatch overhead beats the serial path
                _serial(idx, curve, "small-batch")
                continue
            _dispatch(curve, idx, functools.partial(
                _disp.device_verify, curve, pks, msgs, sigs,
                powers if tally else None, self.min_lanes))
        from tmtpu.libs import timeline as _tl

        _tl.record_flush(backend="tpu", lanes=len(items),
                         ok=sum(mask),
                         seconds=round(_time_mod.perf_counter() - t0, 6))
        return mask, tallied


class SidecarBatchVerifier(BatchVerifier):
    """Ship the deduped miss lanes to the shared verification daemon.

    Slots UNDER the sigcache→dedup layer exactly like the other
    backends: ``_verify_pending`` only ever sees lanes the cache could
    not answer. Per curve present, one sidecar round-trip under the
    ``crypto.sidecar`` breaker; the daemon coalesces concurrent clients'
    lanes into joint device dispatches and returns this request's exact
    mask slice.

    Degradation ladder (never a wrong result, only a slower one):

    1. breaker open / no address → in-process verify immediately;
    2. overload backpressure → in-process verify, NO breaker penalty
       (the daemon is healthy and explicitly shedding load);
    3. connect failure / request deadline / hard error → breaker
       failure + in-process verify;
    4. the in-process fallback is the exact serial CPU verifier, always:
       the daemon owns the chip and a chip serves one process, so a
       sidecar node must never open JAX — a second process on the
       device fails or hangs inside consensus.
    """

    def _fallback_pending(self, sub_items, tally, reason):
        from tmtpu.libs import metrics as _m

        _m.sidecar_client_fallback.inc(len(sub_items), reason=reason)
        return CPUBatchVerifier()._verify_pending(sub_items, tally)

    def _verify_pending(self, items, tally) -> Tuple[List[bool], int]:
        import time as _time

        from tmtpu.libs import timeline as _tl
        from tmtpu.sidecar import client as _sc

        mask: List[bool] = [False] * len(items)
        tallied = 0
        by_curve: Dict[str, List[int]] = {}
        for i, (pk, _msg, _sig, _p) in enumerate(items):
            by_curve.setdefault(pk.type_value(), []).append(i)
        br = _bk.get(SIDECAR_BREAKER_NAME)
        client = _sidecar_client()

        def _apply(idx_list, sub_mask):
            nonlocal tallied
            for j, i in enumerate(idx_list):
                mask[i] = bool(sub_mask[j])
                if mask[i]:
                    tallied += items[i][3]

        for curve, idx in by_curve.items():
            sub_items = [items[i] for i in idx]
            if client is None:
                sub_mask, _t = self._fallback_pending(
                    sub_items, tally, "no-addr")
                _apply(idx, sub_mask)
                continue
            if not br.allow():
                sub_mask, _t = self._fallback_pending(
                    sub_items, tally, "breaker-open")
                _apply(idx, sub_mask)
                continue
            lanes = [(pk.bytes(), msg, sig, power)
                     for pk, msg, sig, power in sub_items]
            t0 = _time.perf_counter()
            try:
                sub_mask, _stallied, info = client.verify(
                    curve, lanes, tally=tally,
                    deadline_s=_sidecar_state["request_deadline_s"])
            except _sc.SidecarOverloaded:
                sub_mask, _t = self._fallback_pending(
                    sub_items, tally, "overloaded")
                _apply(idx, sub_mask)
                continue
            except _sc.SidecarUnavailable as e:
                br.record_failure(e)
                sub_mask, _t = self._fallback_pending(
                    sub_items, tally, "unavailable")
                _apply(idx, sub_mask)
                continue
            dt = _time.perf_counter() - t0
            br.record_success()
            # a sidecar round-trip IS this process's verify RTT: feed
            # the adaptive gather window exactly like a device dispatch
            SCHEDULER.note_dispatch(len(idx), dt)
            _tl.record_sidecar(
                role="client", curve=curve, lanes=len(idx),
                dispatch_lanes=info["dispatch_lanes"],
                dispatch_clients=info["dispatch_clients"],
                seconds=round(dt, 6))
            _apply(idx, sub_mask)
        return mask, tallied


def _resolve_backend(backend: Optional[str]) -> str:
    b = backend or _default_backend
    if b == "auto":
        b = "tpu" if _tpu_available() else "cpu"
    return b


def new_batch_verifier(backend: Optional[str] = None,
                       min_lanes: int = 0) -> BatchVerifier:
    b = _resolve_backend(backend)
    if b == "sidecar":
        return SidecarBatchVerifier(min_lanes)
    if b == "tpu":
        return TPUBatchVerifier(min_lanes)
    return CPUBatchVerifier(min_lanes)


def batch_verify_items(items, backend: Optional[str] = None):
    bv = new_batch_verifier(backend)
    for pk, msg, sig in items:
        bv.add(pk, msg, sig)
    return bv.verify()


def verify_one(pub_key: PubKey, msg: bytes, sig: bytes) -> bool:
    """Cache-aware single-signature verify for paths that cannot batch
    (proposal signature, Vote.verify, privval handshakes): consults the
    verified-signature cache before the serial PubKey verify and records
    successes, so e.g. a proposal re-checked after a WAL replay, or a
    vote object verified outside a VoteSet, rides the verify-once path."""
    cache = sigcache.DEFAULT
    if not cache.enabled():
        return pub_key.verify_signature(msg, sig)
    k = sigcache.cache_key(pub_key.type_value(), pub_key.bytes(), msg, sig)
    if cache.contains(k):
        return True
    ok = pub_key.verify_signature(msg, sig)
    if ok:
        cache.add(k)
    return ok

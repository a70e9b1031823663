"""Node stall watchdog: background checks with configurable deadlines.

A ``Watchdog`` runs registered checks on a fixed interval; each check
returns a verdict ``(healthy, reason, details)``. Transitions to
unhealthy emit a structured log warning and count in the
``tendermint_health_*`` metric set; the aggregate verdict backs the
``/healthz``/``/readyz`` pprof routes and the ``health_detail``
JSON-RPC method.

Built-in check factories cover the liveness axes from the paper's
10k-validator regime: height/round progress (fed by the consensus
RoundState and the libs/timeline journal, which names the stalled
step), peer count, mempool drain, and TPU-backend degradation (the
``tendermint_crypto_cpu_fallback_total`` storm a wedged or lost device
produces — see crypto/batch._tpu_available).

Each evaluation pass also scans the libs/trace span ring for spans
exceeding the slow-span SLO threshold and counts them per span name
(``tendermint_health_slow_spans_total``) — the cheap standing
aggregate of "what got slow" between full trace drains.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

# a check returns (healthy, reason, details); reason is "" when healthy
CheckFn = Callable[[], Tuple[bool, str, Dict]]


class Watchdog:
    def __init__(self, interval_s: float = 1.0,
                 slow_span_threshold_s: float = 1.0, logger=None):
        self.interval_s = max(0.05, float(interval_s))
        self.slow_span_threshold_s = float(slow_span_threshold_s)
        self._checks: "Dict[str, CheckFn]" = {}
        self._verdicts: Dict[str, Dict] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._max_span_id = 0  # slow-span scan watermark
        if logger is None:
            from tmtpu.libs import log

            logger = log.default_logger().with_fields(module="health")
        self.logger = logger

    # -- registration / lifecycle ------------------------------------------

    def register(self, name: str, fn: CheckFn) -> None:
        with self._lock:
            self._checks[name] = fn

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, name="watchdog",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.check_now()
            except Exception as e:  # noqa: BLE001 — watchdog never dies
                self.logger.error("watchdog pass failed", err=str(e))

    # -- evaluation ---------------------------------------------------------

    def check_now(self) -> Dict[str, Dict]:
        """Run every registered check once; update verdicts, metrics, and
        log unhealthy transitions. Returns the fresh verdict map."""
        from tmtpu.libs import metrics as _m

        with self._lock:
            checks = list(self._checks.items())
        now = time.time()
        all_ok = True
        for name, fn in checks:
            try:
                healthy, reason, details = fn()
            except Exception as e:  # noqa: BLE001 — a broken probe is
                # itself a health failure, not a watchdog crash
                healthy, reason, details = False, f"check raised: {e}", {}
            with self._lock:
                prev = self._verdicts.get(name)
                flipped = prev is None or prev["healthy"] != healthy
                self._verdicts[name] = {
                    "healthy": healthy, "reason": reason,
                    "details": details, "checked_at": now,
                    "since": now if flipped else prev["since"],
                }
            _m.health_check_up.set(1.0 if healthy else 0.0, check=name)
            if not healthy:
                all_ok = False
                if flipped:
                    _m.health_stalls.inc(check=name)
                    self.logger.error("watchdog check unhealthy",
                                      check=name, reason=reason, **{
                                          k: v for k, v in details.items()
                                          if isinstance(v, (int, float, str))
                                      })
            elif flipped and prev is not None:
                self.logger.info("watchdog check recovered", check=name)
        _m.health_up.set(1.0 if all_ok else 0.0)
        _m.health_watchdog_ticks.inc()
        self._scan_slow_spans()
        return self.verdicts()

    def _scan_slow_spans(self) -> None:
        """Count spans past the SLO threshold since the last pass; the
        span_id watermark keeps each span counted at most once even
        though snapshot() does not drain the ring."""
        from tmtpu.libs import metrics as _m
        from tmtpu.libs import trace

        if self.slow_span_threshold_s <= 0:
            return
        high = self._max_span_id
        for sp in trace.snapshot():
            if sp.span_id <= self._max_span_id or sp.end_s is None:
                continue
            high = max(high, sp.span_id)
            if sp.duration_s > self.slow_span_threshold_s:
                _m.health_slow_spans.inc(span=sp.name)
        self._max_span_id = high

    # -- reading ------------------------------------------------------------

    def verdicts(self) -> Dict[str, Dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._verdicts.items()}

    def healthy(self) -> Tuple[bool, List[str]]:
        """(all checks pass, reasons for the ones that don't)."""
        with self._lock:
            reasons = [f"{name}: {v['reason']}"
                       for name, v in sorted(self._verdicts.items())
                       if not v["healthy"]]
        return not reasons, reasons

    def liveness(self) -> Tuple[bool, Dict]:
        """The /healthz payload: aggregate verdict + per-check detail."""
        ok, reasons = self.healthy()
        return ok, {"healthy": ok, "reasons": reasons,
                    "checks": self.verdicts()}


# --- built-in check factories ------------------------------------------------


def consensus_progress_check(cs, stall_timeout_s: float,
                             is_syncing: Optional[Callable[[], bool]] = None
                             ) -> CheckFn:
    """Unhealthy when HEIGHT has not advanced for ``stall_timeout_s``
    (and the node is not block/state syncing). Round/step churn does
    not reset the timer: a validator cut off from quorum keeps timing
    out into ever-higher rounds forever, and rounds without commits are
    the signature of a stalled consensus, not progress (a partitioned
    minority would otherwise report healthy indefinitely). The verdict
    names the stuck height/round/step and the timeline's last recorded
    event — the step that stalled."""
    from tmtpu.libs import timeline

    last = {"height": None, "t": time.monotonic()}

    def check() -> Tuple[bool, str, Dict]:
        rs = cs.round_state_nolock()
        now = time.monotonic()
        if rs.height != last["height"]:
            last["height"], last["t"] = rs.height, now
        if is_syncing is not None and is_syncing():
            last["t"] = now  # progress is the syncer's job right now
            return True, "", {"syncing": True}
        age = now - last["t"]
        details = {"height": rs.height, "round": rs.round,
                   "step": rs.step_name(), "stalled_for_s": round(age, 3),
                   "last_timeline_event": timeline.last_event()}
        if age > stall_timeout_s:
            return (False,
                    f"no height progress for {age:.1f}s at "
                    f"{rs.height_round_step()}", details)
        return True, "", details

    return check


def peer_count_check(num_peers: Callable[[], int],
                     min_peers: int) -> CheckFn:
    """Unhealthy when the switch holds fewer than ``min_peers`` peers."""

    def check() -> Tuple[bool, str, Dict]:
        n = num_peers()
        if n < min_peers:
            return (False, f"{n} peers connected, need >= {min_peers}",
                    {"peers": n, "min_peers": min_peers})
        return True, "", {"peers": n}

    return check


def mempool_drain_check(mempool, stall_timeout_s: float) -> CheckFn:
    """Unhealthy when a non-empty mempool has not shrunk for
    ``stall_timeout_s`` — txs are arriving but no block is clearing
    them (complements the consensus check: catches a chain that commits
    empty blocks while CheckTx output piles up)."""
    last = {"size": 0, "t": time.monotonic()}

    def check() -> Tuple[bool, str, Dict]:
        size = mempool.size()
        now = time.monotonic()
        if size < last["size"] or size == 0:
            last["t"] = now  # drained (or empty): timer resets
        last["size"] = size
        age = now - last["t"]
        if size > 0 and age > stall_timeout_s:
            return (False,
                    f"mempool stuck at {size} txs for {age:.1f}s",
                    {"size": size, "stalled_for_s": round(age, 3)})
        return True, "", {"size": size}

    return check


def tpu_backend_check(window_s: float, storm_threshold: int,
                      expect_device: bool = False) -> CheckFn:
    """Unhealthy on a CPU-fallback storm: more than ``storm_threshold``
    lanes landed on ``tendermint_crypto_cpu_fallback_total`` within the
    trailing ``window_s`` — the signature a dead TPU backend leaves
    while consensus limps along serially. With ``expect_device`` the
    probe gauge (``tendermint_crypto_tpu_backend_up``) going to 0 is
    unhealthy on its own."""
    from tmtpu.libs import metrics as _m

    samples: List[Tuple[float, float]] = []  # (t, cumulative fallback)

    def _fallback_total() -> float:
        return sum(_m.crypto_cpu_fallback.summary_series().values())

    def check() -> Tuple[bool, str, Dict]:
        now = time.monotonic()
        total = _fallback_total()
        samples.append((now, total))
        while samples and samples[0][0] < now - window_s:
            samples.pop(0)
        delta = total - samples[0][1]
        up = _m.crypto_tpu_backend_up.summary_series().get("")
        details = {"fallbacks_in_window": delta, "window_s": window_s,
                   "backend_up": up}
        if expect_device and up == 0.0:
            return (False, "tpu backend probe reports down "
                           "(crypto_tpu_backend_up=0)", details)
        if storm_threshold > 0 and delta > storm_threshold:
            return (False,
                    f"cpu fallback storm: {delta:.0f} fallback lanes in "
                    f"{window_s:.0f}s (threshold {storm_threshold})",
                    details)
        return True, "", details

    return check


def latency_slo_check(slo_ms: float, window_s: float = 30.0,
                      consecutive: int = 3) -> CheckFn:
    """Unhealthy when the rolling p99 submit→commit tx latency exceeds
    ``slo_ms`` for ``consecutive`` watchdog samples in a row. The p99 is
    computed from windowed DELTAS of the
    ``tendermint_tx_latency_submit_to_commit_seconds`` bucket counts
    (cumulative snapshots pruned past ``window_s``), so one historic
    latency spike ages out of the verdict instead of pinning it forever.
    Quiet windows (no commits carrying submit-stamped txs) are healthy:
    no traffic is not a latency breach. Registered only when
    ``[instr] latency_slo_ms`` > 0 (node/node.py)."""
    from tmtpu.libs import metrics as _m

    # (t, cumulative bucket counts incl. +Inf total)
    samples: List[Tuple[float, Tuple[int, ...]]] = []
    streak = {"n": 0}

    def check() -> Tuple[bool, str, Dict]:
        now = time.monotonic()
        counts = _m.tx_latency_submit_to_commit.bucket_counts()
        if not counts:
            # nothing observed yet: seed an all-zero baseline so the
            # FIRST real traffic after startup is judged against it
            # instead of waiting one extra tick for a second snapshot
            counts = (0,) * (len(_m.tx_latency_submit_to_commit.buckets)
                             + 1)
        samples.append((now, counts))
        while samples and samples[0][0] < now - window_s:
            samples.pop(0)
        details: Dict = {"slo_ms": slo_ms, "window_s": window_s,
                         "consecutive_needed": consecutive}
        if len(samples) < 2:
            details["observed_in_window"] = 0
            streak["n"] = 0
            _m.health_latency_p99_ms.set(0.0)
            return True, "", details
        first, last = samples[0][1], samples[-1][1]
        delta = [b - a for a, b in zip(first, last)]
        observed = delta[-1]
        details["observed_in_window"] = observed
        if observed <= 0:
            streak["n"] = 0
            _m.health_latency_p99_ms.set(0.0)
            return True, "", details
        p99_ms = _m.percentile_from_buckets(
            _m.tx_latency_submit_to_commit.buckets, delta, 0.99) * 1000.0
        _m.health_latency_p99_ms.set(round(p99_ms, 3))
        details["p99_ms"] = round(p99_ms, 3)
        if p99_ms > slo_ms:
            _m.health_latency_slo_breaches.inc()
            streak["n"] += 1
        else:
            streak["n"] = 0
        details["breach_streak"] = streak["n"]
        if streak["n"] >= consecutive:
            return (False,
                    f"p99 submit->commit {p99_ms:.1f}ms over SLO "
                    f"{slo_ms:.0f}ms for {streak['n']} samples",
                    details)
        return True, "", details

    return check


def validator_flap_check(window_s: float = 60.0,
                         threshold: int = 3) -> CheckFn:
    """Unhealthy when any tracked validator's participation state
    flip-flopped at least ``threshold`` times within the trailing
    ``window_s``. Flap counts come from the per-validator forensics
    ledger (libs/valstats.py): one flap is recorded at each height
    rollup where a validator's voted/missed state differs from the
    previous rollup, so a validator oscillating between present and
    absent — crash-looping, link-flapping, or being throttled — trips
    this check while a cleanly-down or cleanly-up validator does not.
    The reason names the flappiest validator so /healthz carries the
    attribution. Registered only when ``[instr] valstats`` is on and
    ``[health] validator_flap_threshold`` > 0 (node/node.py)."""
    from tmtpu.libs import valstats as _vs

    # (t, cumulative per-address flap counts)
    samples: List[Tuple[float, Dict[str, int]]] = []

    def check() -> Tuple[bool, str, Dict]:
        now = time.monotonic()
        counts = _vs.flap_counts()
        samples.append((now, dict(counts)))
        while samples and samples[0][0] < now - window_s:
            samples.pop(0)
        base = samples[0][1]
        worst_addr, worst_delta = "", 0
        for addr, total in counts.items():
            delta = total - base.get(addr, 0)
            if delta > worst_delta:
                worst_addr, worst_delta = addr, delta
        details: Dict = {"window_s": window_s, "threshold": threshold,
                         "flaps_in_window": worst_delta}
        if worst_addr:
            details["validator"] = worst_addr
        if worst_delta >= threshold:
            return (False,
                    f"validator {worst_addr} flapped {worst_delta} times "
                    f"in {window_s:.0f}s (threshold {threshold})",
                    details)
        return True, "", details

    return check


def breaker_check() -> CheckFn:
    """Unhealthy while any crypto circuit breaker sits OPEN — the node
    is alive but running degraded (CPU-serial verify), which an
    operator must see before the backoff window quietly retries.
    HALF_OPEN is healthy-with-detail: recovery probing in flight."""
    from tmtpu.libs import breaker as _bk

    def check() -> Tuple[bool, str, Dict]:
        snaps = _bk.snapshot_all()
        open_ = {n: s for n, s in snaps.items() if s["state"] == _bk.OPEN}
        details = {"breakers": snaps}
        if open_:
            perm = sorted(n for n, s in open_.items() if s["permanent"])
            reason = f"breaker open: {', '.join(sorted(open_))}"
            if perm:
                reason += f" (permanent: {', '.join(perm)})"
            return False, reason, details
        return True, "", details

    return check


def sidecar_check(window_s: float = 30.0,
                  fallback_threshold: int = 256) -> CheckFn:
    """For nodes running ``crypto_backend=sidecar``: unhealthy while the
    ``crypto.sidecar`` breaker sits OPEN (every batch is riding the
    in-process fallback — correct but without cross-process coalescing)
    or when sidecar fallback lanes exceed ``fallback_threshold`` within
    the trailing window while the breaker still thinks the daemon is
    fine. ``sidecar_client_up`` rides along in the details so /healthz
    names the dead connection."""
    from tmtpu.libs import breaker as _bk
    from tmtpu.libs import metrics as _m

    samples: List[Tuple[float, float]] = []  # (t, cumulative fallbacks)

    def _fallback_total() -> float:
        return sum(_m.sidecar_client_fallback.summary_series().values())

    def check() -> Tuple[bool, str, Dict]:
        from tmtpu.crypto.batch import SIDECAR_BREAKER_NAME

        now = time.monotonic()
        total = _fallback_total()
        samples.append((now, total))
        while samples and samples[0][0] < now - window_s:
            samples.pop(0)
        delta = total - samples[0][1]
        br = _bk.lookup(SIDECAR_BREAKER_NAME)
        state = br.state if br is not None else "unregistered"
        up = _m.sidecar_client_up.summary_series().get("")
        details = {"breaker_state": state, "client_up": up,
                   "fallbacks_in_window": delta, "window_s": window_s}
        if state == _bk.OPEN:
            return (False, "sidecar breaker open: batches riding the "
                           "in-process fallback", details)
        if fallback_threshold > 0 and delta > fallback_threshold:
            return (False,
                    f"sidecar fallback storm: {delta:.0f} lanes in "
                    f"{window_s:.0f}s (threshold {fallback_threshold})",
                    details)
        return True, "", details

    return check


def lightserve_check(snapshot_fn: Callable[[], Dict],
                     hit_rate_floor: float = 0.5,
                     min_lookups: int = 64,
                     backlog_ceiling: int = 4096,
                     window_s: float = 30.0) -> CheckFn:
    """For the lightserve daemon (tmtpu/lightserve): unhealthy when the
    verified-fact cache hit rate over the trailing window drops below
    ``hit_rate_floor`` — the serving tier has regressed from
    answer-from-cache to resolve-per-request and the coalescer is the
    only thing between the provider and a dispatch storm — or when the
    coalescer's session backlog (queued + inflight) exceeds
    ``backlog_ceiling``. The hit-rate verdict waits for ``min_lookups``
    lookups in the window so a cold or idle daemon is not flagged;
    expired refusals count as non-hits (an expiring-everywhere cache IS
    a serving regression, operators should see it).

    ``snapshot_fn`` supplies cumulative counters ``{"cache_hits",
    "cache_misses", "cache_expired", "backlog"}`` — the daemon passes
    ``LightserveServer.health_snapshot``."""

    # (t, hits, misses+expired)
    samples: List[Tuple[float, float, float]] = []

    def check() -> Tuple[bool, str, Dict]:
        now = time.monotonic()
        snap = snapshot_fn()
        hits = float(snap.get("cache_hits", 0))
        non_hits = float(snap.get("cache_misses", 0) +
                         snap.get("cache_expired", 0))
        backlog = int(snap.get("backlog", 0))
        samples.append((now, hits, non_hits))
        while samples and samples[0][0] < now - window_s:
            samples.pop(0)
        d_hits = hits - samples[0][1]
        d_non = non_hits - samples[0][2]
        lookups = d_hits + d_non
        hit_rate = (d_hits / lookups) if lookups > 0 else 1.0
        details: Dict = {"window_s": window_s,
                         "lookups_in_window": lookups,
                         "hit_rate": round(hit_rate, 4),
                         "hit_rate_floor": hit_rate_floor,
                         "backlog": backlog,
                         "backlog_ceiling": backlog_ceiling}
        if backlog_ceiling > 0 and backlog > backlog_ceiling:
            return (False,
                    f"lightserve session backlog {backlog} over ceiling "
                    f"{backlog_ceiling}", details)
        if lookups >= min_lookups and hit_rate < hit_rate_floor:
            return (False,
                    f"lightserve cache hit rate {hit_rate:.2f} below "
                    f"floor {hit_rate_floor:.2f} over {window_s:.0f}s "
                    f"({lookups:.0f} lookups)", details)
        return True, "", details

    return check


def sync_status_check(is_block_syncing: Callable[[], bool],
                      is_state_syncing: Callable[[], bool]) -> CheckFn:
    """Always healthy — surfaces blocksync/statesync progress so
    ``health_detail`` aggregates it and /readyz can gate on it."""

    def check() -> Tuple[bool, str, Dict]:
        bs, ss = bool(is_block_syncing()), bool(is_state_syncing())
        return True, "", {"block_sync": bs, "state_sync": ss,
                          "caught_up": not (bs or ss)}

    return check

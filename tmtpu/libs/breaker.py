"""Circuit breaker for the TPU crypto hot path (and anything else that
must degrade *and recover*).

The north-star ``crypto.backend=tpu`` deployment puts a hardware sidecar
on the consensus hot path (VoteSet.addVote, VerifyCommit, light
verification). Before this module the failure policy was a pair of
one-shot latches: ``crypto/batch._tpu_usable`` probed once and cached
the answer forever (one transient startup failure pinned the node to
CPU for its whole life), and the Pallas ``_kernel_broken`` latches in
tpu/sr_verify.py / k1_verify.py never un-latched. A breaker replaces
both with the classic three-state machine:

    CLOSED ──(failure_threshold consecutive failures)──▶ OPEN
    OPEN ──(backoff elapsed)──▶ HALF_OPEN
    HALF_OPEN ──(half_open_probes consecutive successes)──▶ CLOSED
    HALF_OPEN ──(any failure)──▶ OPEN (backoff doubled, jittered)

While OPEN, ``allow()`` answers False and callers take their fallback
path (CPU serial verify) without touching the device. After the current
backoff window a single caller is let through as a *probe batch*
(HALF_OPEN); its outcome decides whether the device is trusted again.
Backoff grows exponentially from ``backoff_base_s`` to
``backoff_max_s`` with deterministic seeded jitter (±``jitter_ratio``)
so a fleet of validators does not re-probe a shared wedged device
runtime in lockstep.

Every transition lands in the ``tendermint_crypto_breaker_*`` metric
set, the per-height timeline journal (event ``crypto.breaker``), and
the structured log — a node that degraded and healed leaves a complete
audit trail (docs/RESILIENCE.md).

``call_with_deadline`` is the companion primitive: a hung ``jax``
dispatch (a wedged device runtime, a chip another process took) never
returns, so breaker accounting alone cannot save the *current* batch.
Running the device call on a worker thread with a hard join timeout
turns "hung forever" into an exception the caller converts into a
CPU-verified result.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Callable, Dict, List, Optional

from tmtpu.libs import trace

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"

# numeric encoding for the tendermint_crypto_breaker_state gauge
STATE_CODES = {CLOSED: 0.0, OPEN: 1.0, HALF_OPEN: 2.0}


class BreakerOpen(Exception):
    """Raised by ``guard()`` when the breaker is open (callers that use
    ``allow()`` directly never see it)."""


class DeadlineExceeded(Exception):
    """A guarded call did not return within its per-batch deadline."""


def call_with_deadline(fn: Callable, timeout_s: float, *args, **kwargs):
    """Run ``fn(*args, **kwargs)`` on a daemon worker thread and join
    with a hard timeout. Returns the result, re-raises the function's
    exception, or raises DeadlineExceeded if the call is still running
    at the deadline (the worker is abandoned — it holds no locks the
    caller needs, and a later completion is discarded). The worker goes
    on under the caller's open span and trace context (libs/trace
    ``handoff``/``resume``), so what ``fn`` records names its cause.

    ``timeout_s <= 0`` means no deadline: call inline (no thread hop).
    """
    if timeout_s <= 0:
        return fn(*args, **kwargs)
    box: Dict = {}
    done = threading.Event()
    caller = trace.handoff()

    def run():
        try:
            with trace.resume(caller):
                box["result"] = fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=run, name="deadline-call", daemon=True)
    t.start()
    if not done.wait(timeout_s):
        raise DeadlineExceeded(
            f"call did not return within {timeout_s:.3f}s")
    if "error" in box:
        raise box["error"]
    return box.get("result")


class CircuitBreaker:
    """Thread-safe closed → open → half-open breaker.

    All timing goes through the injectable ``clock`` (monotonic
    seconds) and all jitter through a seeded ``random.Random`` so tests
    are deterministic. ``trip_permanent()`` pins the breaker open with
    an infinite backoff — the policy for deterministic Pallas
    compile/lowering rejections, where re-probing pays full
    trace+lowering cost per batch for nothing.
    """

    def __init__(self, name: str,
                 failure_threshold: int = 3,
                 backoff_base_s: float = 1.0,
                 backoff_max_s: float = 60.0,
                 half_open_probes: int = 2,
                 jitter_ratio: float = 0.1,
                 clock: Callable[[], float] = time.monotonic,
                 seed: Optional[int] = None,
                 logger=None):
        self.name = name
        self.failure_threshold = max(1, int(failure_threshold))
        self.backoff_base_s = max(0.0, float(backoff_base_s))
        self.backoff_max_s = max(self.backoff_base_s, float(backoff_max_s))
        self.half_open_probes = max(1, int(half_open_probes))
        self.jitter_ratio = max(0.0, float(jitter_ratio))
        self._clock = clock
        # seeded per breaker name by default: deterministic for tests,
        # de-correlated across the breakers of one process
        self._rng = random.Random(seed if seed is not None
                                  else hash(name) & 0xFFFFFFFF)
        self._lock = threading.Lock()
        self._state = CLOSED
        self._failures = 0          # consecutive, in CLOSED
        self._probe_successes = 0   # consecutive, in HALF_OPEN
        self._open_count = 0        # times opened (drives backoff exp)
        self._open_until = 0.0
        self._permanent = False
        self._last_error: str = ""
        self._transitions: List[Dict] = []  # bounded audit trail
        self.logger = logger
        self._publish_state()

    # -- state machine ------------------------------------------------------

    def allow(self) -> bool:
        """May the caller attempt the protected operation right now?

        CLOSED: yes. OPEN: no, until the backoff elapses — the first
        caller past the deadline flips the breaker to HALF_OPEN and
        becomes the probe. HALF_OPEN: yes (probe batches flow until an
        outcome closes or re-opens the breaker).
        """
        with self._lock:
            if self._state == CLOSED:
                return True
            if self._state == OPEN:
                if self._permanent or self._clock() < self._open_until:
                    return False
                self._transition(HALF_OPEN, "backoff elapsed")
                return True
            return True  # HALF_OPEN

    def guard(self) -> None:
        """``allow()`` as an exception: raises BreakerOpen when closed
        off. Convenience for call sites structured as try/except."""
        if not self.allow():
            raise BreakerOpen(f"breaker {self.name!r} is open")

    def record_success(self) -> None:
        with self._lock:
            if self._state == HALF_OPEN:
                self._probe_successes += 1
                if self._probe_successes >= self.half_open_probes:
                    self._open_count = 0
                    self._transition(
                        CLOSED,
                        f"{self._probe_successes} probe successes")
            self._failures = 0
            if self._state == CLOSED:
                self._last_error = ""

    def record_failure(self, err: Optional[BaseException] = None) -> None:
        from tmtpu.libs import metrics as _m

        _m.crypto_breaker_failures.inc(breaker=self.name)
        with self._lock:
            if err is not None:
                self._last_error = f"{type(err).__name__}: {err}"
            if self._state == HALF_OPEN:
                self._open(f"probe failed: {self._last_error}")
            elif self._state == CLOSED:
                self._failures += 1
                if self._failures >= self.failure_threshold:
                    self._open(
                        f"{self._failures} consecutive failures: "
                        f"{self._last_error}")
            # already OPEN: a straggler failure changes nothing

    def trip_permanent(self, reason: str) -> None:
        """Open with no re-probe — deterministic, non-transient faults
        (Pallas compile rejection). ``reset()`` is the only way back."""
        with self._lock:
            self._permanent = True
            self._last_error = reason
            if self._state != OPEN:
                self._transition(OPEN, f"permanent: {reason}")

    def reset(self) -> None:
        """Force CLOSED and forget history (tests, operator action)."""
        with self._lock:
            self._permanent = False
            self._failures = 0
            self._probe_successes = 0
            self._open_count = 0
            self._open_until = 0.0
            self._last_error = ""
            if self._state != CLOSED:
                self._transition(CLOSED, "reset")
            else:
                self._publish_state()

    def _open(self, reason: str) -> None:
        """Locked. Enter OPEN with the next exponential-backoff window."""
        self._open_count += 1
        backoff = min(self.backoff_max_s,
                      self.backoff_base_s * (2 ** (self._open_count - 1)))
        if self.jitter_ratio > 0:
            backoff *= 1.0 + self._rng.uniform(-self.jitter_ratio,
                                               self.jitter_ratio)
        self._open_until = self._clock() + backoff
        self._transition(OPEN, reason)

    def _transition(self, to: str, reason: str) -> None:
        """Locked. Move to ``to`` and publish metrics/timeline/log."""
        frm = self._state
        self._state = to
        if to == HALF_OPEN:
            self._probe_successes = 0
        if to == CLOSED:
            self._failures = 0
        ev = {"from": frm, "to": to, "reason": reason, "t": time.time()}
        self._transitions.append(ev)
        del self._transitions[:-32]
        self._publish_state()
        from tmtpu.libs import metrics as _m
        from tmtpu.libs import timeline as _tl

        _m.crypto_breaker_transitions.inc(
            breaker=self.name, **{"from": frm, "to": to})
        _tl.record_breaker(breaker=self.name, **{"from": frm, "to": to},
                           reason=reason)
        logger = self.logger
        if logger is None:
            from tmtpu.libs import log

            logger = log.default_logger().with_fields(module="breaker")
            self.logger = logger
        level = logger.error if to == OPEN else logger.info
        try:
            level("breaker transition", breaker=self.name,
                  **{"from": frm, "to": to}, reason=reason)
        except Exception:  # noqa: BLE001 — telemetry must never raise
            pass

    def _publish_state(self) -> None:
        from tmtpu.libs import metrics as _m

        _m.crypto_breaker_state.set(STATE_CODES[self._state],
                                    breaker=self.name)

    # -- reading ------------------------------------------------------------

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def snapshot(self) -> Dict:
        """The health_detail / watchdog view of one breaker."""
        with self._lock:
            now = self._clock()
            return {
                "state": self._state,
                "failures": self._failures,
                "open_count": self._open_count,
                "permanent": self._permanent,
                "last_error": self._last_error,
                "reopen_in_s": (round(max(0.0, self._open_until - now), 3)
                                if self._state == OPEN and not self._permanent
                                else 0.0),
                "transitions": [dict(t) for t in self._transitions[-8:]],
            }


# --- process-global registry -------------------------------------------------
#
# Breakers are per-resource singletons (one for the TPU crypto backend,
# one per Pallas kernel family); the registry gives the watchdog and
# health_detail one place to enumerate them.

_registry: Dict[str, CircuitBreaker] = {}
_registry_lock = threading.Lock()


def get(name: str, **kwargs) -> CircuitBreaker:
    """The breaker registered under ``name``, created on first use.
    kwargs apply only at creation."""
    with _registry_lock:
        br = _registry.get(name)
        if br is None:
            br = CircuitBreaker(name, **kwargs)
            _registry[name] = br
        return br


def configure(name: str, **kwargs) -> CircuitBreaker:
    """Create-or-reconfigure: unlike ``get``, an existing breaker's
    thresholds/backoff are updated in place (config reload, node
    wiring applying config/config.py knobs after import-time get())."""
    br = get(name)
    with br._lock:
        if "failure_threshold" in kwargs:
            br.failure_threshold = max(1, int(kwargs["failure_threshold"]))
        if "backoff_base_s" in kwargs:
            br.backoff_base_s = max(0.0, float(kwargs["backoff_base_s"]))
        if "backoff_max_s" in kwargs:
            br.backoff_max_s = max(br.backoff_base_s,
                                   float(kwargs["backoff_max_s"]))
        if "half_open_probes" in kwargs:
            br.half_open_probes = max(1, int(kwargs["half_open_probes"]))
        if "jitter_ratio" in kwargs:
            br.jitter_ratio = max(0.0, float(kwargs["jitter_ratio"]))
    return br


def lookup(name: str) -> Optional[CircuitBreaker]:
    with _registry_lock:
        return _registry.get(name)


def snapshot_all() -> Dict[str, Dict]:
    with _registry_lock:
        breakers = list(_registry.items())
    return {name: br.snapshot() for name, br in breakers}


def reset_all() -> None:
    """Testing hook: force every registered breaker CLOSED."""
    with _registry_lock:
        breakers = list(_registry.values())
    for br in breakers:
        br.reset()

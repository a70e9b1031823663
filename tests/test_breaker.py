"""libs/breaker.py — the circuit breaker that replaced the one-shot
``_tpu_usable`` / ``_kernel_broken`` latches (docs/RESILIENCE.md).

Everything here drives the state machine through an injectable fake
clock and ``jitter_ratio=0`` so transitions are deterministic; the
registry tests use unique names so the process-global view stays
uncontaminated across test ordering.
"""

import threading
import time

import pytest

from tmtpu.libs import breaker as bk
from tmtpu.libs import metrics as _m


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def mk(name="test.unit", **kw):
    kw.setdefault("failure_threshold", 3)
    kw.setdefault("backoff_base_s", 10.0)
    kw.setdefault("backoff_max_s", 100.0)
    kw.setdefault("half_open_probes", 2)
    kw.setdefault("jitter_ratio", 0.0)
    clock = kw.pop("clock", None) or FakeClock()
    return bk.CircuitBreaker(name, clock=clock, **kw), clock


def test_starts_closed_and_allows():
    br, _ = mk()
    assert br.state == bk.CLOSED
    assert br.allow()
    br.guard()  # no raise


def test_failures_below_threshold_stay_closed():
    br, _ = mk()
    br.record_failure(RuntimeError("x"))
    br.record_failure(RuntimeError("x"))
    assert br.state == bk.CLOSED
    assert br.allow()
    # a success resets the consecutive count: two more failures are
    # again below threshold
    br.record_success()
    br.record_failure(RuntimeError("x"))
    br.record_failure(RuntimeError("x"))
    assert br.state == bk.CLOSED


def test_threshold_failures_open_and_backoff_gates():
    br, clock = mk()
    for _ in range(3):
        br.record_failure(RuntimeError("device fell over"))
    assert br.state == bk.OPEN
    assert not br.allow()
    with pytest.raises(bk.BreakerOpen):
        br.guard()
    snap = br.snapshot()
    assert snap["state"] == bk.OPEN
    assert 0 < snap["reopen_in_s"] <= 10.0
    assert "device fell over" in snap["last_error"]
    # still inside the backoff window
    clock.advance(9.0)
    assert not br.allow()


def test_half_open_probe_closes_after_successes():
    br, clock = mk()
    for _ in range(3):
        br.record_failure(RuntimeError("x"))
    clock.advance(10.5)
    # first caller past the deadline becomes the probe
    assert br.allow()
    assert br.state == bk.HALF_OPEN
    br.record_success()
    assert br.state == bk.HALF_OPEN  # half_open_probes=2
    br.record_success()
    assert br.state == bk.CLOSED
    # recovery resets the backoff exponent: a fresh trip gets base backoff
    for _ in range(3):
        br.record_failure(RuntimeError("x"))
    assert 0 < br.snapshot()["reopen_in_s"] <= 10.0


def test_half_open_failure_reopens_with_doubled_backoff():
    br, clock = mk()
    for _ in range(3):
        br.record_failure(RuntimeError("x"))
    assert br.snapshot()["reopen_in_s"] == 10.0
    clock.advance(10.5)
    assert br.allow()  # half-open probe
    br.record_failure(RuntimeError("probe died"))
    assert br.state == bk.OPEN
    # second open: backoff 10 * 2^1 = 20 (jitter off)
    assert br.snapshot()["reopen_in_s"] == 20.0
    clock.advance(20.5)
    assert br.allow()
    br.record_failure(RuntimeError("again"))
    assert br.snapshot()["reopen_in_s"] == 40.0


def test_backoff_capped_at_max():
    br, clock = mk(backoff_base_s=10.0, backoff_max_s=25.0)
    for _ in range(3):
        br.record_failure(RuntimeError("x"))
    for _ in range(5):  # keep failing every probe
        clock.advance(30.0)
        assert br.allow()
        br.record_failure(RuntimeError("x"))
    assert br.snapshot()["reopen_in_s"] <= 25.0


def test_trip_permanent_never_reprobes():
    br, clock = mk()
    br.trip_permanent("Mosaic lowering rejected the kernel")
    assert br.state == bk.OPEN
    clock.advance(1e9)
    assert not br.allow()
    snap = br.snapshot()
    assert snap["permanent"]
    assert snap["reopen_in_s"] == 0.0
    # reset is the only way back
    br.reset()
    assert br.state == bk.CLOSED
    assert br.allow()
    assert not br.snapshot()["permanent"]


def test_jitter_is_seeded_and_deterministic():
    def trip_and_window(seed):
        br, _ = mk("test.jitter", jitter_ratio=0.2, seed=seed)
        for _ in range(3):
            br.record_failure(RuntimeError("x"))
        return br.snapshot()["reopen_in_s"]

    a, b = trip_and_window(7), trip_and_window(7)
    assert a == b
    assert 8.0 <= a <= 12.0  # 10s base ± 20%
    assert trip_and_window(8) != a


def test_transitions_audit_trail_and_state_gauge():
    br, clock = mk("test.audit")
    for _ in range(3):
        br.record_failure(RuntimeError("x"))
    clock.advance(10.5)
    br.allow()
    br.record_success()
    br.record_success()
    hops = [(t["from"], t["to"]) for t in br.snapshot()["transitions"]]
    assert hops == [(bk.CLOSED, bk.OPEN), (bk.OPEN, bk.HALF_OPEN),
                    (bk.HALF_OPEN, bk.CLOSED)]
    series = _m.crypto_breaker_state.summary_series()
    assert series["breaker=test.audit"] == 0.0  # closed again
    trans = _m.crypto_breaker_transitions.summary_series()
    assert trans["breaker=test.audit,from=closed,to=open"] >= 1


def test_thread_safety_under_concurrent_hammering():
    br, _ = mk("test.threads", failure_threshold=5)
    stop = threading.Event()
    errors = []

    def hammer():
        try:
            while not stop.is_set():
                if br.allow():
                    br.record_success()
                br.record_failure(RuntimeError("x"))
                br.snapshot()
        except Exception as e:  # noqa: BLE001 — the assertion target
            errors.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    stop.set()
    for t in threads:
        t.join()
    assert not errors


# --- registry ----------------------------------------------------------------


def test_registry_get_is_singleton_and_configure_updates():
    a = bk.get("test.registry.one", failure_threshold=7)
    b = bk.get("test.registry.one", failure_threshold=99)  # kwargs ignored
    assert a is b
    assert a.failure_threshold == 7
    bk.configure("test.registry.one", failure_threshold=2,
                 backoff_base_s=1.0, backoff_max_s=4.0,
                 half_open_probes=1, jitter_ratio=0.0)
    assert a.failure_threshold == 2
    assert a.backoff_max_s == 4.0
    assert bk.lookup("test.registry.one") is a
    assert bk.lookup("test.registry.never-created") is None


def test_snapshot_all_and_reset_all():
    br = bk.get("test.registry.two")
    br.trip_permanent("wedged")
    snaps = bk.snapshot_all()
    assert snaps["test.registry.two"]["state"] == bk.OPEN
    assert snaps["test.registry.two"]["permanent"]
    bk.reset_all()
    assert bk.snapshot_all()["test.registry.two"]["state"] == bk.CLOSED


# --- call_with_deadline ------------------------------------------------------


def test_deadline_returns_result_and_reraises():
    assert bk.call_with_deadline(lambda: 42, 5.0) == 42
    with pytest.raises(KeyError):
        bk.call_with_deadline(lambda: (_ for _ in ()).throw(KeyError("k")),
                              5.0)


def test_deadline_hung_call_raises():
    hang = threading.Event()
    t0 = time.monotonic()
    with pytest.raises(bk.DeadlineExceeded):
        bk.call_with_deadline(lambda: hang.wait(30.0), 0.1)
    assert time.monotonic() - t0 < 5.0
    hang.set()  # release the abandoned worker


def test_deadline_zero_calls_inline():
    # no thread hop: the call runs on THIS thread
    ident = bk.call_with_deadline(threading.get_ident, 0)
    assert ident == threading.get_ident()


# --- verify-once cache interaction -------------------------------------------


def test_half_open_not_advanced_by_sigcache_hits(monkeypatch):
    """Verify-once regression (crypto/sigcache.py): cached lanes never
    reach the device dispatch, so a flush served entirely from the
    verified-signature cache must NOT count as a breaker success — only
    a REAL device round-trip may advance half_open → closed. A wedged
    device would otherwise be declared healthy on the strength of
    verifications it never ran."""
    from tmtpu.config.config import CryptoConfig
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.crypto import ed25519 as ed
    from tmtpu.crypto import sigcache
    from tmtpu.tpu import dispatch

    br = bk.get(crypto_batch.BREAKER_NAME)
    clock = FakeClock()
    monkeypatch.setattr(br, "_clock", clock)
    bk.configure(crypto_batch.BREAKER_NAME, failure_threshold=2,
                 backoff_base_s=10.0, backoff_max_s=60.0,
                 half_open_probes=1, jitter_ratio=0.0)
    br.reset()
    monkeypatch.setattr(crypto_batch, "_TPU_MIN_BATCH", 1)
    monkeypatch.setattr(crypto_batch, "_tpu_usable", True)

    priv = ed.gen_priv_key_from_secret(b"half-open-cache")
    pk = priv.pub_key()
    msg = b"cached round trip"
    sig = priv.sign(msg)

    device_calls = []

    def fake_device_verify(curve, pks, msgs, sigs, powers=None,
                           min_lanes=0):
        device_calls.append(len(pks))
        return [True] * len(pks), None

    monkeypatch.setattr(dispatch, "device_verify", fake_device_verify)

    def flush(m, s):
        bv = crypto_batch.TPUBatchVerifier()
        bv.add(pk, m, s)
        return bv.verify()

    try:
        # prime the cache with a real (faked-device) verify while CLOSED
        all_ok, _ = flush(msg, sig)
        assert all_ok and device_calls == [1]
        assert sigcache.DEFAULT.check("ed25519", pk.bytes(), msg, sig)

        # trip the breaker, advance into the half-open window
        br.record_failure(RuntimeError("device fell over"))
        br.record_failure(RuntimeError("device fell over"))
        assert br.state == bk.OPEN
        clock.advance(11.0)

        # a fully cache-served flush: zero dispatches, and the breaker
        # must NOT close on the back of it
        all_ok, mask = flush(msg, sig)
        assert all_ok and mask == [True]
        assert device_calls == [1], "cache hit must not touch the device"
        assert br.state != bk.CLOSED, \
            "cache hits must not advance half_open -> closed"

        # a genuinely new signature forces a real half-open probe
        # round-trip — THAT closes the breaker
        msg2 = b"fresh round trip"
        sig2 = priv.sign(msg2)
        all_ok, _ = flush(msg2, sig2)
        assert all_ok and device_calls == [1, 1]
        assert br.state == bk.CLOSED
    finally:
        br.reset()
        crypto_batch.configure(CryptoConfig())

"""Self-healing crypto backend — the ISSUE 3 acceptance scenarios.

Chaos here means *scripted* chaos: libs/faultinject plans drive the real
injection sites compiled into the TPU verify entry points, and the
``crypto.tpu`` breaker (libs/breaker.py) must (1) open within its
failure threshold, (2) keep every flush returning an exact CPU-verified
mask while open, (3) half-open after backoff and close on recovery —
with the whole sequence recorded in the breaker metric set and the
per-height timeline journal. The hung-device test proves the per-batch
deadline turns "dispatch never returns" into a CPU-verified result.

The device function is monkeypatched with a fake that still fires the
real ``tpu.ed25519.batch`` site — the sr25519/secp256k1 scenarios go
through the REAL ``dispatch.device_verify`` (a row's site fires before
any jax work, so no XLA compile in tier-1).
"""

import hashlib
import threading
import time

import pytest

from tmtpu.crypto import batch as crypto_batch
from tmtpu.crypto import ed25519 as ed
from tmtpu.libs import breaker as _bk
from tmtpu.libs import faultinject
from tmtpu.libs import metrics as _m
from tmtpu.libs import timeline as _tl
from tmtpu.tpu import dispatch

pytestmark = pytest.mark.chaos

BR = crypto_batch.BREAKER_NAME


class FakeClock:
    def __init__(self, t=5000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _series(metric):
    return dict(metric.summary_series())


def _ed_items(n, bad=()):
    items = []
    for i in range(n):
        priv = ed.gen_priv_key_from_secret(b"chaos-ed-%d" % i)
        msg = b"chaos msg %d" % i
        sig = priv.sign(msg)
        if i in bad:
            flip = bytearray(sig)
            flip[0] ^= 0xFF
            sig = bytes(flip)
        items.append((priv.pub_key(), msg, sig))
    return items


@pytest.fixture
def breaker_env(monkeypatch):
    """crypto.tpu breaker on a fake clock with fast, jitter-free
    thresholds; device path forced on; faultinject clean. Teardown
    restores the config/config.py CryptoConfig defaults."""
    br = _bk.get(BR)
    clock = FakeClock()
    monkeypatch.setattr(br, "_clock", clock)
    _bk.configure(BR, failure_threshold=2, backoff_base_s=10.0,
                  backoff_max_s=60.0, half_open_probes=1, jitter_ratio=0.0)
    br.reset()
    monkeypatch.setattr(crypto_batch, "_TPU_MIN_BATCH", 1)
    monkeypatch.setattr(crypto_batch, "_tpu_usable", True)
    # these scenarios re-flush IDENTICAL deterministic items and assert
    # exact device-call / fallback-lane counts — the verify-once cache
    # would legitimately absorb the repeats, so switch it off here
    # (breaker behavior is orthogonal; test_breaker.py covers the
    # cache-hits-don't-close-the-breaker interaction)
    from tmtpu.crypto import sigcache

    sigcache.DEFAULT.set_enabled(False)
    faultinject.reset()
    yield br, clock
    faultinject.reset()
    br.reset()
    from tmtpu.config.config import CryptoConfig

    crypto_batch.configure(CryptoConfig())


def _flush(items):
    bv = crypto_batch.TPUBatchVerifier()
    for pk, msg, sig in items:
        bv.add(pk, msg, sig)
    return bv.verify()


def test_breaker_opens_falls_back_half_opens_and_closes(monkeypatch,
                                                        breaker_env):
    """THE acceptance sequence: injected device errors trip the breaker
    at its threshold; flushes during the outage are CPU-exact; after
    backoff one probe batch closes it — metrics + timeline record it."""
    br, clock = breaker_env
    _tl.DEFAULT.clear()
    _tl.record(7, "consensus.enter_new_round")

    site = dispatch.CURVES["ed25519"].fault
    device_calls = []

    def fake_device_verify(curve, pks, msgs, sigs, powers=None,
                           min_lanes=0):
        device_calls.append(len(pks))
        faultinject.fire(site)
        return [True] * len(pks), None

    monkeypatch.setattr(dispatch, "device_verify", fake_device_verify)
    faultinject.script("tpu.ed25519.batch", faultinject.ERROR, count=2)
    fb0 = _series(_m.crypto_cpu_fallback)

    # flush 1: first injected device error — serial fallback, still CLOSED
    all_ok, mask = _flush(_ed_items(4))
    assert all_ok and mask == [True] * 4
    assert br.state == _bk.CLOSED

    # flush 2: second consecutive error hits the threshold — OPEN; the
    # fallback mask is still exact (lane 2 carries a corrupt signature)
    all_ok, mask = _flush(_ed_items(4, bad={2}))
    assert not all_ok and mask == [True, True, False, True]
    assert br.state == _bk.OPEN
    assert _series(_m.crypto_breaker_state)["breaker=crypto.tpu"] == 1.0

    # flush 3: open breaker short-circuits — the device is not touched
    n_calls = len(device_calls)
    all_ok, mask = _flush(_ed_items(4))
    assert all_ok and mask == [True] * 4
    assert len(device_calls) == n_calls

    # backoff elapses; the plan is exhausted (site healed), so the
    # half-open probe batch succeeds and the breaker closes
    clock.advance(10.5)
    all_ok, mask = _flush(_ed_items(4))
    assert all_ok and mask == [True] * 4
    assert br.state == _bk.CLOSED
    assert len(device_calls) == n_calls + 1
    assert _series(_m.crypto_breaker_state)["breaker=crypto.tpu"] == 0.0

    # every fallback lane was counted with its reason
    fb1 = _series(_m.crypto_cpu_fallback)

    def delta(key):
        return fb1.get(key, 0) - fb0.get(key, 0)

    assert delta("curve=ed25519,reason=device-error") == 8
    assert delta("curve=ed25519,reason=breaker-open") == 4

    # the timeline journal at the in-flight height has the full arc
    evs = [e for rec in _tl.snapshot(height=7) for e in rec["events"]
           if e["event"] == _tl.EVENT_BREAKER
           and e.get("breaker") == "crypto.tpu"]
    hops = [(e["from"], e["to"]) for e in evs]
    assert hops == [("closed", "open"), ("open", "half_open"),
                    ("half_open", "closed")]
    trans = _series(_m.crypto_breaker_transitions)
    for frm, to in hops:
        assert trans[f"breaker=crypto.tpu,from={frm},to={to}"] >= 1


def test_hung_device_returns_cpu_result_within_deadline(monkeypatch,
                                                        breaker_env):
    """A dispatch that never returns must NOT stall the flush: the
    per-batch deadline abandons it and the lanes re-verify serially,
    with the hang counted against the breaker."""
    br, _clock = breaker_env
    _bk.configure(BR, failure_threshold=10)  # a hang alone must not open
    monkeypatch.setenv("TMTPU_TPU_BATCH_DEADLINE", "0.2")
    hang = threading.Event()

    def hung_device_verify(curve, pks, msgs, sigs, powers=None,
                           min_lanes=0):
        hang.wait(30.0)
        return [True] * len(pks), None

    monkeypatch.setattr(dispatch, "device_verify", hung_device_verify)
    d0 = _series(_m.crypto_batch_deadline_exceeded)
    fb0 = _series(_m.crypto_cpu_fallback)
    t0 = time.monotonic()
    all_ok, mask = _flush(_ed_items(4, bad={1}))
    dt = time.monotonic() - t0
    hang.set()  # release the abandoned worker thread
    assert dt < 10.0, f"flush stalled {dt:.1f}s behind a hung dispatch"
    assert not all_ok and mask == [True, False, True, True]
    d1 = _series(_m.crypto_batch_deadline_exceeded)
    assert d1.get("curve=ed25519", 0) - d0.get("curve=ed25519", 0) == 1
    fb1 = _series(_m.crypto_cpu_fallback)
    assert (fb1.get("curve=ed25519,reason=deadline", 0)
            - fb0.get("curve=ed25519,reason=deadline", 0)) == 4
    assert br.state == _bk.CLOSED
    assert br.snapshot()["failures"] == 1


def test_sr_and_k1_sites_inject_at_the_real_entry(breaker_env, monkeypatch):
    """No monkeypatched device fns here: scripted errors on the
    ``tpu.sr25519.batch`` / ``tpu.secp256k1.batch`` sites raise inside
    the REAL dispatch.device_verify (before any jax work), and
    the per-curve fallback re-verifies exactly those lanes."""
    from tmtpu.crypto import sr25519 as sr

    br, _clock = breaker_env
    _bk.configure(BR, failure_threshold=10)

    items = []
    for i in range(3):
        priv = sr.gen_priv_key_from_secret(b"chaos-sr-%d" % i)
        msg = b"sr msg %d" % i
        items.append((priv.pub_key(), msg, priv.sign(msg)))

    faultinject.script("tpu.sr25519.batch", faultinject.ERROR, count=1)
    fb0 = _series(_m.crypto_cpu_fallback)
    all_ok, mask = _flush(items)
    assert all_ok and mask == [True] * 3
    assert br.snapshot()["failures"] == 1
    fb1 = _series(_m.crypto_cpu_fallback)
    assert (fb1.get("curve=sr25519,reason=device-error", 0)
            - fb0.get("curve=sr25519,reason=device-error", 0)) == 3
    inj = _series(_m.fault_injected)
    assert inj.get("site=tpu.sr25519.batch,mode=error", 0) >= 1


def test_k1_site_injects_at_the_real_entry(breaker_env, monkeypatch):
    """Same scenario over the table's secp256k1 row (the
    secp256k1 curve module needs the optional `cryptography` package —
    same gate as test_replay.py)."""
    pytest.importorskip("cryptography")
    from tmtpu.crypto import secp256k1 as k1

    br, _clock = breaker_env
    _bk.configure(BR, failure_threshold=10)

    items = []
    for i in range(3):
        seed = hashlib.sha256(b"chaos-k1-%d" % i).digest()
        priv = k1.PrivKeySecp256k1(
            (int.from_bytes(seed, "big") % (k1.N - 1) + 1)
            .to_bytes(32, "big"))
        msg = b"k1 msg %d" % i
        items.append((priv.pub_key(), msg, priv.sign(msg)))

    faultinject.script("tpu.secp256k1.batch", faultinject.ERROR, count=1)
    fb0 = _series(_m.crypto_cpu_fallback)
    all_ok, mask = _flush(items)
    assert all_ok and mask == [True] * 3
    assert br.snapshot()["failures"] == 1
    fb1 = _series(_m.crypto_cpu_fallback)
    assert (fb1.get("curve=secp256k1,reason=device-error", 0)
            - fb0.get("curve=secp256k1,reason=device-error", 0)) == 3
    inj = _series(_m.fault_injected)
    assert inj.get("site=tpu.secp256k1.batch,mode=error", 0) >= 1


def test_auto_backend_respects_open_breaker(breaker_env, monkeypatch):
    """``auto`` selection consults the breaker BEFORE probing: while
    open it hands out CPU verifiers without touching jax; once reset
    (with the success memo set) the TPU verifier comes back."""
    br, _clock = breaker_env
    monkeypatch.setattr(crypto_batch, "_tpu_usable", None)
    br.record_failure(RuntimeError("probe down"))
    br.record_failure(RuntimeError("probe down"))
    assert br.state == _bk.OPEN
    assert isinstance(crypto_batch.new_batch_verifier("auto"),
                      crypto_batch.CPUBatchVerifier)
    br.reset()
    monkeypatch.setattr(crypto_batch, "_tpu_usable", True)
    assert isinstance(crypto_batch.new_batch_verifier("auto"),
                      crypto_batch.TPUBatchVerifier)


def test_pallas_breaker_policy():
    """Compile/lowering rejections are deterministic → permanent trip;
    transient faults open after 2 and stay re-probeable (the old
    ``_kernel_broken`` latch never un-latched)."""
    br = dispatch.pallas_breaker("chaos-test-curve")
    try:
        br.reset()
        dispatch.note_pallas_failure(
            br, NotImplementedError("pallas lowering not implemented"))
        assert br.state == _bk.OPEN
        assert br.snapshot()["permanent"]
        assert not br.allow()

        br.reset()
        dispatch.note_pallas_failure(br, RuntimeError("transient device fault"))
        assert br.state == _bk.CLOSED  # threshold 2
        dispatch.note_pallas_failure(br, RuntimeError("transient device fault"))
        assert br.state == _bk.OPEN
        assert not br.snapshot()["permanent"]
    finally:
        br.reset()

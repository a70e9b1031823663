"""The dispatch table (tmtpu/tpu/dispatch.py): every row, both steps, one
chip and the virtual mesh, through the one function and its one caller.

One parametrised test over curve x mask/tally x chip/mesh x scenario:

- ``exact``: the mask and the tally of ``TPUBatchVerifier`` equal
  ``CPUBatchVerifier``'s lane for lane, adversarial lanes among them (a
  bad signature, a non-canonical scalar, a wrong length), at a width that
  pads. ed25519 rides the shapes tier-1 compiles anyway (the 64-lane
  bucket; 128 lanes over four virtual devices); the other two curves'
  graphs compile nowhere else in tier-1, so their cases are ``slow``.
- ``pinned``: ``min_lanes`` gives the pinned padded width for every
  curve and step (the compiled steps stood in for by ones that answer at
  once: only the width they are handed is under test).
- ``chaos-site``: a scripted fault at ``tpu.ed25519.batch`` is seen by a
  TALLY flush, and the flush still returns the exact mask and tally.
- ``pallas-fault``: the row's fused kernel step raising notes
  ``pallas.ed25519``, the XLA graph serves that flush, ``crypto.tpu``
  stays closed.
"""

import dataclasses
import hashlib

import pytest

from tmtpu.crypto import batch as crypto_batch
from tmtpu.crypto import ed25519 as ed
from tmtpu.crypto import ed25519_ref
from tmtpu.crypto import secp256k1 as k1
from tmtpu.crypto import sigcache
from tmtpu.crypto import sr25519 as sr
from tmtpu.libs import breaker as bk
from tmtpu.libs import faultinject
from tmtpu.libs import metrics as _m
from tmtpu.tpu import dispatch
from tmtpu.tpu import mesh_dispatch as md


def _priv(curve, i):
    seed = b"dispatch-%s-%d" % (curve.encode(), i)
    if curve == "ed25519":
        return ed.gen_priv_key_from_secret(seed)
    if curve == "sr25519":
        return sr.gen_priv_key_from_secret(seed)
    return k1.PrivKeySecp256k1(
        (int.from_bytes(hashlib.sha256(seed).digest(), "big")
         % (k1.N - 1) + 1).to_bytes(32, "big"))


def _non_canonical(curve, sig):
    """The same signature with its scalar moved out of the canonical
    range: s + L (ed25519; sr25519 under the schnorrkel marker bit),
    n - s (secp256k1: high-S)."""
    if curve == "secp256k1":
        s = k1.N - int.from_bytes(sig[32:], "big")
        return sig[:32] + s.to_bytes(32, "big")
    s = int.from_bytes(sig[32:], "little")
    if curve == "sr25519":
        s &= (1 << 255) - 1
    s += ed25519_ref.L
    if curve == "sr25519":
        s |= 1 << 255
    return sig[:32] + s.to_bytes(32, "little")


BAD = (2, 4, 6)


def _items(curve, n, adversarial=True):
    """n lanes (pub key, msg, sig, power); with ``adversarial`` lane 2
    carries a flipped byte, lane 4 a non-canonical scalar, lane 6 a
    63-byte signature."""
    items = []
    for i in range(n):
        priv = _priv(curve, i)
        msg = b"dispatch msg %d" % i
        sig = priv.sign(msg)
        if adversarial and i == 2:
            sig = bytes([sig[0] ^ 0x40]) + sig[1:]
        elif adversarial and i == 4:
            sig = _non_canonical(curve, sig)
        elif adversarial and i == 6:
            sig = sig[:63]
        items.append((priv.pub_key(), msg, sig, 100 + 7 * i))
    return items


def _flush(verifier, items, tally):
    for lane in items:
        verifier.add(*lane)
    if tally:
        _ok, mask, tallied = verifier.verify_tally()
        return mask, tallied
    return verifier.verify()[1], None


@pytest.fixture
def device(monkeypatch):
    """The device path forced on, the sigcache off, the breakers and the
    mesh as a fresh process has them — before and after."""
    monkeypatch.setattr(crypto_batch, "_TPU_MIN_BATCH", 1)
    monkeypatch.setattr(crypto_batch, "_tpu_usable", True)
    sigcache.DEFAULT.set_enabled(False)
    saved = dict(md._cfg)

    def fresh():
        faultinject.reset()
        md.set_overrides(**saved)
        md.reset()
        for name in (crypto_batch.BREAKER_NAME, md.MESH_BREAKER_NAME,
                     "pallas.ed25519", "pallas.sr25519",
                     "pallas.secp256k1"):
            bk.get(name).reset()
    fresh()
    # what each device flush reported: (curve, backend, impl, lanes, padded)
    flushes = []
    real = _m.observe_crypto_batch
    monkeypatch.setattr(
        _m, "observe_crypto_batch",
        lambda *a: (flushes.append(a[:5]), real(*a))[1])
    yield flushes
    fresh()


def _place(monkeypatch, where):
    if where == "mesh":
        monkeypatch.setenv("TMTPU_MESH_DEVICES", "4")
        monkeypatch.setenv("TMTPU_SHARD_MIN_LANES", "1")
    else:
        monkeypatch.setenv("TMTPU_MESH_DEVICES", "1")


def _exact(curve, tally, where, flushes, monkeypatch):
    items = _items(curve, 10)
    want_mask, want_tally = _flush(crypto_batch.CPUBatchVerifier(), items,
                                   tally)
    assert want_mask == [i not in BAD for i in range(10)]
    del flushes[:]
    got = _flush(crypto_batch.TPUBatchVerifier(), items, tally)
    assert got == (want_mask, want_tally)
    # the nine 64-byte lanes went to the device in one dispatch (the
    # 63-byte one down the serial path), padded to the bucket and, on the
    # mesh, to whole words a shard
    assert flushes == [(curve, "cpu", "mesh-xla", 9, 128)
                       if where == "mesh" else (curve, "cpu", "xla", 9, 64)]
    assert md.dispatch_count() == (1 if where == "mesh" else 0)
    assert bk.get(crypto_batch.BREAKER_NAME).snapshot()["failures"] == 0


def _pinned(curve, tally, where, flushes, monkeypatch):
    """Nine lanes pinned to 200 meet the 256-lane shape, on one chip and
    on four. The compiled steps are stood in for by ones that answer at
    once: only the width they are handed is under test."""
    import jax.numpy as jnp

    widths = []

    def mask_step(packed, *_rest):
        widths.append(int(packed.shape[1]))
        return jnp.ones(packed.shape[1], dtype=bool)

    def tally_step(packed, limbs, *_rest):
        return mask_step(packed), jnp.sum(limbs, axis=1), None

    row = dispatch.CURVES[curve]
    fused = row.tally_xla is not None
    monkeypatch.setitem(dispatch.CURVES, curve, dataclasses.replace(
        row, xla=mask_step,
        tally_xla=tally_step if fused else None,
        mesh_mask=(lambda mesh: mask_step) if row.mesh_mask else None,
        mesh_tally_xla=(lambda mesh: tally_step) if fused else None))
    monkeypatch.setattr(dispatch, "use_pallas_kernel", lambda: False)
    items = _items(curve, 9, adversarial=False)
    del flushes[:]
    mask, tallied = _flush(crypto_batch.TPUBatchVerifier(min_lanes=200),
                           items, tally)
    assert mask == [True] * 9
    assert tallied == (sum(lane[3] for lane in items) if tally else None)
    assert widths == [256]
    assert [f[3:] for f in flushes] == [(9, 256)]
    assert md.dispatch_count() == (1 if where == "mesh" else 0)


def _chaos_site(curve, tally, where, flushes, monkeypatch):
    items = _items(curve, 10)
    want = _flush(crypto_batch.CPUBatchVerifier(), items, tally)
    faultinject.script("tpu.ed25519.batch", faultinject.ERROR, count=1)
    fb0 = dict(_m.crypto_cpu_fallback.summary_series())
    assert _flush(crypto_batch.TPUBatchVerifier(), items, tally) == want
    inj = dict(_m.fault_injected.summary_series())
    assert inj.get("site=tpu.ed25519.batch,mode=error", 0) >= 1
    # the site raised inside the device call: crypto.tpu counted it and
    # exactly the nine device lanes re-verified serially
    assert bk.get(crypto_batch.BREAKER_NAME).snapshot()["failures"] == 1
    fb1 = dict(_m.crypto_cpu_fallback.summary_series())
    key = "curve=ed25519,reason=device-error"
    assert fb1.get(key, 0) - fb0.get(key, 0) == 9


def _pallas_fault(curve, tally, where, flushes, monkeypatch):
    monkeypatch.setenv("TMTPU_TPU_IMPL", "pallas")

    def boom(*_a):
        raise RuntimeError("transient device fault in the kernel step")

    # the tile floor at the bucket, so that the XLA graph which serves
    # the flush is the 64-lane one tier-1 compiles anyway
    monkeypatch.setitem(dispatch.CURVES, curve, dataclasses.replace(
        dispatch.CURVES[curve], tally_kernel=boom, tile=64))
    items = _items(curve, 10)
    want = _flush(crypto_batch.CPUBatchVerifier(), items, tally)
    del flushes[:]
    assert _flush(crypto_batch.TPUBatchVerifier(), items, tally) == want
    assert bk.get("pallas.ed25519").snapshot()["failures"] == 1
    tpu_br = bk.get(crypto_batch.BREAKER_NAME)
    assert tpu_br.state == bk.CLOSED and tpu_br.snapshot()["failures"] == 0
    # served by the XLA graph, on the operands padded for the kernel
    assert flushes == [(curve, "cpu", "xla", 9, 64)]


SCENARIOS = {"exact": _exact, "pinned": _pinned,
             "chaos-site": _chaos_site, "pallas-fault": _pallas_fault}


def _cases():
    for curve in ("ed25519", "sr25519", "secp256k1"):
        for tally in (False, True):
            for where in ("chip", "mesh"):
                step = "tally" if tally else "mask"
                # only ed25519's graphs compile elsewhere in tier-1
                marks = [] if curve == "ed25519" else [pytest.mark.slow]
                yield pytest.param(curve, tally, where, "exact", marks=marks,
                                   id=f"{curve}-{step}-{where}-exact")
                yield pytest.param(curve, tally, where, "pinned",
                                   id=f"{curve}-{step}-{where}-pinned")
    for scenario in ("chaos-site", "pallas-fault"):
        yield pytest.param("ed25519", True, "chip", scenario,
                           id=f"ed25519-tally-chip-{scenario}")


@pytest.mark.parametrize("curve,tally,where,scenario", list(_cases()))
def test_dispatch_table(curve, tally, where, scenario, device, monkeypatch):
    _place(monkeypatch, where)
    SCENARIOS[scenario](curve, tally, where, device, monkeypatch)

"""Differential tests: TPU batch verifier vs the pure-Python spec oracle
(and OpenSSL where available), per SURVEY.md §4 — random and adversarial
batches (corrupted sig/msg/pubkey, non-canonical encodings, mixed lanes)."""

import hashlib
import os

import numpy as np
import pytest

from tmtpu.crypto import ed25519_ref as ref
from tmtpu.tpu import dispatch
from tmtpu.tpu import verify as tv

RNG = np.random.default_rng(7)


def _mk(n, msg_len=96):
    seeds = [bytes(RNG.integers(0, 256, 32, dtype=np.uint8)) for _ in range(n)]
    msgs = [bytes(RNG.integers(0, 256, msg_len, dtype=np.uint8)) for _ in range(n)]
    pks = [ref.public_key(s) for s in seeds]
    sigs = [ref.sign(s, m) for s, m in zip(seeds, msgs)]
    return pks, msgs, sigs


def test_all_valid_batch():
    pks, msgs, sigs = _mk(5)
    assert dispatch.device_verify("ed25519", pks, msgs, sigs)[0].all()


def test_adversarial_lanes_match_oracle():
    pks, msgs, sigs = _mk(12)
    pks, msgs, sigs = list(pks), list(msgs), list(sigs)

    def flip(b: bytes, i: int, bit: int = 0) -> bytes:
        ba = bytearray(b)
        ba[i] ^= 1 << bit
        return bytes(ba)

    sigs[0] = flip(sigs[0], 0)          # corrupt R
    sigs[1] = flip(sigs[1], 40)         # corrupt s
    msgs[2] = flip(msgs[2], 3)          # corrupt msg
    pks[3] = flip(pks[3], 1)            # corrupt pubkey (may fail decompress)
    # s >= L (non-canonical): s' = s + L
    s_int = int.from_bytes(sigs[4][32:], "little") + ref.L
    sigs[4] = sigs[4][:32] + int.to_bytes(s_int, 32, "little")
    # non-canonical pubkey y (>= p): y = p + 1 -> bytes
    pks[5] = int.to_bytes(ref.P + 1, 32, "little")
    # R with sign bit flipped
    sigs[6] = flip(sigs[6], 31, 7)
    # pubkey swapped for another validator's (sig no longer matches)
    pks[7] = pks[11]
    # wrong-length handled at the python layer
    sigs[8] = sigs[8][:63]

    got, _ = dispatch.device_verify("ed25519", pks, msgs, sigs)
    want = np.array(
        [ref.verify(pk, m, s) for pk, m, s in zip(pks, msgs, sigs)], dtype=bool
    )
    assert (got == want).all(), (got, want)
    assert not want[:9].any()
    assert want[9:].all()


def test_low_order_and_mixed_order_points_match_oracle():
    # Signatures "verifying" against low-order pubkeys: with A = identity,
    # any (R=[s]B encoding, s) pair passes cofactorless verify. The TPU path
    # must agree with the oracle (Go stdlib accepts these).
    s = 12345
    R = ref.point_compress(ref.scalar_mult(s, ref.BASE))
    sig = R + int.to_bytes(s, 32, "little")
    pk = ref.point_compress(ref.IDENTITY)
    msg = b"anything"
    assert ref.verify(pk, msg, sig)  # oracle sanity
    assert dispatch.device_verify("ed25519", [pk], [msg], [sig])[0][0]


def test_empty_and_single():
    assert dispatch.device_verify("ed25519", [], [], [])[0].shape == (0,)
    pks, msgs, sigs = _mk(1)
    assert dispatch.device_verify("ed25519", pks, msgs, sigs)[0].all()


def test_large_random_batch_differential():
    n = 33  # crosses a pad bucket boundary (-> 64)
    pks, msgs, sigs = _mk(n, msg_len=120)
    # corrupt a random third of lanes in assorted ways
    idx = RNG.choice(n, size=n // 3, replace=False)
    for i in idx:
        k = int(RNG.integers(0, 3))
        if k == 0:
            sigs[i] = os.urandom(64)
        elif k == 1:
            msgs[i] = os.urandom(50)
        else:
            pks[i] = os.urandom(32)
    got, _ = dispatch.device_verify("ed25519", pks, msgs, sigs)
    want = np.array(
        [ref.verify(pk, m, s) for pk, m, s in zip(pks, msgs, sigs)], dtype=bool
    )
    assert (got == want).all()

"""secp256k1 device batch verification (tmtpu/tpu/fe_k1.py, k1_verify.py) —
field-arithmetic bound tests against Python ints, complete-addition
validation against an affine oracle, and differential verification against
the serial 'cryptography'-backed path on valid/adversarial lanes."""

import random

import numpy as np
import pytest

from tmtpu.crypto.secp256k1 import (
    N, PrivKeySecp256k1, PubKeySecp256k1, gen_priv_key,
)
from tmtpu.tpu import dispatch
from tmtpu.tpu import fe_k1 as fe
from tmtpu.tpu import k1_verify as kv

P = fe.P_INT


def _col(v):
    import jax.numpy as jnp

    return jnp.asarray(fe.limbs_of_int(v))[:, None]


def _val(limbs_col):
    return fe.int_of_limbs(np.asarray(limbs_col)[:, 0])


@pytest.mark.slow
def test_fe_k1_mul_sub_freeze_random():
    rng = random.Random(11)
    for _ in range(12):
        a = rng.randrange(P)
        b = rng.randrange(P)
        ca, cb = _col(a), _col(b)
        assert _val(fe.freeze(fe.mul(ca, cb))) == a * b % P
        assert _val(fe.freeze(fe.add(ca, cb))) == (a + b) % P
        assert _val(fe.freeze(fe.sub(ca, cb))) == (a - b) % P
        assert _val(fe.freeze(fe.sq(ca))) == a * a % P
        assert _val(fe.freeze(fe.mul_small(ca, 21))) == a * 21 % P


@pytest.mark.slow
def test_fe_k1_adversarial_values():
    # worst-case-ish operands: p-1, values with max limbs, tiny values
    cases = [P - 1, P - 2**200, 2**255 - 1, (1 << 256) % P, 1, 0,
             int("1555" * 16, 16) % P]
    for a in cases:
        for b in cases:
            ca, cb = _col(a), _col(b)
            assert _val(fe.freeze(fe.mul(ca, cb))) == a * b % P
            assert _val(fe.freeze(fe.sub(ca, cb))) == (a - b) % P


def test_fe_k1_loose_chains_stay_correct():
    # long op chains without intermediate freeze: bounds must hold
    rng = random.Random(5)
    a = rng.randrange(P)
    b = rng.randrange(P)
    ca, cb = _col(a), _col(b)
    va, vb = a, b
    for i in range(30):
        ca, cb = fe.mul(ca, cb), fe.sub(fe.add(ca, cb), fe.sq(cb))
        va, vb = va * vb % P, (va + vb - vb * vb) % P
    assert _val(fe.freeze(ca)) == va
    assert _val(fe.freeze(cb)) == vb


@pytest.mark.slow
def test_fe_k1_sqrt_chain():
    rng = random.Random(7)
    for _ in range(4):
        r = rng.randrange(P)
        a = r * r % P
        got = _val(fe.freeze(fe.sqrt_candidate(_col(a))))
        assert got * got % P == a
    # non-residue: candidate squares to something else
    nr = 3  # 3 is a non-residue mod this p (p % 12 == 7)
    assert pow(nr, (P - 1) // 2, P) == P - 1
    got = _val(fe.freeze(fe.sqrt_candidate(_col(nr))))
    assert got * got % P != nr


def _aff_add(a, b):
    if a is None:
        return b
    if b is None:
        return a
    x1, y1 = a
    x2, y2 = b
    if x1 == x2 and (y1 + y2) % P == 0:
        return None
    if a == b:
        lam = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return (x3, (lam * (x1 - x3) - y1) % P)


def _proj_val(pt):
    X, Y, Z = (_val(fe.freeze(c)) for c in pt)
    if Z == 0:
        return None
    zi = pow(Z, -1, P)
    return (X * zi % P, Y * zi % P)


@pytest.mark.slow
def test_k1_complete_add_against_oracle():
    g = (kv.GX, kv.GY)
    gp = (_col(kv.GX), _col(kv.GY), _col(1))
    # chain of adds, doubling (P+P through the same formula), inverse
    acc_a, acc_p = None, kv.identity((1,))
    for i in range(8):
        acc_a = _aff_add(acc_a, g)
        acc_p = kv.add(acc_p, gp)
        assert _proj_val(acc_p) == acc_a
    dbl = kv.add(gp, gp)
    assert _proj_val(dbl) == _aff_add(g, g)
    neg = kv.negate(gp)
    assert _proj_val(kv.add(gp, neg)) is None  # P + (-P) = infinity
    assert _proj_val(kv.add(kv.identity((1,)), gp)) == g


def _mk(n, seed=b"k1-dev"):
    import hashlib

    keys = [
        PrivKeySecp256k1(
            (int.from_bytes(hashlib.sha256(seed + bytes([i])).digest(),
                            "big") % (N - 1) + 1).to_bytes(32, "big"))
        for i in range(n)
    ]
    msgs = [b"k1-msg-%d" % i + bytes(range(i % 5)) for i in range(n)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    pks = [k.pub_key().bytes() for k in keys]
    return pks, msgs, sigs


def _serial(pks, msgs, sigs):
    return [
        PubKeySecp256k1(pk).verify_signature(m, s)
        for pk, m, s in zip(pks, msgs, sigs)
    ]


@pytest.mark.slow
def test_k1_batch_all_valid():
    pks, msgs, sigs = _mk(8)
    mask, _ = dispatch.device_verify("secp256k1", pks, msgs, sigs)
    assert mask.all()


@pytest.mark.slow
def test_k1_batch_adversarial_lanes_match_serial():
    pks, msgs, sigs = _mk(12)
    pks, msgs, sigs = list(pks), list(msgs), list(sigs)

    # lane 1: corrupted r
    s1 = bytearray(sigs[1]); s1[5] ^= 0x20; sigs[1] = bytes(s1)
    # lane 2: corrupted message
    msgs[2] = msgs[2] + b"x"
    # lane 3: wrong pubkey
    pks[3] = pks[4]
    # lane 4: high-S (malleated): s -> n - s, rejected by low-S rule
    r4, s4 = sigs[4][:32], int.from_bytes(sigs[4][32:], "big")
    sigs[4] = r4 + (N - s4).to_bytes(32, "big")
    # lane 5: r = 0
    sigs[5] = bytes(32) + sigs[5][32:]
    # lane 6: r >= n
    sigs[6] = N.to_bytes(32, "big") + sigs[6][32:]
    # lane 7: bad pubkey prefix
    pks[7] = b"\x05" + pks[7][1:]
    # lane 8: pubkey x not on curve (x=0 -> y^2=7 non-residue w.h.p.)
    pks[8] = b"\x02" + bytes(32)
    # lane 9: truncated sig
    sigs[9] = sigs[9][:50]
    # lane 10: corrupted s
    s10 = bytearray(sigs[10]); s10[45] ^= 0x04; sigs[10] = bytes(s10)

    want = _serial(pks, msgs, sigs)
    assert want == [i not in (1, 2, 3, 4, 5, 6, 7, 8, 9, 10)
                    for i in range(12)]
    got, _ = dispatch.device_verify("secp256k1", pks, msgs, sigs)
    assert got.tolist() == want


@pytest.mark.slow
def test_three_curve_batch_verifier_dispatch(monkeypatch):
    """TPUBatchVerifier with ed25519 + sr25519 + secp256k1 lanes: one
    device dispatch per curve (BASELINE 'mixed sets'), exact mask and
    tally with one corrupt lane per curve."""
    from tmtpu.crypto import batch as cb
    from tmtpu.crypto import ed25519 as ed
    from tmtpu.crypto import sr25519 as sr

    monkeypatch.setattr(cb, "_TPU_MIN_BATCH", 2)
    gens = [ed.gen_priv_key, lambda: sr.gen_priv_key_from_secret(b"3c"),
            gen_priv_key]
    bv = cb.TPUBatchVerifier()
    want, powers = [], []
    for i in range(9):
        k = gens[i % 3]()
        msg = b"3curve-%d" % i
        sig = k.sign(msg)
        if i in (3, 4, 5):
            sig = sig[:8] + bytes([sig[8] ^ 0xFF]) + sig[9:]
        bv.add(k.pub_key(), msg, sig, power=100 + i)
        ok = k.pub_key().verify_signature(msg, sig)
        want.append(ok)
        powers.append(100 + i if ok else 0)
    all_ok, mask, tallied = bv.verify_tally()
    assert mask == want
    assert not all_ok and sum(mask) == 6
    assert tallied == sum(powers)


@pytest.mark.slow
def test_k1_flipped_parity_pubkey():
    # flipping the compressed prefix selects -Q: signature must fail
    pks, msgs, sigs = _mk(8)
    pks = list(pks)
    flip = 2 if pks[0][0] == 3 else 3
    pks[0] = bytes([flip]) + pks[0][1:]
    want = _serial(pks, msgs, sigs)
    got, _ = dispatch.device_verify("secp256k1", pks, msgs, sigs)
    assert got.tolist() == want
    assert not got[0]

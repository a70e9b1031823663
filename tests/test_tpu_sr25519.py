"""sr25519 device batch verification (tmtpu/tpu/sr_verify.py) — differential
against the serial schnorrkel oracle (tmtpu/crypto/sr25519.py) on valid,
corrupted, and non-canonical lanes, plus the mixed-curve BatchVerifier
dispatch (BASELINE.md "mixed sets"). Runs on the jax CPU backend
(tests/conftest.py) — the graph is identical on TPU."""

import numpy as np
import pytest

from tmtpu.crypto import batch as cb
from tmtpu.crypto import ristretto
from tmtpu.crypto.ed25519 import gen_priv_key as gen_ed
from tmtpu.crypto.sr25519 import (
    L, PrivKeySr25519, PubKeySr25519, gen_priv_key_from_secret,
)
from tmtpu.tpu import dispatch
from tmtpu.tpu import sr_verify as srv


def _mk(n, seed=b"sr-dev"):
    keys = [gen_priv_key_from_secret(seed + bytes([i])) for i in range(n)]
    msgs = [b"msg-%d" % i + bytes(range(i % 7)) for i in range(n)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    pks = [k.pub_key().bytes() for k in keys]
    return pks, msgs, sigs


def _serial(pks, msgs, sigs):
    return [
        PubKeySr25519(pk).verify_signature(m, s)
        for pk, m, s in zip(pks, msgs, sigs)
    ]


@pytest.mark.slow
def test_sr_batch_all_valid():
    pks, msgs, sigs = _mk(12)
    mask, _ = dispatch.device_verify("sr25519", pks, msgs, sigs)
    assert mask.all()


@pytest.mark.slow
def test_sr_batch_adversarial_lanes_match_serial():
    pks, msgs, sigs = _mk(16)
    pks, msgs, sigs = list(pks), list(msgs), list(sigs)

    # lane 1: corrupted signature R
    s1 = bytearray(sigs[1]); s1[3] ^= 0x40; sigs[1] = bytes(s1)
    # lane 2: corrupted message
    msgs[2] = msgs[2] + b"!"
    # lane 3: wrong pubkey (another validator's)
    pks[3] = pks[4]
    # lane 5: schnorrkel marker bit cleared
    s5 = bytearray(sigs[5]); s5[63] &= 0x7F; sigs[5] = bytes(s5)
    # lane 6: non-canonical s (s + L still < 2^255 for small s values)
    s6 = bytearray(sigs[6])
    sval = int.from_bytes(bytes(s6[32:63]) + bytes([s6[63] & 0x7F]), "little")
    if sval + L < 1 << 255:
        s6[32:] = ((sval + L) | (1 << 255)).to_bytes(32, "little")
        sigs[6] = bytes(s6)
    # lane 7: non-canonical R encoding (odd value -> IS_NEGATIVE reject)
    s7 = bytearray(sigs[7]); s7[0] |= 0x01; sigs[7] = bytes(s7)
    # lane 8: pubkey bytes are a non-canonical encoding (>= p)
    pks[8] = (2**255 - 18).to_bytes(32, "little")
    # lane 9: truncated signature
    sigs[9] = sigs[9][:40]
    # lane 10: corrupted s half
    s10 = bytearray(sigs[10]); s10[40] ^= 0x08; sigs[10] = bytes(s10)

    want = _serial(pks, msgs, sigs)
    assert want == [i not in (1, 2, 3, 5, 6, 7, 8, 9, 10)
                    for i in range(16)]
    got, _ = dispatch.device_verify("sr25519", pks, msgs, sigs)
    assert got.tolist() == want


@pytest.mark.slow
def test_sr_identity_encoding_lane():
    # all-zero bytes decode to the ristretto identity; a signature by the
    # "identity pubkey" can only verify when R' == R holds by construction.
    pks, msgs, sigs = _mk(8)
    pks, sigs = list(pks), list(sigs)
    pks[0] = bytes(32)
    want = _serial(pks, msgs, sigs)
    got, _ = dispatch.device_verify("sr25519", pks, msgs, sigs)
    assert got.tolist() == want
    assert not got[0]


@pytest.mark.slow
def test_mixed_curve_batch_verifier_dispatch(monkeypatch):
    """BatchVerifier with interleaved ed25519 + sr25519 lanes: one device
    dispatch per curve, exact per-lane mask, tally over valid lanes."""
    monkeypatch.setattr(cb, "_TPU_MIN_BATCH", 4)
    n = 16
    bv = cb.TPUBatchVerifier()
    want = []
    powers = []
    for i in range(n):
        msg = b"vote-%d" % i
        power = 10 + i
        if i % 2 == 0:
            k = gen_ed()
            sig = k.sign(msg)
            pk = k.pub_key()
        else:
            k = gen_priv_key_from_secret(b"mix" + bytes([i]))
            sig = k.sign(msg)
            pk = k.pub_key()
        if i in (4, 7):  # one bad lane per curve
            sig = sig[:10] + bytes([sig[10] ^ 0xFF]) + sig[11:]
        bv.add(pk, msg, sig, power=power)
        ok = pk.verify_signature(msg, sig)
        want.append(ok)
        powers.append(power if ok else 0)
    all_ok, mask, tallied = bv.verify_tally()
    assert mask == want
    assert not all_ok
    assert tallied == sum(powers)


@pytest.mark.slow
def test_sr_pallas_kernel_interpret_matches_graph():
    """The fused sr25519 Pallas kernel (interpret mode — the same program
    Mosaic compiles on a real TPU) must agree lane-for-lane with the XLA
    graph and the serial oracle on valid + adversarial lanes."""
    from tmtpu.tpu import kernel as tk

    pks, msgs, sigs = _mk(8, seed=b"sr-kern")
    pks, sigs = list(pks), list(sigs)
    s2 = bytearray(sigs[2]); s2[7] ^= 0x10; sigs[2] = bytes(s2)  # bad R
    pks[5] = pks[6]  # wrong key
    args, host_ok = srv.prepare_sr_batch(pks, msgs, sigs)
    want, _ = dispatch.device_verify("sr25519", pks, msgs, sigs)
    got = np.asarray(
        tk.sr_verify_compact_kernel(*args, tile=8, interpret=True))
    assert (got & host_ok).tolist() == want.tolist()
    assert want.tolist() == _serial(pks, msgs, sigs)
    assert not want[2] and not want[5] and want[0]


def test_native_merlin_challenges_match_python():
    """The C STROBE/merlin transcript walk (tmtpu/native/hostprep.c
    tmtpu_sr_challenges) must agree byte-for-byte with the KAT-verified
    pure-Python merlin across message lengths spanning keccak block
    boundaries."""
    from tmtpu import native
    from tmtpu.tpu.sr_verify import _challenge_k

    if native.load() is None:
        pytest.skip("no C toolchain")
    rng = np.random.default_rng(9)
    lens = [0, 1, 100, 143, 144, 145, 163, 164, 165, 166, 167, 200, 331,
            332, 500]
    B = len(lens)
    pks = rng.integers(0, 256, (B, 32), dtype=np.uint8)
    rs = rng.integers(0, 256, (B, 32), dtype=np.uint8)
    msgs = [rng.integers(0, 256, l, dtype=np.uint8).tobytes() for l in lens]
    got = native.sr_challenges(pks, rs, msgs)
    for i in range(B):
        want = _challenge_k(pks[i].tobytes(), msgs[i], rs[i].tobytes())
        assert got[i].tobytes() == want, f"msg len {lens[i]}"


def test_ristretto_decode_oracle_roundtrip():
    """Device decompression matches the host oracle point-for-point on the
    first 32 small multiples of B (covers torsion-free canonical points)."""
    import jax.numpy as jnp

    from tmtpu.tpu import fe

    encs = []
    pts = []
    for i in range(32):
        p = ristretto.scalar_mult(i, ristretto.BASEPOINT)
        e = ristretto.encode(p)
        encs.append(e)
        pts.append(ristretto.decode(e))
    b = np.frombuffer(b"".join(encs), dtype=np.uint8).reshape(32, 32)
    limbs = jnp.asarray(fe.pack_bytes_le(b))
    (x, y, z, t), valid = srv.ristretto_decompress(limbs)
    assert np.asarray(valid).all()
    zinv = fe.invert(z)
    xf = np.asarray(fe.freeze(fe.mul(x, zinv)))
    yf = np.asarray(fe.freeze(fe.mul(y, zinv)))
    for j, p in enumerate(pts):
        px, py, pz, _ = p
        zi = pow(pz, -1, srv.P)
        assert fe.int_of_limbs(xf[:, j]) == px * zi % srv.P
        assert fe.int_of_limbs(yf[:, j]) == py * zi % srv.P

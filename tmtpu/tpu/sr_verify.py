"""Batched sr25519 (schnorrkel) signature verification on TPU.

The reference verifies sr25519 one-at-a-time on CPU through go-schnorrkel
(crypto/sr25519/pubkey.go:50); the spec oracle here is
tmtpu.crypto.sr25519.PubKeySr25519.verify_signature. BASELINE.md lists
sr25519 batches and mixed-curve sets as a north-star config — this module
gives sr25519 the same device pipeline ed25519 has (tmtpu.tpu.verify).

ristretto255 is a quotient group over the same edwards25519 curve, so the
entire field/curve stack (tmtpu.tpu.fe radix-2^13 limbs, tmtpu.tpu.curve
complete point ops and the Straus/Shamir ladder, the fixed-base window
table for B) is reused verbatim. What is new here is batched *ristretto*
decoding (SQRT_RATIO_M1 decompression) and coset equality, per
draft-irtf-cfrg-ristretto255 (host oracle: tmtpu.crypto.ristretto).

Split of labor:
- **host**: length/marker checks, ``s < L``, canonical-encoding byte checks
  (value < p, even), and the merlin transcript absorption producing the
  challenge scalar k (STROBE/Keccak is byte-serial, data-dependent work —
  exactly what SURVEY §7 assigns to the host side);
- **device**: ristretto decode of A and R (one inverse-sqrt each), the
  shared-doubling ladder R' = [s]B + [k](-A), and projective coset
  equality R' == R — all elementwise over the trailing batch dim, sharding
  over lanes like the ed25519 graph.

Verification predicate (exactly the CPU path's): sig parses, A and R are
canonical ristretto encodings, s canonical, and encode(R') == sig.R —
which over canonical encodings is equivalent to the on-device coset
equality decode(sig.R) ≅ R' (encode/decode are inverse bijections between
canonical encodings and cosets, so no byte re-encoding is needed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tmtpu.crypto import ed25519_ref as ref
from tmtpu.crypto import ristretto
from tmtpu.crypto.merlin import Transcript
from tmtpu.tpu import curve, fe
from tmtpu.tpu.verify import digits_msb_device, lanes_as_arrays, lt_le

L = ref.L
P = ref.P

D_LIMBS = fe.limbs_of_int(ref.D)
SQRT_M1_LIMBS = fe.limbs_of_int(ref.SQRT_M1)
NEG_SQRT_M1_LIMBS = fe.limbs_of_int(P - ref.SQRT_M1)
ONE_LIMBS = fe.limbs_of_int(1)
NEG_ONE_LIMBS = fe.limbs_of_int(P - 1)


def _const(limbs):
    return jnp.asarray(limbs)[:, None]


def _one_like(s):
    return jnp.zeros_like(s).at[0].add(1)


def _parity(x_frozen):
    """IS_NEGATIVE per ristretto spec: low bit of the canonical form."""
    return x_frozen[0] & 1


def _abs_fe(x):
    """CT_ABS: negate iff the canonical form is odd. Returns loose limbs."""
    xf = fe.freeze(x)
    return jnp.where((_parity(xf) == 1)[None], fe.neg(xf), xf)


def _invsqrt(w):
    """SQRT_RATIO_M1(1, w): (was_square [B], r [20, B]) with r = 1/sqrt(w)
    when w is a nonzero square (mirrors ristretto._sqrt_ratio_m1 with u=1).
    """
    w3 = fe.mul(fe.sq(w), w)
    w7 = fe.mul(fe.sq(w3), w)
    r = fe.mul(w3, fe.pow_p58(w7))
    check = fe.freeze(fe.mul(w, fe.sq(r)))
    correct = jnp.all(check == _const(ONE_LIMBS), axis=0)
    flipped = jnp.all(check == _const(NEG_ONE_LIMBS), axis=0)
    flipped_i = jnp.all(check == _const(NEG_SQRT_M1_LIMBS), axis=0)
    r = jnp.where(
        (flipped | flipped_i)[None], fe.mul(r, _const(SQRT_M1_LIMBS)), r
    )
    return correct | flipped, _abs_fe(r)


def ristretto_decompress(s):
    """Batched ristretto255 DECODE: s [20, B] canonical limbs (host has
    already rejected values >= p and odd values). Returns (extended point,
    valid mask [B]); invalid lanes hold a garbage-but-finite point that the
    complete formulas never fault on — callers mask."""
    one = _one_like(s)
    ss = fe.sq(s)
    u1 = fe.sub(one, ss)
    u2 = fe.add(one, ss)
    u2_sqr = fe.sq(u2)
    # v = -(d*u1^2) - u2^2
    v = fe.sub(fe.neg(fe.mul(_const(D_LIMBS), fe.sq(u1))), u2_sqr)
    ok, invsqrt = _invsqrt(fe.mul(v, u2_sqr))
    den_x = fe.mul(invsqrt, u2)
    den_y = fe.mul(fe.mul(invsqrt, den_x), v)
    x = _abs_fe(fe.mul(fe.add(s, s), den_x))
    y = fe.mul(u1, den_y)
    t = fe.mul(x, y)
    yf = fe.freeze(y)
    valid = ok & (_parity(fe.freeze(t)) == 0) & ~jnp.all(yf == 0, axis=0)
    return (x, y, one, t), valid


def ristretto_equal(p, q):
    """Coset equality X1*Y2 == Y1*X2 or X1*X2 == Y1*Y2 — projective-safe
    (Z factors scale both products identically), so the ladder's extended
    result compares directly against a decoded (Z=1) point."""
    x1, y1 = p[0], p[1]
    x2, y2 = q[0], q[1]
    a = fe.freeze(fe.sub(fe.mul(x1, y2), fe.mul(y1, x2)))
    b = fe.freeze(fe.sub(fe.mul(x1, x2), fe.mul(y1, y2)))
    return jnp.all(a == 0, axis=0) | jnp.all(b == 0, axis=0)


def sr_verify_core_compact(pk_b, r_b, s_b, k_b, base_table):
    """The jittable device graph: raw 32-byte columns in, mask out.

    pk_b, r_b: [32, B] uint8 ristretto encodings of A and R (host has
    checked canonical: value < p and even); s_b, k_b: [32, B] uint8 LE
    scalars (s from the signature with the schnorrkel marker bit cleared,
    k = merlin challenge, both < L). Returns bool [B]."""
    a_pt, a_ok = ristretto_decompress(fe.pack_bytes_device(pk_b))
    r_pt, r_ok = ristretto_decompress(fe.pack_bytes_device(r_b))
    r_prime = curve.shamir_double_scalar(
        digits_msb_device(s_b), digits_msb_device(k_b),
        curve.negate(a_pt), base_table,
    )
    return a_ok & r_ok & ristretto_equal(r_prime, r_pt)


# ---------------------------------------------------------------------------
# Host-side preparation.

_P_LE = np.frombuffer(int.to_bytes(P, 32, "little"), dtype=np.uint8)
_L_LE = np.frombuffer(int.to_bytes(L, 32, "little"), dtype=np.uint8)


def _native_challenges(pk_arr, r_arr, msgs):
    """Batched merlin challenges via the C hostprep library; None when no
    toolchain is available (callers fall back to the pure-Python walk).
    Disable with TMTPU_NO_NATIVE=1."""
    import os

    if os.environ.get("TMTPU_NO_NATIVE"):
        return None
    try:
        from tmtpu import native
    except Exception:
        return None
    return native.sr_challenges(pk_arr, r_arr, msgs)


def _challenge_k(pk: bytes, msg: bytes, r_bytes: bytes) -> bytes:
    """The merlin transcript walk of sr25519.PubKeySr25519.verify_signature,
    producing the 32-byte LE challenge scalar k (already reduced mod L)."""
    t = Transcript(b"SigningContext")
    t.append_message(b"", b"")
    t.append_message(b"sign-bytes", msg)
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pk)
    t.append_message(b"sign:R", r_bytes)
    k = int.from_bytes(t.challenge_bytes(b"sign:c", 64), "little") % L
    return k.to_bytes(32, "little")


def prepare_sr_batch_packed(pks, msgs, sigs, padded: int = 0):
    """Host prep, packed form: (numpy [128, max(B, padded)] uint8 —
    pk/r/s/k stacked, lanes B.. replicating lane 0 — and host_ok [B]).
    Callers device_put the single plane.

    Host-rejected lanes (wrong length, missing schnorrkel marker bit,
    s >= L, non-canonical A or R encoding) get well-formed dummy inputs and
    are masked out via host_ok."""
    B = len(sigs)
    len_ok, pk_arr, sig_arr = lanes_as_arrays(pks, sigs, 32)
    pk_arr = pk_arr.copy()  # the views are read-only; lanes get zeroed
    r_arr = sig_arr[:, :32].copy()
    s_arr = sig_arr[:, 32:].copy()
    marker_ok = (s_arr[:, 31] & 0x80) != 0
    s_arr[:, 31] &= 0x7F
    host_ok = (
        len_ok & marker_ok & lt_le(s_arr, _L_LE)
        # canonical ristretto encodings: value < p AND even (IS_NEGATIVE
        # inputs are rejected by DECODE before any field math)
        & lt_le(pk_arr, _P_LE) & ((pk_arr[:, 0] & 1) == 0)
        & lt_le(r_arr, _P_LE) & ((r_arr[:, 0] & 1) == 0)
    )
    if not host_ok.all():
        bad = ~host_ok
        s_arr[bad] = 0
        pk_arr[bad] = 0
        r_arr[bad] = 0
    # merlin challenge per lane (STROBE/Keccak on host; see module doc).
    # The C library (tmtpu/native/hostprep.c tmtpu_sr_challenges) walks the
    # transcripts ~300x faster than the pure-Python merlin — 42 ms vs 12.6 s
    # per 10k lanes; the Python path remains as the no-toolchain fallback
    # and differential oracle (tests/test_tpu_sr25519.py).
    k_arr = _native_challenges(pk_arr, r_arr, msgs)
    if k_arr is None:
        from tmtpu.libs import metrics as _m

        _m.crypto_sr_python_transcript_lanes.inc(B)
        k_arr = np.frombuffer(
            b"".join(
                _challenge_k(p.tobytes(), bytes(m), r.tobytes())
                for p, m, r in zip(pk_arr, msgs, r_arr)
            ),
            dtype=np.uint8,
        ).reshape(B, 32)
    # ONE [128, B] host plane (pk/r/s/k stacked): callers device_put it as
    # a single transfer, same reason the ed25519 path packs
    # (verify.prepare_batch_packed)
    packed = np.empty((128, max(B, padded)), dtype=np.uint8)
    for row, arr in enumerate((pk_arr, r_arr, s_arr, k_arr)):
        packed[32 * row:32 * row + 32, :B] = arr.T
    packed[:, B:] = packed[:, :1]
    return packed, host_ok


def prepare_sr_batch(pks, msgs, sigs):
    """Per-plane form of prepare_sr_batch_packed: ([32, B] jnp x4
    (pk, r, s, k), host_ok) — tests and the sharded per-plane path."""
    packed, host_ok = prepare_sr_batch_packed(pks, msgs, sigs)
    from tmtpu.tpu.verify import split_packed

    return tuple(jnp.asarray(p) for p in split_packed(packed)), host_ok


@jax.jit
def _sr_verify_packed_jit(packed, table):
    """Packed-input twin: ONE [128, B] uint8 H2D transfer, split device-
    side (slices are free under jit)."""
    from tmtpu.tpu.verify import split_packed

    return sr_verify_core_compact(*split_packed(packed), table)


@jax.jit
def _sr_kernel_packed_jit(packed):
    from tmtpu.tpu import kernel as tk
    from tmtpu.tpu.verify import split_packed

    return tk.sr_verify_compact_kernel(*split_packed(packed))

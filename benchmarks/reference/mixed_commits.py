"""Commits over a validator set of three key types (ed25519, sr25519,
secp256k1, round-robin by the index the keys are made from), made from a
seed, and the plain serial reference of ``VerifyCommit``
(types/validator_set.go:667) they are judged by. Nothing here imports
``tmtpu``.

ed25519 and the canonical sign bytes are ``reference/commits.py``'s.

secp256k1 (crypto/secp256k1/secp256k1.go): ECDSA over SHA-256 through
``cryptography`` (OpenSSL), RFC 6979 nonces so that a seed gives the same
bytes, the signature as 64 bytes R||S with S in the low half (a high S
is refused before the equation is looked at), the address
RIPEMD160(SHA256(33-byte compressed key)).

sr25519 (crypto/sr25519/pubkey.go:50, go-schnorrkel with an empty signing
context) in Python integers, written from the public specifications:
Keccak-f[1600] (FIPS 202; offsets and round constants computed as the
standard defines them), STROBE-128 as merlin frames it, merlin
transcripts, ristretto255 (RFC 9496) over edwards25519, schnorrkel's
labels and its marker bit. The signing nonce is SHA-512(key nonce ||
transcript input), not schnorrkel's transcript rng: any nonce verifies.

Signing 9,500 signatures a commit is the cost, so ``Pool`` shares a
commit's slots over worker processes that hold the set's keys (started
afresh: they import this module, never JAX); the same workers verify.
"""

from __future__ import annotations

import hashlib
import multiprocessing
import random
from concurrent.futures import Future, ProcessPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey, Ed25519PublicKey)
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature, encode_dss_signature)

from benchmarks.reference.commits import (  # noqa: F401  (re-exported)
    ABSENT, COMMIT, NIL, CommitData, Outcome, tamper_signature,
    vote_sign_bytes)

ED25519, SR25519, SECP256K1 = "ed25519", "sr25519", "secp256k1"
CURVES = (ED25519, SR25519, SECP256K1)     # key i is of CURVES[i % 3]

# --- Keccak-f[1600] (FIPS 202 section 3) -------------------------------------

_M64 = (1 << 64) - 1


def _keccak_tables():
    """The rho offsets, the pi destinations (lane x + 5y) and the iota
    constants, each computed the way the standard defines it."""
    rot = [0] * 25
    x, y = 1, 0
    for t in range(24):
        rot[x + 5 * y] = (t + 1) * (t + 2) // 2 % 64
        x, y = y, (2 * x + 3 * y) % 5
    dest = [0] * 25
    for x in range(5):
        for y in range(5):
            dest[x + 5 * y] = y + 5 * ((2 * x + 3 * y) % 5)
    rcs, reg = [], 1
    for _round in range(24):
        rc = 0
        for j in range(7):
            if reg & 1:
                rc |= 1 << ((1 << j) - 1)
            reg <<= 1
            if reg & 0x100:
                reg ^= 0x171
        rcs.append(rc)
    return rot, dest, rcs


_ROT, _DEST, _RC = _keccak_tables()
_RHO_PI = [(i, i % 5, _DEST[i], _ROT[i], 64 - _ROT[i]) for i in range(25)]
_CHI = [(i, i - i % 5 + (i + 1) % 5, i - i % 5 + (i + 2) % 5)
        for i in range(25)]


def keccak_f(a: List[int]) -> List[int]:
    """25 lanes of 64 bits (lane x + 5y) -> the permuted lanes."""
    for rc in _RC:
        c0 = a[0] ^ a[5] ^ a[10] ^ a[15] ^ a[20]
        c1 = a[1] ^ a[6] ^ a[11] ^ a[16] ^ a[21]
        c2 = a[2] ^ a[7] ^ a[12] ^ a[17] ^ a[22]
        c3 = a[3] ^ a[8] ^ a[13] ^ a[18] ^ a[23]
        c4 = a[4] ^ a[9] ^ a[14] ^ a[19] ^ a[24]
        d = (c4 ^ ((c1 << 1 | c1 >> 63) & _M64),
             c0 ^ ((c2 << 1 | c2 >> 63) & _M64),
             c1 ^ ((c3 << 1 | c3 >> 63) & _M64),
             c2 ^ ((c4 << 1 | c4 >> 63) & _M64),
             c3 ^ ((c0 << 1 | c0 >> 63) & _M64))
        b = [0] * 25
        for i, col, to, left, right in _RHO_PI:
            v = a[i] ^ d[col]
            b[to] = (v << left | v >> right) & _M64
        a = [b[i] ^ (~b[j] & b[k]) for i, j, k in _CHI]
        a[0] ^= rc
    return a


def sha3_256(data: bytes) -> bytes:
    """SHA3-256 over ``keccak_f``: what the tests hold the permutation to
    (against hashlib's). Nothing else here uses it."""
    rate = 136
    padded = bytearray(data) + b"\x06"
    padded += bytes(-len(padded) % rate)
    padded[-1] |= 0x80
    a = [0] * 25
    for off in range(0, len(padded), rate):
        for i in range(rate // 8):
            a[i] ^= int.from_bytes(padded[off + 8 * i:off + 8 * i + 8],
                                   "little")
        a = keccak_f(a)
    return b"".join(v.to_bytes(8, "little") for v in a[:4])


# --- STROBE-128 as merlin uses it, and merlin transcripts --------------------

_RATE = 166                              # 200 - 128/4 - 2
_F_I, _F_A, _F_C, _F_M = 1, 2, 4, 16      # the op flags merlin needs


class Strobe:
    """The duplex merlin is built on: meta-AD, AD and PRF operations over
    Keccak-f[1600] at STROBE's 128-bit security level."""

    def __init__(self, protocol: bytes = b""):
        if not protocol:
            return
        st = bytearray(200)
        st[0:6] = bytes([1, _RATE + 2, 1, 0, 1, 96])
        st[6:18] = b"STROBEv1.0.2"
        self.st = self._permute(st)
        self.pos = self.pos_begin = 0
        self.flags = 0
        self.operate(_F_M | _F_A, protocol)

    @staticmethod
    def _permute(st: bytearray) -> bytearray:
        lanes = keccak_f([int.from_bytes(st[i:i + 8], "little")
                          for i in range(0, 200, 8)])
        return bytearray(b"".join(v.to_bytes(8, "little") for v in lanes))

    def clone(self) -> "Strobe":
        s = Strobe()
        s.st, s.pos, s.pos_begin, s.flags = \
            bytearray(self.st), self.pos, self.pos_begin, self.flags
        return s

    def _run_f(self) -> None:
        self.st[self.pos] ^= self.pos_begin
        self.st[self.pos + 1] ^= 0x04
        self.st[_RATE + 1] ^= 0x80
        self.st = self._permute(self.st)
        self.pos = self.pos_begin = 0

    def _absorb(self, data: bytes) -> None:
        for byte in data:
            self.st[self.pos] ^= byte
            self.pos += 1
            if self.pos == _RATE:
                self._run_f()

    def operate(self, flags: int, data: bytes = b"", more: bool = False,
                squeeze: int = 0) -> bytes:
        """One STROBE operation: absorbs ``data`` (meta-AD, AD) or, for
        the PRF, squeezes ``squeeze`` bytes."""
        if not more:
            old, self.pos_begin, self.flags = \
                self.pos_begin, self.pos + 1, flags
            self._absorb(bytes([old, flags]))
            if flags & _F_C and self.pos:
                self._run_f()
        elif flags != self.flags:
            raise ValueError("continued another operation")
        if not squeeze:
            self._absorb(data)
            return b""
        out = bytearray()
        for _ in range(squeeze):
            out.append(self.st[self.pos])
            self.st[self.pos] = 0
            self.pos += 1
            if self.pos == _RATE:
                self._run_f()
        return bytes(out)


class Transcript:
    """merlin: every message framed by its label and length."""

    def __init__(self, label: bytes = None):
        if label is not None:
            self.strobe = Strobe(b"Merlin v1.0")
            self.append(b"dom-sep", label)

    def clone(self) -> "Transcript":
        t = Transcript()
        t.strobe = self.strobe.clone()
        return t

    def append(self, label: bytes, message: bytes) -> None:
        s = self.strobe
        s.operate(_F_M | _F_A, label)
        s.operate(_F_M | _F_A, len(message).to_bytes(4, "little"), more=True)
        s.operate(_F_A, message)

    def challenge(self, label: bytes, n: int) -> bytes:
        s = self.strobe
        s.operate(_F_M | _F_A, label)
        s.operate(_F_M | _F_A, n.to_bytes(4, "little"), more=True)
        return s.operate(_F_I | _F_A | _F_C, squeeze=n)


# --- edwards25519 and ristretto255 (RFC 9496) --------------------------------

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = -121665 * pow(121666, -1, P) % P
D2 = 2 * D % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
# the base point: y = 4/5, x the even root
_BY = 4 * pow(5, -1, P) % P
Point = Tuple[int, int, int, int]          # extended (X, Y, Z, T), a = -1
ZERO: Point = (0, 1, 1, 0)


def _sqrt_ratio_m1(u: int, v: int) -> Tuple[bool, int]:
    """RFC 9496 4.2: (u/v was a square, the non-negative root of u/v or
    of i*u/v)."""
    v3 = v * v % P * v % P
    v7 = v3 * v3 % P * v % P
    r = u * v3 % P * pow(u * v7 % P, (P - 5) // 8, P) % P
    check = v * r % P * r % P
    correct = check == u % P
    flipped = check == -u % P
    flipped_i = check == -u * SQRT_M1 % P
    if flipped or flipped_i:
        r = r * SQRT_M1 % P
    if r & 1:
        r = P - r
    return correct or flipped, r


_BX = _sqrt_ratio_m1((_BY * _BY - 1) % P, (D * _BY * _BY + 1) % P)[1]
BASE: Point = (_BX, _BY, 1, _BX * _BY % P)
INVSQRT_A_MINUS_D = _sqrt_ratio_m1(1, (-1 - D) % P)[1]


def pt_add(p: Point, q: Point) -> Point:
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = t1 * D2 % P * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def pt_double(p: Point) -> Point:
    x1, y1, z1, _t = p
    a = x1 * x1 % P
    b = y1 * y1 % P
    c = 2 * z1 * z1 % P
    e = ((x1 + y1) * (x1 + y1) - a - b) % P
    g = b - a
    f = g - c
    h = -a - b
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def pt_neg(p: Point) -> Point:
    return (-p[0] % P, p[1], p[2], -p[3] % P)


def pt_mul(k: int, p: Point) -> Point:
    """4-bit windows, most significant first."""
    row = [ZERO, p]
    for _ in range(14):
        row.append(pt_add(row[-1], p))
    acc = ZERO
    for shift in range(252, -1, -4):
        if acc is not ZERO:
            acc = pt_double(pt_double(pt_double(pt_double(acc))))
        digit = k >> shift & 15
        if digit:
            acc = pt_add(acc, row[digit])
    return acc


def _base_table() -> List[List[Point]]:
    """[window][digit - 1] = digit * 256^window * B."""
    table, start = [], BASE
    for _w in range(32):
        row = [start]
        for _ in range(254):
            row.append(pt_add(row[-1], start))
        table.append(row)
        start = pt_add(row[-1], start)
    return table


_BASE_TABLE = _base_table()


def base_mul(k: int) -> Point:
    """k * B from the table of the base point's multiples: one addition
    a byte of the scalar."""
    acc = ZERO
    for w in range(32):
        digit = k >> 8 * w & 255
        if digit:
            acc = pt_add(acc, _BASE_TABLE[w][digit - 1])
    return acc


def ristretto_decode(s: bytes) -> Optional[Point]:
    """RFC 9496 4.3.1. None for what is no canonical encoding."""
    if len(s) != 32:
        return None
    v0 = int.from_bytes(s, "little")
    if v0 >= P or v0 & 1:
        return None
    ss = v0 * v0 % P
    u1, u2 = (1 - ss) % P, (1 + ss) % P
    u2_sqr = u2 * u2 % P
    v = (-(D * u1 % P * u1) - u2_sqr) % P
    was_square, invsqrt = _sqrt_ratio_m1(1, v * u2_sqr % P)
    den_x = invsqrt * u2 % P
    den_y = invsqrt * den_x % P * v % P
    x = 2 * v0 * den_x % P
    if x & 1:
        x = P - x
    y = u1 * den_y % P
    t = x * y % P
    if not was_square or t & 1 or y == 0:
        return None
    return (x, y, 1, t)


def ristretto_encode(p: Point) -> bytes:
    """RFC 9496 4.3.2."""
    x0, y0, z0, t0 = p
    u1 = (z0 + y0) * (z0 - y0) % P
    u2 = x0 * y0 % P
    _sq, invsqrt = _sqrt_ratio_m1(1, u1 * u2 % P * u2 % P)
    den1 = invsqrt * u1 % P
    den2 = invsqrt * u2 % P
    z_inv = den1 * den2 % P * t0 % P
    if t0 * z_inv % P & 1:
        x, y = y0 * SQRT_M1 % P, x0 * SQRT_M1 % P
        den_inv = den1 * INVSQRT_A_MINUS_D % P
    else:
        x, y, den_inv = x0 % P, y0 % P, den2
    if x * z_inv % P & 1:
        y = -y % P
    s = den_inv * (z0 - y) % P
    if s & 1:
        s = P - s
    return s.to_bytes(32, "little")


# --- sr25519: schnorrkel over ristretto255 and merlin ------------------------

def _sr_prefix() -> Transcript:
    """go-schnorrkel NewSigningContext([]byte{}, ...) up to the message:
    the part of every transcript that no message changes."""
    t = Transcript(b"SigningContext")
    t.append(b"", b"")
    return t


_SR_PREFIX = _sr_prefix()


def _sr_transcript(msg: bytes, pub: bytes) -> Transcript:
    """The signing context's transcript of ``msg``, then the signature
    protocol's name and the key: what signing and verifying share."""
    t = _SR_PREFIX.clone()
    t.append(b"sign-bytes", msg)
    t.append(b"proto-name", b"Schnorr-sig")
    t.append(b"sign:pk", pub)
    return t


def _sr_challenge(t: Transcript, r_bytes: bytes) -> int:
    t.append(b"sign:R", r_bytes)
    return int.from_bytes(t.challenge(b"sign:c", 64), "little") % L


def sr_expand(mini: bytes) -> Tuple[int, bytes]:
    """schnorrkel MiniSecretKey::expand_ed25519 -> (key scalar, nonce):
    SHA-512, ed25519's clamp, then the cofactor divided out."""
    h = hashlib.sha512(mini).digest()
    key = bytearray(h[:32])
    key[0] &= 248
    key[31] &= 63
    key[31] |= 64
    return int.from_bytes(key, "little") >> 3, h[32:]


def sr_public(mini: bytes) -> bytes:
    return ristretto_encode(base_mul(sr_expand(mini)[0]))


def sr_sign(mini: bytes, pub: bytes, msg: bytes) -> bytes:
    key, nonce = sr_expand(mini)
    t = _sr_transcript(msg, pub)
    r = int.from_bytes(hashlib.sha512(nonce + msg).digest(), "little") % L
    r_bytes = ristretto_encode(base_mul(r))
    s = (_sr_challenge(t, r_bytes) * key + r) % L
    sig = bytearray(r_bytes + s.to_bytes(32, "little"))
    sig[63] |= 0x80                     # schnorrkel's marker
    return bytes(sig)


def sr_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(sig) != 64 or not sig[63] & 0x80:
        return False                    # not marked as schnorrkel's
    s = int.from_bytes(sig[32:], "little") & ~(1 << 255)
    a = ristretto_decode(pub)
    if s >= L or a is None:
        return False
    k = _sr_challenge(_sr_transcript(msg, pub), sig[:32])
    r = pt_add(base_mul(s), pt_mul(k, pt_neg(a)))
    return ristretto_encode(r) == sig[:32]


# --- secp256k1 ---------------------------------------------------------------

K1_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
_K1 = ec.SECP256K1()
_K1_DET = ec.ECDSA(hashes.SHA256(), deterministic_signing=True)
_K1_ALG = ec.ECDSA(hashes.SHA256())


def k1_private(secret: bytes) -> ec.EllipticCurvePrivateKey:
    return ec.derive_private_key(
        int.from_bytes(secret, "big") % (K1_N - 1) + 1, _K1)


def k1_public(priv: ec.EllipticCurvePrivateKey) -> bytes:
    return priv.public_key().public_bytes(
        serialization.Encoding.X962,
        serialization.PublicFormat.CompressedPoint)


def k1_sign(priv: ec.EllipticCurvePrivateKey, msg: bytes) -> bytes:
    r, s = decode_dss_signature(priv.sign(msg, _K1_DET))
    if s > K1_N // 2:
        s = K1_N - s
    return r.to_bytes(32, "big") + s.to_bytes(32, "big")


def k1_equation_holds(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """ECDSA alone, no low-S rule: what shows that a high-S twin is
    refused for its S and for nothing else."""
    r, s = int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:], "big")
    try:
        ec.EllipticCurvePublicKey.from_encoded_point(_K1, pub).verify(
            encode_dss_signature(r, s), msg, _K1_ALG)
    except (InvalidSignature, ValueError):
        return False
    return True


def k1_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(sig) != 64:
        return False
    r, s = int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:], "big")
    if s > K1_N // 2:           # secp256k1.go:195-197, before the equation
        return False
    return 0 < r < K1_N and 0 < s and k1_equation_holds(pub, msg, sig)


def k1_high_s_twin(sig: bytes) -> bytes:
    """(r, n - s): the ECDSA equation holds for it as for (r, s)."""
    s = K1_N - int.from_bytes(sig[32:], "big")
    return sig[:32] + s.to_bytes(32, "big")


def _rol32(v: int, n: int) -> int:
    return (v << n | v >> (32 - n)) & 0xFFFFFFFF


_RMD_R = (
    0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15,
    7, 4, 13, 1, 10, 6, 15, 3, 12, 0, 9, 5, 2, 14, 11, 8,
    3, 10, 14, 4, 9, 15, 8, 1, 2, 7, 0, 6, 13, 11, 5, 12,
    1, 9, 11, 10, 0, 8, 12, 4, 13, 3, 7, 15, 14, 5, 6, 2,
    4, 0, 5, 9, 7, 12, 2, 10, 14, 1, 3, 8, 11, 6, 15, 13)
_RMD_RP = (
    5, 14, 7, 0, 9, 2, 11, 4, 13, 6, 15, 8, 1, 10, 3, 12,
    6, 11, 3, 7, 0, 13, 5, 10, 14, 15, 8, 12, 4, 9, 1, 2,
    15, 5, 1, 3, 7, 14, 6, 9, 11, 8, 12, 2, 10, 0, 4, 13,
    8, 6, 4, 1, 3, 11, 15, 0, 5, 12, 2, 13, 9, 7, 10, 14,
    12, 15, 10, 4, 1, 5, 8, 7, 6, 2, 13, 14, 0, 3, 9, 11)
_RMD_S = (
    11, 14, 15, 12, 5, 8, 7, 9, 11, 13, 14, 15, 6, 7, 9, 8,
    7, 6, 8, 13, 11, 9, 7, 15, 7, 12, 15, 9, 11, 7, 13, 12,
    11, 13, 6, 7, 14, 9, 13, 15, 14, 8, 13, 6, 5, 12, 7, 5,
    11, 12, 14, 15, 14, 15, 9, 8, 9, 14, 5, 6, 8, 6, 5, 12,
    9, 15, 5, 11, 6, 8, 13, 12, 5, 12, 13, 14, 11, 8, 5, 6)
_RMD_SP = (
    8, 9, 9, 11, 13, 15, 15, 5, 7, 7, 8, 11, 14, 14, 12, 6,
    9, 13, 15, 7, 12, 8, 9, 11, 7, 7, 12, 7, 6, 15, 13, 11,
    9, 7, 15, 11, 8, 6, 6, 14, 12, 13, 5, 14, 13, 13, 7, 5,
    15, 5, 8, 11, 14, 14, 6, 14, 6, 9, 12, 9, 12, 5, 15, 8,
    8, 5, 12, 9, 12, 5, 14, 6, 8, 13, 6, 5, 15, 13, 11, 11)
_RMD_K = (0, 0x5A827999, 0x6ED9EBA1, 0x8F1BBCDC, 0xA953FD4E)
_RMD_KP = (0x50A28BE6, 0x5C4DD124, 0x6D703EF3, 0x7A6D76E9, 0)


def _rmd_f(j: int, x: int, y: int, z: int) -> int:
    if j == 0:
        return x ^ y ^ z
    if j == 1:
        return (x & y) | (~x & z)
    if j == 2:
        return (x | ~y) ^ z
    if j == 3:
        return (x & z) | (y & ~z)
    return x ^ (y | ~z)


def _ripemd160_plain(data: bytes) -> bytes:
    """RIPEMD-160 (Dobbertin, Bosselaers, Preneel 1996), for a host whose
    OpenSSL keeps it in the legacy provider."""
    h = [0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476, 0xC3D2E1F0]
    msg = data + b"\x80" + bytes(-(len(data) + 9) % 64) \
        + (8 * len(data)).to_bytes(8, "little")
    for off in range(0, len(msg), 64):
        x = [int.from_bytes(msg[off + 4 * i:off + 4 * i + 4], "little")
             for i in range(16)]
        a, b, c, d, e = h
        ap, bp, cp, dp, ep = h
        for j in range(80):
            rnd = j // 16
            t = _rol32((a + _rmd_f(rnd, b, c, d) + x[_RMD_R[j]]
                        + _RMD_K[rnd]) & 0xFFFFFFFF, _RMD_S[j]) + e
            a, e, d, c, b = e, d, _rol32(c, 10), b, t & 0xFFFFFFFF
            t = _rol32((ap + _rmd_f(4 - rnd, bp, cp, dp) + x[_RMD_RP[j]]
                        + _RMD_KP[rnd]) & 0xFFFFFFFF, _RMD_SP[j]) + ep
            ap, ep, dp, cp, bp = ep, dp, _rol32(cp, 10), bp, t & 0xFFFFFFFF
        t = (h[1] + c + dp) & 0xFFFFFFFF
        h[1] = (h[2] + d + ep) & 0xFFFFFFFF
        h[2] = (h[3] + e + ap) & 0xFFFFFFFF
        h[3] = (h[4] + a + bp) & 0xFFFFFFFF
        h[4] = (h[0] + b + cp) & 0xFFFFFFFF
        h[0] = t
    return b"".join(v.to_bytes(4, "little") for v in h)


def ripemd160(data: bytes) -> bytes:
    try:
        return hashlib.new("ripemd160", data).digest()
    except ValueError:
        return _ripemd160_plain(data)


# --- keys, the set -----------------------------------------------------------

def _secret(seed: int, i: int) -> bytes:
    return hashlib.sha256(b"bench-val-%d-%d" % (seed, i)).digest()


def make_key(curve: str, secret: bytes) -> Tuple[object, bytes, bytes]:
    """-> (what signs, public key bytes, address)."""
    if curve == ED25519:
        sk = Ed25519PrivateKey.from_private_bytes(secret)
        pub = sk.public_key().public_bytes_raw()
        return sk, pub, hashlib.sha256(pub).digest()[:20]
    if curve == SR25519:
        pub = sr_public(secret)
        return secret, pub, hashlib.sha256(pub).digest()[:20]
    sk = k1_private(secret)
    pub = k1_public(sk)
    return sk, pub, ripemd160(hashlib.sha256(pub).digest())


def sign(curve: str, priv, pub: bytes, msg: bytes) -> bytes:
    if curve == ED25519:
        return priv.sign(msg)
    if curve == SR25519:
        return sr_sign(priv, pub, msg)
    return k1_sign(priv, msg)


def verify_signature(curve: str, pub: bytes, msg: bytes, sig: bytes) -> bool:
    if curve == ED25519:
        try:
            Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
        except (InvalidSignature, ValueError):
            return False
        return True
    if curve == SR25519:
        return sr_verify(pub, msg, sig)
    return k1_verify(pub, msg, sig)


@dataclass
class ValSet:
    """Validators in the set's own order: voting power descending, then
    address ascending (types/validator_set.go ValidatorsByVotingPower) —
    with equal powers the three key types interleave."""
    curves: List[str]
    privs: List[object]
    pubs: List[bytes]
    addrs: List[bytes]
    powers: List[int]

    @property
    def total_power(self) -> int:
        return sum(self.powers)

    def slots_of(self, curve: str) -> List[int]:
        return [i for i, c in enumerate(self.curves) if c == curve]


def make_valset(seed: int, n: int, power: int = 1) -> ValSet:
    rows = []
    for i in range(n):
        curve = CURVES[i % 3]
        priv, pub, addr = make_key(curve, _secret(seed, i))
        rows.append((addr, curve, priv, pub))
    rows.sort(key=lambda r: r[0])
    return ValSet([r[1] for r in rows], [r[2] for r in rows],
                  [r[3] for r in rows], [r[0] for r in rows], [power] * n)


# --- commits -----------------------------------------------------------------

def absent_by_curve(n_absent: int) -> Dict[str, int]:
    """``n_absent`` slots shared over the key types as evenly as whole
    numbers allow, the earlier type taking the odd one."""
    return {c: n_absent // 3 + (k < n_absent % 3)
            for k, c in enumerate(CURVES)}


def plan_commit(vals: ValSet, seed: int, k: int, chain_id: str,
                absent: Dict[str, int], n_nil: int = 0) -> CommitData:
    """Commit number ``k`` of a seed with no signature made yet. The seed
    chooses WHICH validators of each key type are absent (and which of
    the rest vote nil), never how many."""
    rng = random.Random(seed * 1_000_003 + k)
    out = set()
    for curve in CURVES:
        out.update(rng.sample(vals.slots_of(curve), absent[curve]))
    nil = set(rng.sample([i for i in range(len(vals.pubs)) if i not in out],
                         n_nil))
    base = 1_700_000_000 * 10**9 + k * 10**9
    return CommitData(
        chain_id, 1_000 + k, 0,
        hashlib.sha256(b"bench-block-%d-%d" % (seed, k)).digest(), 1,
        hashlib.sha256(b"bench-parts-%d-%d" % (seed, k)).digest(),
        [(ABSENT, 0, b"") if i in out else
         (NIL if i in nil else COMMIT, base + i, b"")
         for i in range(len(vals.pubs))])


def present_slots(c: CommitData) -> List[int]:
    return [i for i, (f, _t, _s) in enumerate(c.sigs) if f != ABSENT]


def present_by_curve(vals: ValSet, c: CommitData) -> Dict[str, int]:
    out = dict.fromkeys(CURVES, 0)
    for i in present_slots(c):
        out[vals.curves[i]] += 1
    return out


# What a signer or a verifier needs of a commit: what its votes share, and
# (slot, flag, timestamp, signature) of the slots it is given.
Head = Tuple[str, int, int, bytes, int, bytes]
Share = List[Tuple[int, int, int, bytes]]


def _head(c: CommitData) -> Head:
    return (c.chain_id, c.height, c.round, c.block_hash, c.parts_total,
            c.parts_hash)


def _share(c: CommitData, slots: Sequence[int]) -> Share:
    return [(i,) + c.sigs[i] for i in slots]


def sign_share(vals: ValSet, head: Head, share: Share
               ) -> List[Tuple[int, bytes]]:
    return [(i, sign(vals.curves[i], vals.privs[i], vals.pubs[i],
                     vote_sign_bytes(*head, ts, nil=flag == NIL)))
            for i, flag, ts, _sig in share]


def bad_in_share(vals: ValSet, head: Head, share: Share, trust: str = ""
                 ) -> List[int]:
    """The slots whose signature the serial reference refuses. ``trust``
    is the CONTROL, not the reference: every signature of that key type
    is taken for good unlooked at."""
    return [i for i, flag, ts, sig in share if vals.curves[i] != trust
            and not verify_signature(
                vals.curves[i], vals.pubs[i],
                vote_sign_bytes(*head, ts, nil=flag == NIL), sig)]


def outcome(vals: ValSet, c: CommitData, bad: Sequence[int]) -> Outcome:
    """validator_set.go:667 once every present signature has been looked
    at: the first refused lane (counted over the present signatures), or
    the tally of the votes for the block against 2/3 of the total."""
    if bad:
        return ("bad_sig", sum(1 for f, _t, _s in c.sigs[:min(bad)]
                               if f != ABSENT))
    needed = vals.total_power * 2 // 3
    tallied = sum(pw for pw, (f, _t, _s) in zip(vals.powers, c.sigs)
                  if f == COMMIT)
    if tallied <= needed:
        return ("low_power", tallied, needed)
    return ("ok",)


def _fill(c: CommitData, signed: List[Tuple[int, bytes]]) -> None:
    for i, sig in signed:
        c.sigs[i] = (c.sigs[i][0], c.sigs[i][1], sig)


def make_commit(vals: ValSet, seed: int, k: int, chain_id: str,
                absent: Dict[str, int], n_nil: int = 0) -> CommitData:
    c = plan_commit(vals, seed, k, chain_id, absent, n_nil)
    _fill(c, sign_share(vals, _head(c), _share(c, present_slots(c))))
    return c


def verify_commit(vals: ValSet, c: CommitData, trust: str = "") -> Outcome:
    """The plain serial reference: every signature that is present is
    verified, power is tallied over the votes for the block, and more
    than 2/3 of the total has to be there."""
    return outcome(vals, c, bad_in_share(
        vals, _head(c), _share(c, present_slots(c)), trust))


def replace_signature(c: CommitData, idx: int, sig: bytes) -> CommitData:
    sigs = list(c.sigs)
    sigs[idx] = (sigs[idx][0], sigs[idx][1], sig)
    return CommitData(c.chain_id, c.height, c.round, c.block_hash,
                      c.parts_total, c.parts_hash, sigs)


# --- the workers -------------------------------------------------------------

_WORKER: dict = {}


def _worker_init(seed: int, n: int, power: int) -> None:
    _WORKER["vals"] = make_valset(seed, n, power)


def _worker_sign(job) -> List[Tuple[int, bytes]]:
    return sign_share(_WORKER["vals"], *job)


def _worker_bad(job) -> List[int]:
    return bad_in_share(_WORKER["vals"], *job)


class Pool:
    """``workers`` processes that each make and hold the set's keys, or
    this process when ``workers`` is 1. A commit's slots are dealt round
    the jobs, so every job holds the three key types alike."""

    def __init__(self, seed: int, n: int, power: int, workers: int):
        self.workers = max(1, workers)
        self.pool = None
        if self.workers > 1:
            self.pool = ProcessPoolExecutor(
                self.workers,
                mp_context=multiprocessing.get_context("spawn"),
                initializer=_worker_init, initargs=(seed, n, power))
        else:
            _worker_init(seed, n, power)

    def _jobs(self, commits: List[CommitData], *more) -> list:
        per = 2 * self.workers          # short jobs: no worker idles long
        return [(c, (_head(c), _share(c, slots[k::per])) + more)
                for c in commits for slots in [present_slots(c)]
                for k in range(per) if slots[k::per]]

    def _submit(self, fn, jobs: list) -> List[Future]:
        if self.pool is not None:
            return [self.pool.submit(fn, job) for _c, job in jobs]
        done = [Future() for _ in jobs]
        for future, (_c, job) in zip(done, jobs):
            future.set_result(fn(job))
        return done

    def sign_commits(self, commits: List[CommitData]):
        """Starts signing every present slot of ``commits``; the call of
        what it returns waits for the signatures and fills them in."""
        jobs = self._jobs(commits)
        pending = self._submit(_worker_sign, jobs)

        def wait() -> None:
            for (c, _job), done in zip(jobs, pending):
                _fill(c, done.result())
        return wait

    def verify_commits(self, vals: ValSet, commits: List[CommitData],
                       trust: str = "") -> List[Outcome]:
        jobs = self._jobs(commits, trust)
        bad: Dict[int, List[int]] = {id(c): [] for c in commits}
        for (c, _job), done in zip(jobs, self._submit(_worker_bad, jobs)):
            bad[id(c)] += done.result()
        return [outcome(vals, c, bad[id(c)]) for c in commits]

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown(cancel_futures=True)

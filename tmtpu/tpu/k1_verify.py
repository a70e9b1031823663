"""Batched secp256k1 ECDSA verification on TPU.

Reference semantics: crypto/secp256k1/secp256k1.go:195-197 (btcec Verify of
64-byte R||S low-S signatures over SHA-256(msg)); the serial oracle here is
tmtpu.crypto.secp256k1.PubKeySecp256k1.verify_signature. This completes the
BASELINE.md curve set (ed25519 — tmtpu.tpu.verify; sr25519 —
tmtpu.tpu.sr_verify; secp256k1 — this module) so mixed-curve valsets batch
every lane onto the device.

secp256k1 is short-Weierstrass (y^2 = x^3 + 7) over a different prime than
the 25519 curves, so this module pairs its own field (tmtpu.tpu.fe_k1) with
the *complete* projective addition formulas of Renes–Costello–Batina 2016
(algorithm 7, a = 0, b3 = 21): one formula valid for every input pair —
identity, doubling, inverses — which is what a SIMD batch needs, exactly as
the unified Edwards formulas are for ed25519 (tmtpu.tpu.curve).

Split of labor:
- **host**: signature parsing (r, s in [1, n-1], low-S), SHA-256 digests
  (C-speed via hashlib over the batch), the mod-n scalar work
  u1 = h/s, u2 = r/s (Python bigints per lane — mod-n inversion has no
  13-bit-limb-friendly shape and is ~2 µs/lane), and the canonical-x
  candidates r, r+n for the final comparison;
- **device**: pubkey decompression (sqrt via one (p+1)/4 power chain),
  the Straus/Shamir ladder R = [u1]G + [u2]Q over 64 4-bit windows, and
  the projective check x(R) ≡ r (mod n) — i.e. X == r*Z or (when
  r + n < p, probability ~2^-127) X == (r+n)*Z, with R != infinity.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from tmtpu.crypto.secp256k1 import N
from tmtpu.tpu import fe_k1 as fe
from tmtpu.tpu.verify import lanes_as_arrays, lt_le

P = fe.P_INT
B3 = 21  # 3*b for y^2 = x^3 + 7
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

WINDOW = 4
NDIGITS = 64

SEVEN_LIMBS = fe.limbs_of_int(7)


def _const(limbs):
    return jnp.asarray(limbs)[:, None]


# ---------------------------------------------------------------------------
# Complete projective point ops (RCB16 algorithm 7, a = 0).


def identity(batch_shape):
    z = jnp.zeros((fe.NLIMBS,) + tuple(batch_shape), dtype=jnp.int32)
    one = jnp.concatenate(
        [jnp.ones((1,) + tuple(batch_shape), dtype=jnp.int32), z[1:]], axis=0
    )
    return (z, one, z)


def add(p, q):
    """Complete addition: valid for ALL input pairs (including P+P, P+(-P),
    identity operands) — 12 muls + 2 small-constant muls. Validated against
    the affine oracle in tests/test_tpu_k1.py."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    t0 = fe.mul(X1, X2)
    t1 = fe.mul(Y1, Y2)
    t2 = fe.mul(Z1, Z2)
    t3 = fe.sub(fe.mul(fe.add(X1, Y1), fe.add(X2, Y2)), fe.add(t0, t1))
    t4 = fe.sub(fe.mul(fe.add(Y1, Z1), fe.add(Y2, Z2)), fe.add(t1, t2))
    y3 = fe.sub(fe.mul(fe.add(X1, Z1), fe.add(X2, Z2)), fe.add(t0, t2))
    t0 = fe.mul_small(t0, 3)  # 3 X1X2  (a = 0)
    t2 = fe.mul_small(t2, B3)  # b3 Z1Z2
    z3 = fe.add(t1, t2)  # Y1Y2 + b3 Z1Z2
    t1 = fe.sub(t1, t2)  # Y1Y2 - b3 Z1Z2
    y3 = fe.mul_small(y3, B3)  # b3 (X1Z2 + X2Z1)
    x3 = fe.sub(fe.mul(t3, t1), fe.mul(t4, y3))
    y3 = fe.add(fe.mul(y3, t0), fe.mul(t1, z3))
    z3 = fe.add(fe.mul(z3, t4), fe.mul(t0, t3))
    return (x3, y3, z3)


def double(p):
    """Complete doubling (RCB16 algorithm 9, a = 0): 6 muls + 2 squarings +
    1 small-constant mul — vs 12 + 2 for ``add(p, p)``. Exception-free for
    every curve point including the identity (traced: (0,1,0) -> (0,1,0));
    secp256k1 has no order-2 points (prime group order), so y = 0 never
    occurs on valid inputs. Validated against ``add(p, p)`` and the affine
    oracle in tests/test_tpu_k1.py."""
    X, Y, Z = p
    t0 = fe.sq(Y)
    z3 = fe.add(t0, t0)
    z3 = fe.add(z3, z3)
    z3 = fe.add(z3, z3)  # 8 Y^2
    t1 = fe.mul(Y, Z)
    t2 = fe.mul_small(fe.sq(Z), B3)  # b3 Z^2
    x3 = fe.mul(t2, z3)
    y3 = fe.add(t0, t2)
    z3 = fe.mul(t1, z3)
    t1 = fe.add(t2, t2)
    t2 = fe.add(t1, t2)
    t0 = fe.sub(t0, t2)
    y3 = fe.add(x3, fe.mul(t0, y3))
    t1 = fe.mul(X, Y)
    x3 = fe.mul(t0, t1)
    x3 = fe.add(x3, x3)
    return (x3, y3, z3)


def negate(p):
    X, Y, Z = p
    return (X, fe.neg(Y), Z)


# ---------------------------------------------------------------------------
# Window tables (mirrors tmtpu.tpu.curve, with 3-component projective rows).


def _affine_mult(k: int):
    """Host oracle: k*G affine via RCB over Python ints (exercised against
    the 'cryptography' library in tests)."""

    def aff_add(a, b):
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

    acc = None
    g = (GX, GY)
    for _ in range(k):
        acc = aff_add(acc, g)
    return acc


def fixed_base_table() -> np.ndarray:
    """[16, 3, 20] int32: projective (X, Y, Z) of d*G for d in 0..15
    (identity (0,1,0) at d=0, affine Z=1 otherwise)."""
    rows = []
    for d in range(1 << WINDOW):
        if d == 0:
            x, y, z = 0, 1, 0
        else:
            x, y = _affine_mult(d)
            z = 1
        rows.append(
            np.stack(
                [fe.limbs_of_int(x), fe.limbs_of_int(y), fe.limbs_of_int(z)]
            )
        )
    return np.stack(rows)


def lookup_const(table_f32, digits):
    """[16, 3, 20] f32 table, [B] digits -> ([20, B] x3) via one-hot matmul
    (limbs < 2^13 are exact in f32; HIGHEST avoids bf16 truncation)."""
    oh = jax.nn.one_hot(digits, 1 << WINDOW, dtype=jnp.float32)  # [B, 16]
    flat = table_f32.reshape(1 << WINDOW, -1)
    sel = jnp.matmul(oh, flat, precision=jax.lax.Precision.HIGHEST)
    sel = sel.astype(jnp.int32).T.reshape(3, fe.NLIMBS, -1)
    return (sel[0], sel[1], sel[2])


def build_lane_table(q):
    """Per-lane window table [16, 3, 20, B]: d*Q for d in 0..15, built with
    15 complete adds under lax.scan (compile-size friendly)."""
    B = q[0].shape[1:]
    ident = identity(B)

    def step(acc, _):
        nxt = add(acc, q)
        return nxt, jnp.stack(nxt)

    _, rest = jax.lax.scan(step, q, None, length=(1 << WINDOW) - 2)
    head = jnp.stack([jnp.stack(ident), jnp.stack(q)])
    return jnp.concatenate([head, rest])


def lookup_lane(table_f32, digits):
    oh = jax.nn.one_hot(digits, 1 << WINDOW, dtype=jnp.float32, axis=0)
    sel = jnp.einsum(
        "tclb,tb->clb", table_f32, oh, precision=jax.lax.Precision.HIGHEST
    ).astype(jnp.int32)
    return (sel[0], sel[1], sel[2])


def shamir_double_scalar(u1_digits, u2_digits, q_point, base_table_f32):
    """[u1]G + [u2]Q per lane, MSB-first 4-bit windows — the Weierstrass
    twin of tmtpu.tpu.curve.shamir_double_scalar (doublings shared across
    both scalars, via the dedicated complete doubling)."""
    lane_table = build_lane_table(q_point).astype(jnp.float32)
    batch = q_point[0].shape[1:]

    def body(w, p):
        for _ in range(WINDOW):
            p = double(p)
        d1 = jax.lax.dynamic_index_in_dim(u1_digits, w, 0, keepdims=False)
        d2 = jax.lax.dynamic_index_in_dim(u2_digits, w, 0, keepdims=False)
        p = add(p, lookup_const(base_table_f32, d1))
        p = add(p, lookup_lane(lane_table, d2))
        return p

    return jax.lax.fori_loop(0, NDIGITS, body, identity(batch))


# ---------------------------------------------------------------------------
# Decompression + the verify graph.


def decompress(x, parity):
    """SEC1 point decompression: x [20, B] canonical limbs (host-checked
    < p), parity [B] in {0,1} (0x02 prefix -> even y). Returns
    ((x, y, 1), valid): y = sqrt(x^3 + 7) with the requested parity;
    invalid where x^3 + 7 is a non-residue."""
    y2 = fe.add(fe.mul(fe.sq(x), x), _const(SEVEN_LIMBS))
    y = fe.sqrt_candidate(y2)
    yf = fe.freeze(y)
    valid = jnp.all(fe.freeze(fe.sq(y)) == fe.freeze(y2), axis=0)
    flip = (yf[0] & 1) != parity
    y = jnp.where(flip[None], fe.neg(yf), yf)
    one = jnp.zeros_like(x).at[0].add(1)
    return (x, y, one), valid


def digits_msb_device_be(s_bytes):
    """DEVICE [32, B] big-endian scalar bytes -> [64, B] int32 4-bit
    windows, most-significant first (big-endian twin of
    tmtpu.tpu.verify.digits_msb_device)."""
    s = s_bytes.astype(jnp.int32)
    hi = s >> 4
    lo = s & 0x0F
    return jnp.stack([hi, lo], axis=1).reshape((64,) + s.shape[1:])


def verify_core_compact(pkx_b, parity, u1_b, u2_b, r_b, rpn_b, base_table):
    """The jittable device graph: raw byte columns in, mask out.

    pkx_b: [32, B] uint8 big-endian pubkey x (host-checked < p);
    parity: [B] int32 (compressed-prefix parity bit);
    u1_b, u2_b: [32, B] uint8 big-endian scalars h/s, r/s mod n;
    r_b: [32, B] uint8 big-endian r (as a field element, r < n < p);
    rpn_b: [32, B] uint8 big-endian second x-candidate — r+n when
    r + n < p, else a copy of r (a harmless duplicate check).
    Returns bool [B]: pubkey decodes AND R = [u1]G + [u2]Q is finite with
    x(R) mod n == r."""
    q_pt, q_ok = decompress(fe.pack_bytes_device(pkx_b), parity)
    r_pt = shamir_double_scalar(
        digits_msb_device_be(u1_b), digits_msb_device_be(u2_b),
        q_pt, base_table,
    )
    X, _, Z = r_pt
    zf = fe.freeze(Z)
    finite = ~jnp.all(zf == 0, axis=0)
    xf = fe.freeze(X)
    r_l = fe.pack_bytes_device(r_b)
    rpn_l = fe.pack_bytes_device(rpn_b)
    m1 = jnp.all(xf == fe.freeze(fe.mul(r_l, Z)), axis=0)
    m2 = jnp.all(xf == fe.freeze(fe.mul(rpn_l, Z)), axis=0)
    return q_ok & finite & (m1 | m2)


# ---------------------------------------------------------------------------
# Host-side preparation.

_P_BE = np.frombuffer(int.to_bytes(P, 32, "big"), dtype=np.uint8)
_N_BE = np.frombuffer(int.to_bytes(N, 32, "big"), dtype=np.uint8)
_HALF_N1_BE = np.frombuffer(
    int.to_bytes(N // 2 + 1, 32, "big"), dtype=np.uint8)
_DUMMY_SCALAR = int.to_bytes(1, 32, "big")


def _lt_be(arr: np.ndarray, bound_be: np.ndarray) -> np.ndarray:
    """arr < bound lexicographically over big-endian [B, 32] rows
    (little-endian helper reversed)."""
    return lt_le(arr[:, ::-1], bound_be[::-1].copy())


def prepare_k1_batch_packed(pks, msgs, sigs, padded: int = 0):
    """Host prep, packed form: (numpy [168, max(B, padded)] uint8, lanes
    B.. replicating lane 0, and host_ok [B]). Host rejects wrong lengths,
    bad SEC1 prefixes, r/s out of [1, n-1], and non-low-S (s > n/2) —
    matching the serial path's checks before any curve math."""
    B = len(sigs)
    len_ok, pk_arr, sig_arr = lanes_as_arrays(pks, sigs, 33)
    r_arr = sig_arr[:, :32].copy()
    s_arr = sig_arr[:, 32:]
    prefix = pk_arr[:, 0]
    pkx = pk_arr[:, 1:].copy()
    nonzero_r = r_arr.any(axis=1)
    nonzero_s = s_arr.any(axis=1)
    host_ok = (
        len_ok
        & ((prefix == 2) | (prefix == 3))
        & _lt_be(pkx, _P_BE)
        & nonzero_r & _lt_be(r_arr, _N_BE)
        & nonzero_s & _lt_be(s_arr, _HALF_N1_BE)  # s <= n/2 (low-S)
    )
    # scalar work per lane (Python bigints): w = s^-1, u1 = h*w, u2 = r*w.
    # The n inversions fold into ONE via Montgomery's batch-inversion
    # trick (prefix products + a single pow(-1) + backward sweep): 9 ms
    # vs 103 ms per 4096 lanes — host prep would otherwise bottleneck the
    # fused kernel's device rate on this single-core host.
    ok_idx = [i for i in range(B) if host_ok[i]]
    svals = [int.from_bytes(s_arr[i], "big") for i in ok_idx]
    w_of = {}
    if svals:
        prefix = [0] * len(svals)
        acc = 1
        for j, s in enumerate(svals):
            prefix[j] = acc
            acc = acc * s % N
        inv_acc = pow(acc, -1, N)
        for j in range(len(svals) - 1, -1, -1):
            w_of[ok_idx[j]] = inv_acc * prefix[j] % N
            inv_acc = inv_acc * svals[j] % N
    u1_list, u2_list, rpn_list = [], [], []
    for i in range(B):
        if not host_ok[i]:
            u1_list.append(_DUMMY_SCALAR)
            u2_list.append(_DUMMY_SCALAR)
            rpn_list.append(_DUMMY_SCALAR)
            continue
        r = int.from_bytes(r_arr[i], "big")
        h = int.from_bytes(hashlib.sha256(bytes(msgs[i])).digest(), "big")
        w = w_of[i]
        u1_list.append((h * w % N).to_bytes(32, "big"))
        u2_list.append((r * w % N).to_bytes(32, "big"))
        rpn = r + N
        rpn_list.append((rpn if rpn < P else r).to_bytes(32, "big"))
    if not host_ok.all():
        bad = ~host_ok
        pkx[bad] = 0
        r_arr[bad] = np.frombuffer(_DUMMY_SCALAR, dtype=np.uint8)
    u1_arr = np.frombuffer(b"".join(u1_list), dtype=np.uint8).reshape(B, 32)
    u2_arr = np.frombuffer(b"".join(u2_list), dtype=np.uint8).reshape(B, 32)
    rpn_arr = np.frombuffer(b"".join(rpn_list), dtype=np.uint8).reshape(B, 32)
    parity = (pk_arr[:, 0] & 1).astype(np.uint8)
    # ONE [168, B] host plane: 5 byte planes + the parity row (+7 zero
    # rows to an 8-multiple) — single H2D transfer, split on device
    # (see verify.prepare_batch_packed)
    packed = np.zeros((168, max(B, padded)), dtype=np.uint8)
    for row, arr in enumerate((pkx, u1_arr, u2_arr, r_arr, rpn_arr)):
        packed[32 * row:32 * row + 32, :B] = arr.T
    packed[160, :B] = parity
    packed[:, B:] = packed[:, :1]
    return packed, host_ok


def split_packed_k1(packed):
    """Device-side: [168, B] -> ((pkx, u1, u2, r, rpn) [32, B], parity
    [B] int32)."""
    planes = tuple(packed[32 * i : 32 * (i + 1)] for i in range(5))
    return planes, packed[160].astype(jnp.int32)


def prepare_k1_batch(pks, msgs, sigs):
    """Per-plane form of prepare_k1_batch_packed (tests): ((pkx, u1, u2,
    r, rpn) [32, B] jnp, parity [B] int32, host_ok)."""
    packed, host_ok = prepare_k1_batch_packed(pks, msgs, sigs)
    planes, parity = split_packed_k1(jnp.asarray(packed))
    return planes, parity, host_ok


_BASE_TABLE_F32 = None


def base_table_f32():
    global _BASE_TABLE_F32
    if _BASE_TABLE_F32 is None:
        _BASE_TABLE_F32 = jnp.asarray(fixed_base_table(), dtype=jnp.float32)
    return _BASE_TABLE_F32


@jax.jit
def _k1_verify_compact_jit(pkx_b, parity, u1_b, u2_b, r_b, rpn_b, table):
    return verify_core_compact(pkx_b, parity, u1_b, u2_b, r_b, rpn_b, table)


@jax.jit
def _k1_verify_packed_jit(packed, table):
    """Packed-input twin: ONE [168, B] uint8 H2D transfer, split device-
    side (slices are free under jit)."""
    planes, parity = split_packed_k1(packed)
    return verify_core_compact(planes[0], parity, *planes[1:], table)


@jax.jit
def _k1_kernel_packed_jit(packed):
    from tmtpu.tpu import k1_kernel as kk

    planes, parity = split_packed_k1(packed)
    return kk.k1_verify_compact_kernel(planes[0], parity, *planes[1:])

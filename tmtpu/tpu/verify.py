"""Batched ed25519 signature verification on TPU.

The device graph reproduces, lane-for-lane, the cofactorless Go-stdlib verify
semantics (reference: crypto/ed25519/ed25519.go:148-155; spec oracle:
tmtpu.crypto.ed25519_ref.verify):

    decode A; reject s >= L; h = SHA-512(R || A || msg) mod L;
    R' = [s]B + [h](-A); byte-compare encode(R') against the signature's R.

Split of labor:
- **host** (cheap, data-dependent byte work): length checks, ``s < L``,
  canonical-``y`` check on A, SHA-512 (messages are short and distinct),
  reduction mod L — vectorized numpy / C-backed hashlib;
- **device**: byte->limb unpacking and 4-bit window extraction (raw
  32-byte columns ship over the host link — 128 B/lane), then all the
  field/curve arithmetic (~99% of the FLOPs): point decompression (sqrt
  in GF(p)), the shared-doubling Straus/Shamir ladder [s]B + [h](-A), and
  the byte-exact compressed comparison.

Every device op is elementwise over the trailing batch dimension, so the
whole pipeline shards over a device mesh by splitting lanes (data parallel
over signatures); see tmtpu.tpu.sharding.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from tmtpu.crypto import ed25519_ref as ref
from tmtpu.libs import trace
from tmtpu.tpu import curve, fe

L = ref.L
WINDOW = curve.WINDOW
NDIGITS = curve.NDIGITS

D_LIMBS = fe.limbs_of_int(ref.D)
SQRT_M1_LIMBS = fe.limbs_of_int(ref.SQRT_M1)


def _const(limbs):
    return jnp.asarray(limbs)[:, None]


def decompress(y, sign):
    """Batched point decompression: y limbs [20, B] (canonical, < p —
    guaranteed by the host-side check), sign [B] in {0,1}.

    Returns (extended point, valid mask [B]). Invalid lanes hold a garbage
    point (the complete add formulas never fault on it); callers mask.
    Mirrors ed25519_ref._recover_x.
    """
    one = jnp.zeros_like(y).at[0].add(1)
    y2 = fe.sq(y)
    u = fe.sub(y2, one)  # y^2 - 1
    v = fe.add(fe.mul(_const(D_LIMBS), y2), one)  # d y^2 + 1 (never 0: d non-square)
    v3 = fe.mul(fe.sq(v), v)
    v7 = fe.mul(fe.sq(v3), v)
    x = fe.mul(fe.mul(u, v3), fe.pow_p58(fe.mul(u, v7)))
    vxx = fe.freeze(fe.mul(v, fe.sq(x)))
    u_f = fe.freeze(u)
    nu_f = fe.freeze(fe.neg(u))
    ok_direct = jnp.all(vxx == u_f, axis=0)
    ok_twist = jnp.all(vxx == nu_f, axis=0)
    x = jnp.where(ok_twist[None], fe.mul(x, _const(SQRT_M1_LIMBS)), x)
    valid = ok_direct | ok_twist
    xf = fe.freeze(x)
    x_is_zero = jnp.all(xf == 0, axis=0)
    # x == 0 with sign bit set is not a valid encoding (_recover_x: None).
    valid &= ~(x_is_zero & (sign == 1))
    x = jnp.where(((xf[0] & 1) != sign)[None], fe.neg(x), x)
    z = jnp.zeros_like(y).at[0].add(1)
    return (x, y, z, fe.mul(x, y)), valid


def verify_core(pk_y, pk_sign, r_y, r_sign, s_digits, h_digits, base_table):
    """The jittable device graph: all-curve-arithmetic part of batch verify.

    pk_y, r_y: [20, B] canonical limbs of A's / R's claimed y;
    pk_sign, r_sign: [B] int32 sign bits;
    s_digits, h_digits: [64, B] MSB-first 4-bit windows of s and h;
    base_table: [16, 3, 20] float32 niels table of small multiples of B.

    Returns bool [B]: lanes where A decodes AND encode([s]B + [h](-A)) == R.
    """
    a_point, a_ok = decompress(pk_y, pk_sign)
    r_prime = curve.shamir_double_scalar(
        s_digits, h_digits, curve.negate(a_point), base_table
    )
    return a_ok & curve.compress_check(r_prime, r_y, r_sign)


def digits_msb_device(s_bytes):
    """DEVICE [32, B] scalar bytes (LE) -> [64, B] int32 4-bit windows,
    most-significant first (MSB-first because the Straus ladder consumes
    windows high-to-low)."""
    s = s_bytes.astype(jnp.int32)
    lo = s & 0x0F
    hi = s >> 4
    # interleave LSB-first: window 2i = lo[i], 2i+1 = hi[i]
    inter = jnp.stack([lo, hi], axis=1).reshape((64,) + s.shape[1:])
    return inter[::-1]


def verify_core_compact(pk_b, r_b, s_b, h_b, base_table):
    """Compact-transfer device graph: raw 32-byte columns in, mask out.

    pk_b, r_b, s_b, h_b: [32, B] uint8 — the A and R encodings and the
    s / h scalars exactly as on the wire (128 B/lane vs 848 B/lane for
    pre-unpacked limbs+digits; unpacking is a handful of elementwise ops).
    Host guarantees: s < L, A.y canonical (host_ok covers violators).
    """
    pk_sign = (pk_b[31] >> 7).astype(jnp.int32)
    r_sign = (r_b[31] >> 7).astype(jnp.int32)
    mask_hi = jnp.asarray(0x7F, dtype=pk_b.dtype)
    pk_y = fe.pack_bytes_device(pk_b.at[31].set(pk_b[31] & mask_hi))
    r_y = fe.pack_bytes_device(r_b.at[31].set(r_b[31] & mask_hi))
    return verify_core(pk_y, pk_sign, r_y, r_sign,
                       digits_msb_device(s_b), digits_msb_device(h_b),
                       base_table)


# ---------------------------------------------------------------------------
# Host-side preparation.


_L_LE = np.frombuffer(int.to_bytes(L, 32, "little"), dtype=np.uint8)
_ZERO64 = bytes(64)


def _native_prep(pk_arr, sig_arr, msgs, plane):
    """One C call that writes the flush's lanes into ``plane`` (tmtpu/native
    ``prep_ed25519``: SHA-512 + mod-L + s<L + the four byte planes); None
    when no toolchain is available (callers fall back to the numpy/hashlib
    path below). Disable with TMTPU_NO_NATIVE=1."""
    import os

    if os.environ.get("TMTPU_NO_NATIVE"):
        return None
    try:
        from tmtpu import native
    except Exception:
        return None
    return native.prep_ed25519(pk_arr, sig_arr, msgs, plane)


def lt_le(arr: np.ndarray, bound_le: np.ndarray) -> np.ndarray:
    """Vectorized lexicographic ``arr < bound`` over little-endian [B, 32]
    byte rows (the most significant differing byte decides). Used for the
    canonical-scalar (s < L, Go scMinimal) and canonical-field-element
    (value < p) checks here and in sr_verify."""
    B = arr.shape[0]
    diff = arr != bound_le[None, :]
    idx = 31 - np.argmax(diff[:, ::-1], axis=1)
    rows = np.arange(B)
    return diff.any(axis=1) & (arr[rows, idx] < bound_le[idx])


def _s_below_l(s_arr: np.ndarray) -> np.ndarray:
    return lt_le(s_arr, _L_LE)


def lanes_as_arrays(pks, sigs, key_len: int):
    """A flush's keys and signatures as two contiguous buffers: (len_ok
    bool [B], pk_arr [B, key_len], sig_arr [B, 64]), read-only uint8 views
    of one join each. Lanes are any bytes-like objects; lengths are read in
    one C-level pass a list (no frame a lane), and only where one is off
    are that lane's key and signature swapped for zeros, lane by lane."""
    B = len(sigs)
    len_ok = (np.fromiter(map(len, pks), dtype=np.int64, count=B) == key_len) \
        & (np.fromiter(map(len, sigs), dtype=np.int64, count=B) == 64)
    if not len_ok.all():
        zero_pk = bytes(key_len)
        pks = [p if ok else zero_pk for p, ok in zip(pks, len_ok)]
        sigs = [s if ok else _ZERO64 for s, ok in zip(sigs, len_ok)]
    return (len_ok,
            np.frombuffer(b"".join(pks), dtype=np.uint8).reshape(B, key_len),
            np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(B, 64))


def prepare_batch_packed(pks, msgs, sigs, padded: int = 0):
    """Host prep, packed form: returns (numpy [128, W] uint8, host_ok [B]),
    W = max(B, padded): the operand at the width the device step takes,
    lanes B.. replicating lane 0 (well-formed; their results are
    discarded), built in one pass with no Python statement a lane.

    The four 32-byte planes (pk, r, s, h) are stacked into ONE array so
    the host->device hop is a single transfer: at 128 B/lane a flush is
    about a megabyte, so each hop's fixed cost (a host copy, a DMA
    set-up) outweighs its bytes, and one array also gives the jitted
    step one argument to shard. Output is pure numpy: callers decide
    when the device_put happens (and can overlap it with compute).

    The flow: ``lanes_as_arrays`` (a ``len`` pass and a join each for keys
    and signatures), the same for the messages, then one C call that hashes every
    lane and writes its four fields into the plane's columns (without
    the native library: hashlib a lane, four transposed copies).

    Host-side checks (the ones the device never sees): wrong lengths,
    non-canonical s (>= L), non-canonical A.y (>= p); violating lanes get
    dummy-but-wellformed inputs and are masked via host_ok. No limb/digit
    expansion here — that runs on device (verify_core_compact) — so the
    host does only byte shuffling plus SHA-512 challenge hashing and the
    mod-L reduction."""
    B = len(sigs)
    len_ok, pk_arr, sig_arr = lanes_as_arrays(pks, sigs, 32)
    packed = np.empty((128, max(B, padded)), dtype=np.uint8)
    native = _native_prep(pk_arr, sig_arr, msgs, packed)
    if native is not None:
        s_ok, sha = native
        trace.annotate(impl="native", sha=sha)
    else:
        trace.annotate(impl="python", sha="hashlib")
        s_ok = _s_below_l(sig_arr[:, 32:])
        h_arr = np.frombuffer(
            b"".join(
                int.to_bytes(
                    int.from_bytes(
                        hashlib.sha512(r.tobytes() + p.tobytes()
                                       + bytes(m)).digest(),
                        "little",
                    ) % L,
                    32, "little",
                )
                for r, p, m in zip(sig_arr[:, :32], pk_arr, msgs)
            ),
            dtype=np.uint8,
        ).reshape(B, 32)
        packed[0:32, :B] = pk_arr.T
        packed[32:96, :B] = sig_arr.T
        packed[96:128, :B] = h_arr.T
        if not s_ok.all():
            packed[64:96, :B][:, ~s_ok] = 0
    host_ok = len_ok & s_ok
    # canonicality of A.y (device packs the masked bytes; the check is
    # host's): y >= p is 2^255 - 19 + d, d < 19 — looked for among the one
    # lane in 128 whose top byte allows it
    top = np.flatnonzero((pk_arr[:, 31] & 0x7F) == 0x7F)
    host_ok[top[(pk_arr[top, 0] >= 0xED)
                & (pk_arr[top, 1:31] == 0xFF).all(axis=1)]] = False
    packed[:, B:] = packed[:, :1]
    return packed, host_ok


def split_packed(packed):
    """Device-side: one [128, B] plane -> the four [32, B] byte columns."""
    return packed[0:32], packed[32:64], packed[64:96], packed[96:128]


def prepare_batch_compact(pks, msgs, sigs):
    """Compact host prep: returns ([32, B] uint8 x4 (pk, r, s, h) as jnp
    arrays, host_ok). Thin split over prepare_batch_packed for callers
    that want per-plane arrays (tests, the sharded pjit path whose
    in_shardings are per-plane); the production single-transfer paths use
    the packed form directly."""
    packed, host_ok = prepare_batch_packed(pks, msgs, sigs)
    return tuple(jnp.asarray(p) for p in split_packed(packed)), host_ok


_BASE_TABLE_F32 = None


def base_table_f32():
    global _BASE_TABLE_F32
    if _BASE_TABLE_F32 is None:
        _BASE_TABLE_F32 = jnp.asarray(
            curve.fixed_base_niels_table(), dtype=jnp.float32
        )
    return _BASE_TABLE_F32


@jax.jit
def _verify_compact_jit(pk_b, r_b, s_b, h_b, table):
    return verify_core_compact(pk_b, r_b, s_b, h_b, table)


@jax.jit
def _verify_packed_jit(packed, table):
    return verify_core_compact(*split_packed(packed), table)


@jax.jit
def _verify_packed_kernel_jit(packed):
    from tmtpu.tpu import kernel as tk

    return tk.verify_compact_kernel(*split_packed(packed))

"""Fused Pallas TPU kernel for batched ed25519 verification.

Same per-lane semantics as ``tmtpu.tpu.verify.verify_core_compact`` (the
cofactorless Go-stdlib verify; reference crypto/ed25519/ed25519.go:148-155,
oracle tmtpu.crypto.ed25519_ref.verify), but the entire pipeline — byte
unpack, point decompression, the 64-window Straus/Shamir ladder and the
byte-exact compressed comparison — runs inside ONE Pallas kernel per lane
tile, so the ~3000 field multiplies per signature keep their operands in
VMEM/vector registers instead of round-tripping [20, B] limb arrays through
HBM after every op (which is what bounds the plain-XLA graph: it measures
~22k sig/s on a v5e chip, two orders of magnitude below the VPU's integer
throughput).

Layout: limb arrays are [NLIMBS, T] int32 with the T lanes on the TPU vector
lanes — identical to tmtpu.tpu.fe — so the field/curve routines from
``fe``/``curve`` are reused verbatim inside the kernel. Kernel-specific code
is only what touches refs or needs [1, T]-shaped masks: byte→limb unpack,
the per-lane window-table build/lookup (select chains instead of one-hot
matmuls), decompression and the final compare.

Grid: one program per tile of ``tile`` lanes; programs are independent
(data-parallel over signatures), so the kernel composes with shard_map
lane-sharding across a device mesh unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tmtpu.tpu import curve, fe

NLIMBS = fe.NLIMBS
RADIX = fe.RADIX
WINDOW = curve.WINDOW
NDIGITS = curve.NDIGITS
NTAB = 1 << WINDOW

# Constants plane layout: one [NLIMBS, CONST_COLS] int32 input carries every
# limb-vector constant the kernel needs (Pallas rejects closed-over arrays).
# Columns 0-4: K64P, P_LIMBS, 2d, d, sqrt(-1); columns 16..63: the fixed-base
# niels table (entry d, coord c at column 16 + 3*d + c).
CONST_COLS = 64
_BTAB_COL0 = 16

# default lane-tile per kernel program; batch sizes must be multiples
DEFAULT_TILE = 256

_CONSTS_PLANE = None


def _consts_plane() -> np.ndarray:
    global _CONSTS_PLANE
    if _CONSTS_PLANE is None:
        plane = np.zeros((NLIMBS, CONST_COLS), dtype=np.int32)
        plane[:, 0] = fe.K64P
        plane[:, 1] = fe.P_LIMBS
        plane[:, 2] = curve.D2_LIMBS
        plane[:, 3] = fe.limbs_of_int(curve.ref.D)
        plane[:, 4] = fe.limbs_of_int(curve.ref.SQRT_M1)
        btab = curve.fixed_base_niels_table()  # [16, 3, 20]
        for d in range(NTAB):
            for c in range(3):
                plane[:, _BTAB_COL0 + 3 * d + c] = btab[d, c]
        _CONSTS_PLANE = plane
    return _CONSTS_PLANE


def _unpack_limbs_255(b):
    """[32, T] int32 LE bytes -> [20, T] radix-2^13 limbs of the low 255
    bits (bit 255 — the ed25519 sign bit — is excluded). Each limb spans at
    most 3 bytes, so this is ~6 elementwise row ops per limb."""
    rows = []
    for limb in range(NLIMBS):
        lo_bit = RADIX * limb
        if lo_bit >= 255:
            rows.append(jnp.zeros_like(b[0:1]))
            continue
        hi_bit = min(lo_bit + RADIX, 255)  # exclusive
        nbits = hi_bit - lo_bit
        off = lo_bit & 7
        k0 = lo_bit >> 3
        acc = b[k0 : k0 + 1] >> off
        shift = 8 - off
        k = k0 + 1
        while shift < nbits:
            acc = acc | (b[k : k + 1] << shift)
            shift += 8
            k += 1
        rows.append(acc & ((1 << nbits) - 1))
    return jnp.concatenate(rows, axis=0)


def _digit_rows_msb(b):
    """[32, T] int32 LE scalar bytes -> list of 64 [1, T] 4-bit windows,
    most-significant window first (row w = window 63-w)."""
    rows = []
    for w in range(NDIGITS):
        j = NDIGITS - 1 - w
        byte = b[j // 2 : j // 2 + 1]
        rows.append((byte >> 4) if (j & 1) else (byte & 0x0F))
    return rows


def _row0_one(y):
    """[20, T]-shaped constant 1 (limb vector of the field element 1) —
    concat form; .at[].set lowers to scatter, unsupported in Mosaic."""
    return jnp.concatenate(
        [jnp.ones((1, y.shape[1]), jnp.int32),
         jnp.zeros((NLIMBS - 1, y.shape[1]), jnp.int32)], axis=0)


def _eq_all(a, b):
    """[20, T] x2 -> bool [1, T]: rows equal in every limb. Limbs are
    canonical (< 2^13) so the |diff| sum can't overflow."""
    return jnp.sum(jnp.abs(a - b), axis=0, keepdims=True) == 0


def _decompress(y, sign):
    """Kernel twin of tmtpu.tpu.verify.decompress with [1, T] masks.
    y: [20, T] canonical limbs (host-checked < p), sign: [1, T] in {0,1}."""
    one = _row0_one(y)
    y2 = fe.sq(y)
    u = fe.sub(y2, one)
    v = fe.add(fe.mul(fe.const_col("D", fe.limbs_of_int(curve.ref.D)), y2), one)
    v3 = fe.mul(fe.sq(v), v)
    v7 = fe.mul(fe.sq(v3), v)
    x = fe.mul(fe.mul(u, v3), fe.pow_p58(fe.mul(u, v7)))
    vxx = fe.freeze(fe.mul(v, fe.sq(x)))
    u_f = fe.freeze(u)
    nu_f = fe.freeze(fe.neg(u))
    ok_direct = _eq_all(vxx, u_f)
    ok_twist = _eq_all(vxx, nu_f)
    x = jnp.where(
        ok_twist,
        fe.mul(x, fe.const_col("SQRT_M1", fe.limbs_of_int(curve.ref.SQRT_M1))),
        x,
    )
    valid = ok_direct | ok_twist
    xf = fe.freeze(x)
    x_is_zero = jnp.sum(xf, axis=0, keepdims=True) == 0
    valid &= ~(x_is_zero & (sign == 1))
    x = jnp.where((xf[0:1] & 1) != sign, fe.neg(x), x)
    z = _row0_one(y)
    return (x, y, z, fe.mul(x, y)), valid


def _compress_check(p, y_claim, sign_claim):
    """Kernel twin of curve.compress_check -> bool [1, T]."""
    X, Y, Z, _ = p
    zinv = fe.invert(Z)
    y = fe.freeze(fe.mul(Y, zinv))
    x = fe.freeze(fe.mul(X, zinv))
    return _eq_all(y, y_claim) & ((x[0:1] & 1) == sign_claim)


def _verify_kernel(consts_ref, fc_ref, pk_ref, r_ref, s_ref, h_ref, out_ref,
                   ym_ref, yp_ref, z2_ref, t2_ref, sd_ref, hd_ref,
                   use_dus: bool = True):
    """One lane tile end-to-end. Scratch: the per-lane cached table of
    d*(-A) for d in 0..15 as 4 coordinate planes [16*20, T], plus the two
    MSB-first digit planes [64, T].

    fc_ref carries the five fe-level limb constants pre-replicated to full
    tile width [5*20, T]: narrow [20, 1] constants inside the kernel die in
    Mosaic's layout pass (slice-of-broadcast canonicalizes to a
    2-axis-broadcast of a [1, 1], which has no lowering). consts_ref
    ([20, 64]) still feeds the fixed-base table selects, which never get
    row-sliced."""
    consts = consts_ref[:]
    ctx = {
        "K64P": fc_ref[0 * NLIMBS : 1 * NLIMBS],
        "P_LIMBS": fc_ref[1 * NLIMBS : 2 * NLIMBS],
        "D2": fc_ref[2 * NLIMBS : 3 * NLIMBS],
        "D": fc_ref[3 * NLIMBS : 4 * NLIMBS],
        "SQRT_M1": fc_ref[4 * NLIMBS : 5 * NLIMBS],
        "_dus": use_dus,
    }
    with fe.const_context(ctx):
        _verify_body(consts, pk_ref, r_ref, s_ref, h_ref, out_ref,
                     ym_ref, yp_ref, z2_ref, t2_ref, sd_ref, hd_ref)


def _shamir_ladder(consts, neg_a, tab_refs, d1_ref, d2_ref, T):
    """Shared kernel core: build the per-lane cached window table for -A
    in scratch (entry 0 = identity, entry 1 = -A, then 14 sequential
    adds — each ~8 field muls, unrolled), then run the 64-window
    Straus/Shamir ladder [scalar1]B + [scalar2](-A) with select-chain
    lookups (fixed-base niels from the constants plane; per-lane cached
    from scratch). Returns the extended result."""
    ym_ref, yp_ref, z2_ref, t2_ref = tab_refs
    ident = curve.identity((T,))
    ic = curve.to_cached(ident)
    c1 = curve.to_cached(neg_a)
    for ref_, val in zip(tab_refs, ic):
        ref_[0:NLIMBS] = val
    for ref_, val in zip(tab_refs, c1):
        ref_[NLIMBS : 2 * NLIMBS] = val
    acc = neg_a
    for d in range(2, NTAB):
        acc = curve.add_cached(acc, c1)
        for ref_, val in zip(tab_refs, curve.to_cached(acc)):
            ref_[d * NLIMBS : (d + 1) * NLIMBS] = val

    def lookup_base(dig):
        """dig [1, T] -> niels tuple of [20, T]: select over the 16 table
        columns of the constants plane."""
        sel = [None, None, None]
        for d in range(NTAB):
            m = dig == d
            for c in range(3):
                col = _BTAB_COL0 + 3 * d + c
                const = consts[:, col : col + 1]  # [20, 1]
                sel[c] = (jnp.where(m, const, sel[c])
                          if sel[c] is not None
                          else jnp.broadcast_to(const, (NLIMBS, T)))
        return tuple(sel)

    def lookup_a(dig):
        """dig [1, T] -> cached tuple of [20, T] from the scratch table."""
        outs = []
        for ref_ in tab_refs:
            acc_c = ref_[0:NLIMBS]
            for d in range(1, NTAB):
                acc_c = jnp.where(dig == d,
                                  ref_[d * NLIMBS : (d + 1) * NLIMBS], acc_c)
            outs.append(acc_c)
        return tuple(outs)

    def body(w, p):
        for _ in range(WINDOW):
            p = curve.double(p)
        d1 = d1_ref[pl.ds(w, 1)]
        d2 = d2_ref[pl.ds(w, 1)]
        p = curve.add_niels(p, lookup_base(d1))
        p = curve.add_cached(p, lookup_a(d2))
        return p

    return jax.lax.fori_loop(0, NDIGITS, body, ident)


def _verify_body(consts, pk_ref, r_ref, s_ref, h_ref, out_ref,
                 ym_ref, yp_ref, z2_ref, t2_ref, sd_ref, hd_ref):
    T = pk_ref.shape[1]

    pk_b = pk_ref[:].astype(jnp.int32)
    r_b = r_ref[:].astype(jnp.int32)

    pk_y = _unpack_limbs_255(pk_b)
    r_y = _unpack_limbs_255(r_b)
    pk_sign = pk_b[31:32] >> 7
    r_sign = r_b[31:32] >> 7

    for w, row in enumerate(_digit_rows_msb(s_ref[:].astype(jnp.int32))):
        sd_ref[w : w + 1] = row
    for w, row in enumerate(_digit_rows_msb(h_ref[:].astype(jnp.int32))):
        hd_ref[w : w + 1] = row

    a_point, a_ok = _decompress(pk_y, pk_sign)
    rp = _shamir_ladder(consts, curve.negate(a_point),
                        (ym_ref, yp_ref, z2_ref, t2_ref), sd_ref, hd_ref, T)

    ok = a_ok & _compress_check(rp, r_y, r_sign)
    out_ref[:] = jnp.broadcast_to(ok.astype(jnp.int32), (8, T))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _verify_pallas_jit(pk_b, r_b, s_b, h_b, tile: int, interpret: bool):
    B = pk_b.shape[1]
    grid = (B // tile,)
    spec_in = pl.BlockSpec((32, tile), lambda i: (0, i),
                           memory_space=pltpu.VMEM)
    spec_consts = pl.BlockSpec((NLIMBS, CONST_COLS), lambda i: (0, 0),
                               memory_space=pltpu.VMEM)
    plane = _consts_plane()
    # fe-level constants at full tile width (see _verify_kernel docstring)
    fcols = np.concatenate([plane[:, j] for j in range(5)])  # [5*20]
    fc = jnp.asarray(np.repeat(fcols[:, None], tile, axis=1))
    spec_fc = pl.BlockSpec((5 * NLIMBS, tile), lambda i: (0, 0),
                           memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_verify_kernel, use_dus=not interpret),
        grid=grid,
        in_specs=[spec_consts, spec_fc] + [spec_in] * 4,
        out_specs=pl.BlockSpec((8, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((8, B), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # ym
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # yp
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # z2
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # t2d
            pltpu.VMEM((NDIGITS, tile), jnp.int32),        # s digits
            pltpu.VMEM((NDIGITS, tile), jnp.int32),        # h digits
        ],
        interpret=interpret,
    )(jnp.asarray(plane), fc, pk_b.astype(jnp.int32),
      r_b.astype(jnp.int32), s_b.astype(jnp.int32), h_b.astype(jnp.int32))
    return out[0]


def _default_interpret() -> bool:
    # compiled (Mosaic) on a TPU, interpreted anywhere else; a failing
    # jax.devices() surfaces (same check as dispatch.use_pallas_kernel)
    return jax.devices()[0].platform != "tpu"


def verify_compact_kernel(pk_b, r_b, s_b, h_b, *, tile: int = 256,
                          interpret: bool | None = None):
    """Drop-in twin of verify.verify_core_compact running as one fused
    Pallas kernel. pk_b/r_b/s_b/h_b: [32, B] uint8 device arrays (B a
    multiple of ``tile``; dispatch.padded_lanes sees to it). Returns bool [B]."""
    if interpret is None:
        interpret = _default_interpret()
    return _verify_pallas_jit(pk_b, r_b, s_b, h_b, tile, interpret) != 0


# ---------------------------------------------------------------------------
# sr25519 fused kernel. Same skeleton as the ed25519 kernel — unpack,
# decompress, per-lane window table, the 64-window Straus/Shamir ladder —
# with ristretto255 decompression (SQRT_RATIO_M1, run for BOTH the pubkey
# A and the signature's R) and projective coset equality replacing the
# Edwards decompress/compress-compare. Semantics twin:
# tmtpu.tpu.sr_verify.sr_verify_core_compact (oracle
# tmtpu.crypto.sr25519.PubKeySr25519.verify_signature).

# fc plane columns for the sr kernel (full tile width; see _verify_kernel
# docstring for why narrow constants can't live inside the kernel):
# K64P, P_LIMBS, D2, D, SQRT_M1, NEG_ONE, NEG_SQRT_M1.
_SR_FC_N = 7

_SR_FCOLS = None


def _sr_fcols() -> np.ndarray:
    global _SR_FCOLS
    if _SR_FCOLS is None:
        P = curve.ref.P
        plane = _consts_plane()  # columns 0-4 are the five fe constants
        _SR_FCOLS = np.concatenate(
            [plane[:, j] for j in range(5)]
            + [fe.limbs_of_int(P - 1), fe.limbs_of_int(P - curve.ref.SQRT_M1)]
        )  # [7*20]
    return _SR_FCOLS


def _abs_fe_k(x):
    """CT_ABS with a [1, T] mask: negate iff the canonical form is odd."""
    xf = fe.freeze(x)
    return jnp.where((xf[0:1] & 1) == 1, fe.neg(xf), xf)


def _ristretto_decompress_k(s):
    """Kernel twin of sr_verify.ristretto_decompress: s [20, T] canonical
    limbs (host-checked < p and even). Returns (extended point, valid
    [1, T])."""
    one = _row0_one(s)
    ss = fe.sq(s)
    u1 = fe.sub(one, ss)
    u2 = fe.add(one, ss)
    u2_sqr = fe.sq(u2)
    d = fe.const_col("D", fe.limbs_of_int(curve.ref.D))
    v = fe.sub(fe.neg(fe.mul(d, fe.sq(u1))), u2_sqr)
    # SQRT_RATIO_M1(1, w) with w = v*u2^2
    w = fe.mul(v, u2_sqr)
    w3 = fe.mul(fe.sq(w), w)
    w7 = fe.mul(fe.sq(w3), w)
    r = fe.mul(w3, fe.pow_p58(w7))
    check = fe.freeze(fe.mul(w, fe.sq(r)))
    correct = _eq_all(check, one)
    flipped = _eq_all(
        check, fe.const_col("NEG_ONE", fe.limbs_of_int(curve.ref.P - 1)))
    flipped_i = _eq_all(
        check,
        fe.const_col("NEG_SQRT_M1",
                     fe.limbs_of_int(curve.ref.P - curve.ref.SQRT_M1)))
    sqrt_m1 = fe.const_col("SQRT_M1", fe.limbs_of_int(curve.ref.SQRT_M1))
    r = jnp.where(flipped | flipped_i, fe.mul(r, sqrt_m1), r)
    ok = correct | flipped
    invsqrt = _abs_fe_k(r)
    den_x = fe.mul(invsqrt, u2)
    den_y = fe.mul(fe.mul(invsqrt, den_x), v)
    x = _abs_fe_k(fe.mul(fe.add(s, s), den_x))
    y = fe.mul(u1, den_y)
    t = fe.mul(x, y)
    yf = fe.freeze(y)
    y_zero = jnp.sum(yf, axis=0, keepdims=True) == 0
    valid = ok & ((fe.freeze(t)[0:1] & 1) == 0) & ~y_zero
    return (x, y, one, t), valid


def _coset_eq_k(p, q):
    """Kernel twin of sr_verify.ristretto_equal -> bool [1, T] (canonical
    limbs are non-negative, so sum == 0 means every limb is zero)."""
    x1, y1 = p[0], p[1]
    x2, y2 = q[0], q[1]
    a = fe.freeze(fe.sub(fe.mul(x1, y2), fe.mul(y1, x2)))
    b = fe.freeze(fe.sub(fe.mul(x1, x2), fe.mul(y1, y2)))
    za = jnp.sum(a, axis=0, keepdims=True) == 0
    zb = jnp.sum(b, axis=0, keepdims=True) == 0
    return za | zb


def _sr_verify_kernel(consts_ref, fc_ref, pk_ref, r_ref, s_ref, k_ref,
                      out_ref, ym_ref, yp_ref, z2_ref, t2_ref, sd_ref,
                      kd_ref, use_dus: bool = True):
    consts = consts_ref[:]
    ctx = {
        "K64P": fc_ref[0 * NLIMBS : 1 * NLIMBS],
        "P_LIMBS": fc_ref[1 * NLIMBS : 2 * NLIMBS],
        "D2": fc_ref[2 * NLIMBS : 3 * NLIMBS],
        "D": fc_ref[3 * NLIMBS : 4 * NLIMBS],
        "SQRT_M1": fc_ref[4 * NLIMBS : 5 * NLIMBS],
        "NEG_ONE": fc_ref[5 * NLIMBS : 6 * NLIMBS],
        "NEG_SQRT_M1": fc_ref[6 * NLIMBS : 7 * NLIMBS],
        "_dus": use_dus,
    }
    with fe.const_context(ctx):
        _sr_verify_body(consts, pk_ref, r_ref, s_ref, k_ref, out_ref,
                        ym_ref, yp_ref, z2_ref, t2_ref, sd_ref, kd_ref)


def _sr_verify_body(consts, pk_ref, r_ref, s_ref, k_ref, out_ref,
                    ym_ref, yp_ref, z2_ref, t2_ref, sd_ref, kd_ref):
    T = pk_ref.shape[1]

    # canonical ristretto encodings have bit 255 clear (value < p,
    # host-checked), so the 255-bit unpack captures the full value
    pk_s = _unpack_limbs_255(pk_ref[:].astype(jnp.int32))
    r_s = _unpack_limbs_255(r_ref[:].astype(jnp.int32))

    for w, row in enumerate(_digit_rows_msb(s_ref[:].astype(jnp.int32))):
        sd_ref[w : w + 1] = row
    for w, row in enumerate(_digit_rows_msb(k_ref[:].astype(jnp.int32))):
        kd_ref[w : w + 1] = row

    a_point, a_ok = _ristretto_decompress_k(pk_s)
    r_point, r_ok = _ristretto_decompress_k(r_s)
    rp = _shamir_ladder(consts, curve.negate(a_point),
                        (ym_ref, yp_ref, z2_ref, t2_ref), sd_ref, kd_ref, T)

    ok = a_ok & r_ok & _coset_eq_k(rp, r_point)
    out_ref[:] = jnp.broadcast_to(ok.astype(jnp.int32), (8, T))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _sr_verify_pallas_jit(pk_b, r_b, s_b, k_b, tile: int, interpret: bool):
    B = pk_b.shape[1]
    grid = (B // tile,)
    spec_in = pl.BlockSpec((32, tile), lambda i: (0, i),
                           memory_space=pltpu.VMEM)
    spec_consts = pl.BlockSpec((NLIMBS, CONST_COLS), lambda i: (0, 0),
                               memory_space=pltpu.VMEM)
    fc = jnp.asarray(np.repeat(_sr_fcols()[:, None], tile, axis=1))
    spec_fc = pl.BlockSpec((_SR_FC_N * NLIMBS, tile), lambda i: (0, 0),
                           memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_sr_verify_kernel, use_dus=not interpret),
        grid=grid,
        in_specs=[spec_consts, spec_fc] + [spec_in] * 4,
        out_specs=pl.BlockSpec((8, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((8, B), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # ym
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # yp
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # z2
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # t2d
            pltpu.VMEM((NDIGITS, tile), jnp.int32),        # s digits
            pltpu.VMEM((NDIGITS, tile), jnp.int32),        # k digits
        ],
        interpret=interpret,
    )(jnp.asarray(_consts_plane()), fc, pk_b.astype(jnp.int32),
      r_b.astype(jnp.int32), s_b.astype(jnp.int32), k_b.astype(jnp.int32))
    return out[0]


def sr_verify_compact_kernel(pk_b, r_b, s_b, k_b, *, tile: int = 256,
                             interpret: bool | None = None):
    """Fused-kernel twin of sr_verify.sr_verify_core_compact.
    pk_b/r_b/s_b/k_b: [32, B] uint8 device arrays (B a multiple of
    ``tile``). Returns bool [B]."""
    if interpret is None:
        interpret = _default_interpret()
    return _sr_verify_pallas_jit(pk_b, r_b, s_b, k_b, tile, interpret) != 0

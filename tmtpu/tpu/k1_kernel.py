"""Fused Pallas TPU kernel for batched secp256k1 ECDSA verification.

Same per-lane semantics as ``tmtpu.tpu.k1_verify.verify_core_compact`` (the
btcec low-S verify; reference crypto/secp256k1/secp256k1.go:195-197, serial
oracle tmtpu.crypto.secp256k1.PubKeySecp256k1.verify_signature), but the
whole device half — big-endian byte unpack, SEC1 decompression (one
(p+1)/4 square-root chain), the 64-window Straus/Shamir ladder
R = [u1]G + [u2]Q and the projective x(R) ≡ r check — runs inside ONE
Pallas kernel per lane tile, keeping the ~4000 field multiplies per
signature in VMEM/vector registers instead of round-tripping [20, B] limb
arrays through HBM after every op. That HBM round-trip is what bounds the
plain-XLA graph (tmtpu.tpu.k1_verify): it loses to serial OpenSSL on CPU
(VERDICT r2 weak #2); the same fusion took ed25519 from 22k to 260k sig/s
(tmtpu.tpu.kernel).

Layout matches tmtpu.tpu.kernel: limb arrays are [NLIMBS, T] int32 with T
lanes on the TPU vector lanes, so the fe_k1/k1_verify field and point
routines run verbatim inside the kernel (their constants arrive through
fe.const_context planes — Pallas rejects closed-over arrays). Kernel-only
code is what touches refs or needs [1, T] masks: the big-endian unpack,
digit extraction, decompression, select-chain window lookups and the final
compare.

Grid: one program per ``tile`` lanes; programs are data-parallel over
signatures, so the kernel composes with shard_map lane-sharding unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tmtpu.tpu import fe_k1 as fe
from tmtpu.tpu import k1_verify as kv
from tmtpu.tpu.kernel import _default_interpret

NLIMBS = fe.NLIMBS
RADIX = fe.RADIX
WINDOW = kv.WINDOW
NDIGITS = kv.NDIGITS
NTAB = 1 << WINDOW

# Constants plane: [NLIMBS, CONST_COLS] int32. Columns 3*d + c hold
# coordinate c (X, Y, Z) of the fixed-base table entry d*G (projective,
# identity at d = 0) — 48 columns total.
CONST_COLS = 48

# fe-level constants at full tile width (narrow [20, 1] constants die in
# Mosaic's layout pass — see tmtpu.tpu.kernel._verify_kernel): KSUB (sub),
# P_LIMBS (freeze), SEVEN (decompress).
_FC_N = 3

DEFAULT_TILE = 256

_CONSTS_PLANE = None
_FCOLS = None


def _consts_plane() -> np.ndarray:
    global _CONSTS_PLANE
    if _CONSTS_PLANE is None:
        plane = np.zeros((NLIMBS, CONST_COLS), dtype=np.int32)
        tab = kv.fixed_base_table()  # [16, 3, 20]
        for d in range(NTAB):
            for c in range(3):
                plane[:, 3 * d + c] = tab[d, c]
        _CONSTS_PLANE = plane
    return _CONSTS_PLANE


def _fcols() -> np.ndarray:
    global _FCOLS
    if _FCOLS is None:
        _FCOLS = np.concatenate(
            [fe.KSUB, fe.P_LIMBS, fe.limbs_of_int(7)]).astype(np.int32)
    return _FCOLS


def _unpack_limbs_be(b):
    """[32, T] int32 BIG-endian bytes -> [20, T] radix-2^13 limbs of the
    full 256-bit value (callers guarantee value < p < 2^256). Byte k of the
    little-endian order is row 31-k of the big-endian input."""
    rows = []
    for limb in range(NLIMBS):
        lo_bit = RADIX * limb
        if lo_bit >= 256:
            rows.append(jnp.zeros_like(b[0:1]))
            continue
        hi_bit = min(lo_bit + RADIX, 256)
        nbits = hi_bit - lo_bit
        off = lo_bit & 7
        k = lo_bit >> 3
        acc = b[31 - k : 32 - k] >> off
        shift = 8 - off
        k += 1
        while shift < nbits:
            acc = acc | (b[31 - k : 32 - k] << shift)
            shift += 8
            k += 1
        rows.append(acc & ((1 << nbits) - 1))
    return jnp.concatenate(rows, axis=0)


def _row0_one(x):
    """[20, T] limb vector of the field element 1 (concat form — .at[].set
    lowers to scatter, unsupported in Mosaic)."""
    return jnp.concatenate(
        [jnp.ones((1, x.shape[1]), jnp.int32),
         jnp.zeros((NLIMBS - 1, x.shape[1]), jnp.int32)], axis=0)


def _eq_all(a, b):
    """[20, T] x2 canonical limbs -> bool [1, T] rowwise equality."""
    return jnp.sum(jnp.abs(a - b), axis=0, keepdims=True) == 0


def _decompress_k(x, parity):
    """Kernel twin of k1_verify.decompress with [1, T] masks. x: [20, T]
    canonical limbs (host-checked < p); parity: [1, T] in {0, 1}."""
    seven = fe.const_col("K1_SEVEN", fe.limbs_of_int(7))
    y2 = fe.add(fe.mul(fe.sq(x), x), seven)
    y = fe.sqrt_candidate(y2)
    yf = fe.freeze(y)
    valid = _eq_all(fe.freeze(fe.sq(y)), fe.freeze(y2))
    flip = (yf[0:1] & 1) != parity
    y = jnp.where(flip, fe.neg(yf), yf)
    return (x, y, _row0_one(x)), valid


def _digit_rows_msb_be(b):
    """[32, T] int32 BIG-endian scalar bytes -> 64 [1, T] 4-bit windows,
    most-significant first (row 2i = hi nibble of byte i)."""
    rows = []
    for w in range(NDIGITS):
        byte = b[w // 2 : w // 2 + 1]
        rows.append((byte >> 4) if (w % 2 == 0) else (byte & 0x0F))
    return rows


def _k1_ladder(consts, q, tab_refs, d1_ref, d2_ref, T):
    """Build the per-lane window table d*Q (d in 0..15) in scratch — 14
    sequential complete adds, unrolled — then run the 64-window
    Straus/Shamir ladder [u1]G + [u2]Q with select-chain lookups (the
    fixed-base projective rows from the constants plane; the per-lane rows
    from scratch). Returns the projective result."""
    tx_ref, ty_ref, tz_ref = tab_refs
    ident = kv.identity((T,))
    for ref_, val in zip(tab_refs, ident):
        ref_[0:NLIMBS] = val
    for ref_, val in zip(tab_refs, q):
        ref_[NLIMBS : 2 * NLIMBS] = val
    acc = q
    for d in range(2, NTAB):
        acc = kv.add(acc, q)
        for ref_, val in zip(tab_refs, acc):
            ref_[d * NLIMBS : (d + 1) * NLIMBS] = val

    def lookup_base(dig):
        sel = [None, None, None]
        for d in range(NTAB):
            m = dig == d
            for c in range(3):
                col = 3 * d + c
                const = consts[:, col : col + 1]  # [20, 1]
                sel[c] = (jnp.where(m, const, sel[c])
                          if sel[c] is not None
                          else jnp.broadcast_to(const, (NLIMBS, T)))
        return tuple(sel)

    def lookup_lane(dig):
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
            p = kv.double(p)
        d1 = d1_ref[pl.ds(w, 1)]
        d2 = d2_ref[pl.ds(w, 1)]
        p = kv.add(p, lookup_base(d1))
        p = kv.add(p, lookup_lane(d2))
        return p

    return jax.lax.fori_loop(0, NDIGITS, body, ident)


def _k1_verify_kernel(consts_ref, fc_ref, pkx_ref, par_ref, u1_ref, u2_ref,
                      r_ref, rpn_ref, out_ref, tx_ref, ty_ref, tz_ref,
                      d1_ref, d2_ref, use_dus: bool = True):
    consts = consts_ref[:]
    ctx = {
        "K1_KSUB": fc_ref[0 * NLIMBS : 1 * NLIMBS],
        "K1_P": fc_ref[1 * NLIMBS : 2 * NLIMBS],
        "K1_SEVEN": fc_ref[2 * NLIMBS : 3 * NLIMBS],
        "_dus": use_dus,
    }
    from tmtpu.tpu.fe import const_context

    with const_context(ctx):
        _k1_verify_body(consts, pkx_ref, par_ref, u1_ref, u2_ref, r_ref,
                        rpn_ref, out_ref, (tx_ref, ty_ref, tz_ref),
                        d1_ref, d2_ref)


def _k1_verify_body(consts, pkx_ref, par_ref, u1_ref, u2_ref, r_ref,
                    rpn_ref, out_ref, tab_refs, d1_ref, d2_ref):
    T = pkx_ref.shape[1]

    x_limbs = _unpack_limbs_be(pkx_ref[:].astype(jnp.int32))
    parity = par_ref[0:1]

    for w, row in enumerate(_digit_rows_msb_be(u1_ref[:].astype(jnp.int32))):
        d1_ref[w : w + 1] = row
    for w, row in enumerate(_digit_rows_msb_be(u2_ref[:].astype(jnp.int32))):
        d2_ref[w : w + 1] = row

    q, q_ok = _decompress_k(x_limbs, parity)
    rp = _k1_ladder(consts, q, tab_refs, d1_ref, d2_ref, T)

    X, _, Z = rp
    zf = fe.freeze(Z)
    finite = jnp.sum(zf, axis=0, keepdims=True) != 0
    xf = fe.freeze(X)
    r_l = _unpack_limbs_be(r_ref[:].astype(jnp.int32))
    rpn_l = _unpack_limbs_be(rpn_ref[:].astype(jnp.int32))
    m1 = _eq_all(xf, fe.freeze(fe.mul(r_l, Z)))
    m2 = _eq_all(xf, fe.freeze(fe.mul(rpn_l, Z)))
    ok = q_ok & finite & (m1 | m2)
    out_ref[:] = jnp.broadcast_to(ok.astype(jnp.int32), (8, T))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _k1_verify_pallas_jit(pkx_b, parity, u1_b, u2_b, r_b, rpn_b,
                          tile: int, interpret: bool):
    B = pkx_b.shape[1]
    grid = (B // tile,)
    spec_in = pl.BlockSpec((32, tile), lambda i: (0, i),
                           memory_space=pltpu.VMEM)
    spec_par = pl.BlockSpec((8, tile), lambda i: (0, i),
                            memory_space=pltpu.VMEM)
    spec_consts = pl.BlockSpec((NLIMBS, CONST_COLS), lambda i: (0, 0),
                               memory_space=pltpu.VMEM)
    fc = jnp.asarray(np.repeat(_fcols()[:, None], tile, axis=1))
    spec_fc = pl.BlockSpec((_FC_N * NLIMBS, tile), lambda i: (0, 0),
                           memory_space=pltpu.VMEM)
    par8 = jnp.broadcast_to(parity[None, :].astype(jnp.int32), (8, B))
    out = pl.pallas_call(
        functools.partial(_k1_verify_kernel, use_dus=not interpret),
        grid=grid,
        in_specs=[spec_consts, spec_fc, spec_in, spec_par] + [spec_in] * 4,
        out_specs=pl.BlockSpec((8, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((8, B), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # table X
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # table Y
            pltpu.VMEM((NTAB * NLIMBS, tile), jnp.int32),  # table Z
            pltpu.VMEM((NDIGITS, tile), jnp.int32),        # u1 digits
            pltpu.VMEM((NDIGITS, tile), jnp.int32),        # u2 digits
        ],
        interpret=interpret,
    )(jnp.asarray(_consts_plane()), fc, pkx_b.astype(jnp.int32), par8,
      u1_b.astype(jnp.int32), u2_b.astype(jnp.int32),
      r_b.astype(jnp.int32), rpn_b.astype(jnp.int32))
    return out[0]


def k1_verify_compact_kernel(pkx_b, parity, u1_b, u2_b, r_b, rpn_b, *,
                             tile: int = 256,
                             interpret: bool | None = None):
    """Fused-kernel twin of k1_verify.verify_core_compact. pkx_b/u1_b/
    u2_b/r_b/rpn_b: [32, B] uint8 big-endian device arrays (B a multiple
    of ``tile``); parity: [B] int32. Returns bool [B]."""
    if interpret is None:
        interpret = _default_interpret()
    return _k1_verify_pallas_jit(
        pkx_b, parity, u1_b, u2_b, r_b, rpn_b, tile, interpret) != 0

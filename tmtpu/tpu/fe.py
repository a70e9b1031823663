"""GF(2^255-19) field arithmetic for TPU, batch-vectorized in JAX.

This is the arithmetic core of the TPU batch signature verifier (the
north-star `crypto.backend=tpu` path; the reference verifies serially on CPU
via Go stdlib — crypto/ed25519/ed25519.go:148).

Representation
--------------
A field element is 20 limbs in radix 2^13 (20*13 = 260 bits), dtype int32,
stored limbs-FIRST: an array of shape ``[20, B]`` for a batch of B elements.
The batch dimension is trailing so it lands on the TPU vector lanes (128-wide)
and the small limb dimension on sublanes; every op below is elementwise over
the batch.

TPUs have no 64-bit integer ALU, so limbs are sized such that all
intermediate products and sums fit in int32:

- all routine outputs keep limbs in ``[0, 9500]`` ("loose" form);
- schoolbook products then satisfy ``20 * 9500^2 = 1.805e9 < 2^31``;
- 2^260 ≡ 608 (mod p) folds the high half back (608 = 2^5 * 19), and
  2^520 ≡ 608^2 folds the product's final carry-out.

Carry propagation is done with *vectorized* passes (all limbs at once); the
number of passes per op is chosen so the stated bounds hold for any input in
loose form (see the per-op comments — these are static bounds, not
probabilistic). Only `freeze` (canonicalization for byte-exact compare)
needs an exact sequential borrow chain, and it runs once per verification.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

RADIX = 13
NLIMBS = 20
MASK = (1 << RADIX) - 1
# 2^260 = 2^(13*20) ≡ 2^5 * 19 = 608 (mod p)
FOLD = 608
# 2^520 ≡ 608^2 (mod p)
FOLD2 = FOLD * FOLD

P_INT = 2**255 - 19


def limbs_of_int(v: int) -> np.ndarray:
    """Canonical little-endian radix-2^13 limbs of ``v`` (host helper)."""
    out = np.zeros(NLIMBS, dtype=np.int32)
    for i in range(NLIMBS):
        out[i] = v & MASK
        v >>= RADIX
    assert v == 0
    return out


def int_of_limbs(a) -> int:
    """Host-side: integer value of a single limb vector (any bounds)."""
    a = np.asarray(a)
    return sum(int(a[i]) << (RADIX * i) for i in range(a.shape[0]))


P_LIMBS = limbs_of_int(P_INT)


# ---------------------------------------------------------------------------
# Constant plumbing. Outside Pallas, limb-vector constants are just
# jnp.asarray'd numpy arrays (XLA embeds them). Inside a Pallas kernel,
# closed-over arrays are rejected ("captures constants — pass them as
# inputs"), so tmtpu.tpu.kernel passes one [20, n] constants plane as a
# kernel input and installs its columns here; every fe/curve routine then
# picks constants up from the active context.

import contextvars

# ContextVar, not a module global: a kernel trace on one thread must not
# leak its Ref-slice constants into an XLA-path trace running concurrently
# on another thread (e.g. consensus compiling the kernel while an RPC
# thread verifies over the plain graph).
_CONST_CTX: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "tmtpu_fe_const_ctx", default=None)


@contextlib.contextmanager
def const_context(consts: dict):
    """Install kernel-provided full-width constant planes (keys: K64P,
    P_LIMBS, D2, D, SQRT_M1) for the duration of a kernel trace."""
    token = _CONST_CTX.set(consts)
    try:
        yield
    finally:
        _CONST_CTX.reset(token)


def const_col(name: str, np_vec) -> jnp.ndarray:
    """Column(s) for a named limb constant — from the kernel context when
    one is active ([20, T] there), else a plain embedded [20, 1]."""
    ctx = _CONST_CTX.get()
    if ctx is not None:
        return ctx[name]
    return jnp.asarray(np_vec)[:, None]


def pack_bytes_le(b: np.ndarray) -> np.ndarray:
    """[B, 32] uint8 little-endian byte strings -> [20, B] int32 limbs.

    Only the low 255 bits are packed (bit 255 — the ed25519 sign bit — is
    masked off by the caller before/after as needed: this packs all 256 bits'
    worth only up to 260, so callers must pre-mask byte 31's top bit if it
    must be excluded)."""
    assert b.ndim == 2 and b.shape[1] == 32
    bits = np.unpackbits(b, axis=1, bitorder="little")  # [B, 256]
    pad = np.zeros((b.shape[0], NLIMBS * RADIX - 256), dtype=bits.dtype)
    bits = np.concatenate([bits, pad], axis=1)  # [B, 260]
    w = (1 << np.arange(RADIX, dtype=np.int32))  # [13]
    limbs = bits.reshape(b.shape[0], NLIMBS, RADIX).astype(np.int32) @ w
    return np.ascontiguousarray(limbs.T)  # [20, B]


def pack_bytes_device(b):
    """DEVICE-side [32, B] uint8/int32 little-endian byte strings ->
    [20, B] int32 limbs (the on-device twin of ``pack_bytes_le``).

    Shipping raw 32-byte encodings and unpacking on device cuts H2D
    traffic 2.5x vs pre-packed [20, B] int32 limbs, and the host does
    no limb arithmetic at all; the few elementwise shifts here are noise
    beside the curve math. Callers mask byte 31's sign bit beforehand
    when packing point encodings."""
    b = b.astype(jnp.int32)  # [32, B]
    bits = (b[:, None, :] >> jnp.arange(8, dtype=jnp.int32)[None, :, None]) & 1
    bits = bits.reshape((256,) + b.shape[1:])  # [256, B], LSB-first
    pad = jnp.zeros((NLIMBS * RADIX - 256,) + b.shape[1:], dtype=jnp.int32)
    bits = jnp.concatenate([bits, pad], axis=0)  # [260, B]
    w = (1 << jnp.arange(RADIX, dtype=jnp.int32))  # [13]
    limbs = bits.reshape((NLIMBS, RADIX) + b.shape[1:])
    return (limbs * w[None, :, None]).sum(axis=1, dtype=jnp.int32)


def at_add(x, lo: int, v):
    """x.at[lo:lo+v.shape[0]].add(v), in the form the active compiler
    wants.

    jax lowers ``.at[].add`` to scatter-add even for static slices, and
    Mosaic (Pallas TPU) has no scatter-add lowering — while
    dynamic-update-slice + elementwise add are native to it. Outside the
    kernel the scatter form stays: XLA fuses it well, and the zeros-DUS-add
    expansion blows up XLA:CPU compile time (the multichip dryrun budget).
    Kernel traces are detected via the active const_context (installed by
    tmtpu.tpu.kernel for exactly the duration of the kernel trace); its
    "_dus" entry is False for interpret-mode kernels, which execute through
    XLA CPU where the scatter form is both supported and much faster to
    compile."""
    ctx = _CONST_CTX.get()
    if ctx is not None and ctx.get("_dus", True):
        n = v.shape[0]
        parts = []
        if lo:
            parts.append(x[:lo])
        parts.append(x[lo : lo + n] + v)
        if lo + n < x.shape[0]:
            parts.append(x[lo + n :])
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return x.at[lo : lo + v.shape[0]].add(v)


def _carry_pass(x, fold):
    """One vectorized carry pass. If ``fold`` is nonzero, the carry out of
    the top limb wraps to limb 0 multiplied by ``fold``; otherwise the top
    limb keeps its excess (caller guarantees no overflow)."""
    c = x >> RADIX
    x = x - (c << RADIX)
    x = at_add(x, 1, c[:-1])
    if fold:
        x = at_add(x, 0, fold * c[-1:])
    else:
        x = at_add(x, x.shape[0] - 1, c[-1:] << RADIX)
    return x


def carry(x, passes: int, fold: int = FOLD):
    for _ in range(passes):
        x = _carry_pass(x, fold)
    return x


def add(a, b):
    """a + b. Inputs loose (limbs ≤ 9500) -> sum limbs ≤ 19000 -> one pass:
    carries ≤ 2, fold adds ≤ 2*608 to limb 0 -> limbs ≤ 8191+2+1216 = 9409."""
    return carry(a + b, 1)


# 64p as 20 limbs, each in [15168, 16383]: canonical limbs of 64p (21 limbs,
# top = 1) with the top limb folded down and one unit borrowed into each
# lower limb so that limbwise subtraction of any loose element stays with
# small magnitude. Verified in tests: int value == 64 * P_INT.
def _k64p() -> np.ndarray:
    m = np.zeros(NLIMBS + 1, dtype=np.int64)
    v = 64 * P_INT
    for i in range(NLIMBS + 1):
        m[i] = v & MASK
        v >>= RADIX
    k = m[:NLIMBS].copy()
    k[NLIMBS - 1] += m[NLIMBS] << RADIX  # fold 21st limb into the 20th
    # borrow 1 from limb i+1, add 2^13 to limb i, for i = 18..0
    for i in range(NLIMBS - 2, -1, -1):
        k[i] += 1 << RADIX
        k[i + 1] -= 1
    out = k.astype(np.int32)
    assert int_of_limbs(out) == 64 * P_INT
    assert out.min() >= 15000
    return out


K64P = _k64p()


def sub(a, b):
    """a - b + 64p (so the value stays non-negative). Pre-carry limbs are in
    [15168-9500, 16383+2*9500] ⊂ [5668, 35383]; two passes: after pass 1
    carries ≤ 4 so limb0 ≤ 8191+4+608*4 ≤ 10627, after pass 2 carries ≤ 1 so
    limbs ≤ 8191+1+608 = 8800."""
    return carry(a + const_col("K64P", K64P) - b, 2)


def neg(a):
    zero = jnp.zeros_like(a)
    return sub(zero, a)


def _fold_product(c):
    """[40, B] raw-ish coefficients -> [20, B] loose limbs."""
    # Two no-top-fold passes bring 40 coefficients from ≤ 1.9e9 down:
    # pass 1 carries ≤ 232k -> limbs ≤ 8191+232k; pass 2 carries ≤ 29 ->
    # limbs ≤ 8191+30 (the top limb may keep an excess ≤ 2^31 via the
    # explicit fold below).
    c = carry(c, 1, fold=FOLD2)
    c = carry(c, 1, fold=FOLD2)
    # Fold limbs 20..39 (weight 2^260 * 2^13j ≡ 608 * 2^13j):
    low = c[:NLIMBS] + FOLD * c[NLIMBS:]
    # low ≤ 8221 + 608*8221 ≈ 5.0e6; three folding passes:
    # p1: carries ≤ 611 -> limb0 ≤ 8191 + 611 + 608*611 ≈ 3.8e5
    # p2: carries ≤ 47  -> limbs ≤ 8191 + 47 + 608
    # p3: carries ≤ 1   -> limbs ≤ 8191 + 1 + 608 = 8800
    return carry(low, 3)


def mul(a, b):
    """Schoolbook product + reduction. Inputs loose (≤ 9500 -> coefficient
    bound 20*9500^2 = 1.805e9 < 2^31-1). Output loose (≤ 8800)."""
    B = jnp.broadcast_shapes(a.shape[1:], b.shape[1:])
    # broadcast [20, 1] constants up front: per-row slices of an
    # unbroadcast constant are [1, 1] and their implicit broadcast against
    # [20, B] is a 2-axis broadcast Mosaic can't lower (XLA: free either way)
    a = jnp.broadcast_to(a, (NLIMBS,) + B)
    b = jnp.broadcast_to(b, (NLIMBS,) + B)
    c = jnp.zeros((2 * NLIMBS,) + B, dtype=jnp.int32)
    for i in range(NLIMBS):
        c = at_add(c, i, a[i : i + 1] * b)
    return _fold_product(c)


def sq(a):
    """Square, using symmetry: c_k = sum_{i<j,i+j=k} 2 a_i a_j + a_{k/2}^2.
    With a ≤ 9500 the doubled-operand terms are ≤ 10*(2*9500)*9500 +
    9500^2 = 1.9e9 < 2^31."""
    B = a.shape[1:]
    a2 = a + a  # ≤ 19000; only ever multiplied by a ≤ 9500 below
    c = jnp.zeros((2 * NLIMBS,) + B, dtype=jnp.int32)
    for i in range(NLIMBS):
        c = at_add(c, 2 * i, a[i : i + 1] * a[i : i + 1])
        if i + 1 < NLIMBS:
            c = at_add(c, 2 * i + 1, a2[i : i + 1] * a[i + 1 :])
    return _fold_product(c)


def freeze(x):
    """Canonical form: limbs in [0, 2^13), value in [0, p). Input loose
    (non-negative value, limbs ≤ 9500).

    Verification compares the recomputed R' encoding byte-exactly against the
    signature's R (ed25519_ref.verify), so this must be *exactly* canonical
    for every input — the final carry and the conditional subtract use full
    sequential chains (20 steps each), not the probabilistic-settling
    vectorized passes. Runs once per point decode, so the cost is noise."""
    x = carry(x, 3)  # limbs ≤ 8800, value < 2^260
    for _ in range(2):
        # value < 2^260: bits ≥ 255 live in limb 19 (weight 2^247) bits ≥ 8.
        # Subtract q*2^255 and add q*19 (2^255 ≡ 19 mod p).
        q = x[NLIMBS - 1 :] >> (255 - RADIX * (NLIMBS - 1))
        x = at_add(x, NLIMBS - 1, -(q << 8))
        x = at_add(x, 0, 19 * q)
        x = carry(x, 2)
    # Now value < 2^255 + eps; exact sequential carry (no fold can trigger:
    # value < 2^256 << 2^260).
    for i in range(NLIMBS - 1):
        c = x[i : i + 1] >> RADIX
        x = at_add(at_add(x, i, -(c << RADIX)), i + 1, c)
    # x may still be in [p, 2^255): conditionally subtract p with an exact
    # borrow chain.
    t = x - const_col("P_LIMBS", P_LIMBS)
    for i in range(NLIMBS - 1):
        c = t[i : i + 1] >> RADIX
        t = at_add(at_add(t, i, -(c << RADIX)), i + 1, c)
    return jnp.where(t[NLIMBS - 1 :] < 0, x, t)


def sqn(a, n: int):
    """a^(2^n) — n repeated squarings via fori_loop (keeps the graph small
    for the long runs inside the inversion chain)."""
    if n <= 4:
        for _ in range(n):
            a = sq(a)
        return a
    return jax.lax.fori_loop(0, n, lambda _, x: sq(x), a)


def pow_p58(a):
    """a^((p-5)/8) = a^(2^252 - 3) — the square-root exponent used in point
    decompression (x = uv^3 (uv^7)^((p-5)/8)). Same ladder family as
    ``invert``: 252 squarings + 11 multiplies, batch-vectorized."""
    t0 = sq(a)  # 2
    t1 = mul(a, sq(sq(t0)))  # 9
    t0 = mul(t0, t1)  # 11
    t0 = mul(t1, sq(t0))  # 31 = 2^5 - 1
    t0 = mul(t0, sqn(t0, 5))  # 2^10 - 1
    t1 = mul(sqn(t0, 10), t0)  # 2^20 - 1
    t2 = mul(sqn(t1, 20), t1)  # 2^40 - 1
    t1 = mul(sqn(t2, 10), t0)  # 2^50 - 1
    t2 = mul(sqn(t1, 50), t1)  # 2^100 - 1
    t2 = mul(sqn(t2, 100), t2)  # 2^200 - 1
    t1 = mul(sqn(t2, 50), t1)  # 2^250 - 1
    return mul(sqn(t1, 2), a)  # 2^252 - 3


def invert(a):
    """a^(p-2) = a^(2^255 - 21) via the standard curve25519 addition chain
    (254 squarings + 11 multiplies), batch-vectorized."""
    t0 = sq(a)  # 2
    t1 = mul(a, sq(sq(t0)))  # 9
    t0 = mul(t0, t1)  # 11
    t1 = mul(t1, sq(t0))  # 31 = 2^5 - 1
    t1 = mul(t1, sqn(t1, 5))  # 2^10 - 1
    t2 = mul(sqn(t1, 10), t1)  # 2^20 - 1
    t2 = mul(sqn(t2, 20), t2)  # 2^40 - 1
    t1 = mul(sqn(t2, 10), t1)  # 2^50 - 1
    t2 = mul(sqn(t1, 50), t1)  # 2^100 - 1
    t2 = mul(sqn(t2, 100), t2)  # 2^200 - 1
    t1 = mul(sqn(t2, 50), t1)  # 2^250 - 1
    return mul(sqn(t1, 5), t0)  # 2^255 - 2^5 + 11 = 2^255 - 21

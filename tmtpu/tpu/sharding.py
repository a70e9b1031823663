"""Multi-chip sharding for the batch verifier + on-device vote tally.

The framework's scale axis is validator-set size (SURVEY.md §5: per-round
work is O(V) signature verifies + O(V) bitarray/power bookkeeping, V ≤ 10000
— types/vote_set.go:18). The TPU mapping is data parallelism over signature
*lanes*: every per-lane array (limbs [20, B], digits [64, B], masks [B]) is
sharded on its trailing batch dimension over a 1-D device mesh (axis
``"sig"``), the fixed-base table is replicated, and the only cross-device
traffic is the tally reduction (psum of power-limb sums — a few hundred
bytes) riding ICI. Scaling to multi-host meshes changes nothing in this
file: the same NamedSharding specs lay lanes out over DCN-connected hosts
and XLA inserts the hierarchical reduction.

Voting powers are int64 in the reference (types/validator.go). TPUs have no
64-bit integer ALU, so powers ride as 5×13-bit limbs ([5, B] int32, same
radix as the field arithmetic); per-limb lane sums stay < 2^31 for any
B ≤ 2^17 and are recombined into a Python int on the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tmtpu.tpu import verify as tv

POWER_RADIX = 13
POWER_LIMBS = 5  # 5 * 13 = 65 bits >= int64


def powers_to_limbs(powers, out=None) -> np.ndarray:
    """int64 powers, array or list [B] -> [5, B] int32 radix-2^13 limbs,
    as five shifts and masks over the whole array; written into ``out``
    (int32 [5, B], e.g. the first B lanes of a wider operand) when given.
    A negative power, or one no int64 holds, is refused."""
    try:
        p = np.asarray(powers, dtype=np.int64)
    except OverflowError:
        raise ValueError("voting power exceeds 63 bits") from None
    if p.size and p.min() < 0:
        raise ValueError("negative voting power")
    if out is None:
        out = np.empty((POWER_LIMBS, p.shape[0]), dtype=np.int32)
    for j in range(POWER_LIMBS):
        out[j] = (p >> (POWER_RADIX * j)) & ((1 << POWER_RADIX) - 1)
    return out


def limb_sums_to_int(sums) -> int:
    s = np.asarray(sums, dtype=np.int64)
    return int(sum(int(s[j]) << (POWER_RADIX * j) for j in range(POWER_LIMBS)))


def pack_bitarray(mask):
    """bool [B] -> uint32 words [ceil(B/32)] (zero-padded high bits).
    The on-device equivalent of libs/bits.BitArray for vote bookkeeping."""
    b = mask.shape[0]
    if b % 32:
        mask = jnp.concatenate(
            [mask, jnp.zeros(32 - b % 32, dtype=mask.dtype)]
        )
        b = mask.shape[0]
    w = mask.reshape(b // 32, 32).astype(jnp.uint32)
    return (w << jnp.arange(32, dtype=jnp.uint32)[None, :]).sum(
        axis=1, dtype=jnp.uint32
    )


def verify_tally_step_compact(pk_b, r_b, s_b, h_b, power_limbs, table):
    """The flagship device step: batch-verify all lanes, then reduce the
    valid lanes' voting power and pack the validity bitarray — the fused
    VoteSet.addVote hot path (types/vote_set.go:233-304) for a whole
    round's votes at once. Inputs are raw [32, B] byte columns (128 B/lane
    over the host->device link), unpacked on device
    (tv.verify_core_compact). Returns (mask [B] bool, power_sums [5]
    int32, bit_words [B/32] uint32)."""
    mask = tv.verify_core_compact(pk_b, r_b, s_b, h_b, table)
    power_sums = jnp.sum(power_limbs * mask[None].astype(jnp.int32), axis=1)
    return mask, power_sums, pack_bitarray(mask)


def verify_tally_step_kernel(pk_b, r_b, s_b, h_b, power_limbs):
    """verify_tally_step_compact with the verification running as the
    fused Pallas kernel (tmtpu.tpu.kernel) — the production TPU path; the
    tally stays a handful of XLA reduction ops on the kernel's mask."""
    from tmtpu.tpu import kernel as tk

    mask = tk.verify_compact_kernel(pk_b, r_b, s_b, h_b)
    power_sums = jnp.sum(power_limbs * mask[None].astype(jnp.int32), axis=1)
    return mask, power_sums, pack_bitarray(mask)


def verify_tally_packed_kernel(packed, power_limbs):
    """Packed-input twin of verify_tally_step_kernel: ONE [128, B] uint8
    plane (pk | r | s | h) so the host->device hop is a single transfer
    (see tv.prepare_batch_packed)."""
    return verify_tally_step_kernel(*tv.split_packed(packed), power_limbs)


def verify_tally_packed_compact(packed, power_limbs, table):
    """Packed-input twin of verify_tally_step_compact (XLA-graph path)."""
    return verify_tally_step_compact(
        *tv.split_packed(packed), power_limbs, table)


def make_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), ("sig",))


def sharded_verify_tally_compact(mesh: Mesh):
    """Build the pjit'd multi-chip step for ``mesh``: every [32, B] byte
    column shards on its lane ("sig") dimension, unpack happens
    shard-locally, and only the power reduction crosses devices as an XLA
    psum riding ICI."""
    lane = NamedSharding(mesh, P(None, "sig"))
    flat = NamedSharding(mesh, P("sig"))
    repl = NamedSharding(mesh, P())
    return jax.jit(
        verify_tally_step_compact,
        in_shardings=(lane, lane, lane, lane, lane, repl),
        out_shardings=(flat, repl, flat),
    )


def sharded_verify_tally_kernel(mesh: Mesh, *, tile: int | None = None,
                                interpret: bool | None = None):
    """Multi-chip fused-kernel step: shard_map over the "sig" lane axis
    with the Pallas kernel running shard-locally on each chip and the
    power tally reduced across the mesh with one psum riding ICI. Each
    shard's lane count must be a multiple of the kernel tile.

    This is the production pod-scale path; the XLA-graph twin
    (sharded_verify_tally_compact) remains for CPU meshes and the driver
    dryrun, where Mosaic isn't available."""
    from tmtpu.tpu import kernel as tk

    kw = {}
    if tile is not None:
        kw["tile"] = tile
    if interpret is not None:
        kw["interpret"] = interpret

    def local_step(pk_b, r_b, s_b, h_b, power_limbs):
        mask = tk.verify_compact_kernel(pk_b, r_b, s_b, h_b, **kw)
        local = jnp.sum(power_limbs * mask[None].astype(jnp.int32), axis=1)
        power_sums = jax.lax.psum(local, "sig")
        return mask, power_sums, pack_bitarray(mask)

    return jax.jit(jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(None, "sig"),) * 5,
        out_specs=(P("sig"), P(), P("sig")),
        check_vma=False,
    ))


def sharded_verify_tally_packed(mesh: Mesh):
    """Packed-input twin of :func:`sharded_verify_tally_compact` — the
    production mesh-dispatch entry (tpu/mesh_dispatch.py). ONE [128, B]
    uint8 plane rides host->device, shards on its lane dimension, and is
    split shard-locally; the power tally crosses devices as the only
    collective. B must be a multiple of 32 x n_devices (the packed
    bitarray output shards one uint32 word per 32 lanes)."""
    lane = NamedSharding(mesh, P(None, "sig"))
    flat = NamedSharding(mesh, P("sig"))
    repl = NamedSharding(mesh, P())
    return jax.jit(
        verify_tally_packed_compact,
        in_shardings=(lane, lane, repl),
        out_shardings=(flat, repl, flat),
    )


def sharded_verify_tally_packed_kernel(mesh: Mesh, *,
                                       tile: int | None = None,
                                       interpret: bool | None = None):
    """Packed-input twin of :func:`sharded_verify_tally_kernel`: the
    fused Pallas kernel under shard_map with a single [128, B] transfer.
    Each shard's lane count must be a multiple of the kernel tile."""
    from tmtpu.tpu import kernel as tk

    kw = {}
    if tile is not None:
        kw["tile"] = tile
    if interpret is not None:
        kw["interpret"] = interpret

    def local_step(packed, power_limbs):
        mask = tk.verify_compact_kernel(*tv.split_packed(packed), **kw)
        local = jnp.sum(power_limbs * mask[None].astype(jnp.int32), axis=1)
        power_sums = jax.lax.psum(local, "sig")
        return mask, power_sums, pack_bitarray(mask)

    return jax.jit(jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(None, "sig"), P(None, "sig")),
        out_specs=(P("sig"), P(), P("sig")),
        check_vma=False,
    ))


def sharded_verify_sr(mesh: Mesh):
    """Lane-sharded sr25519 batch verify over ``mesh``: the [128, B]
    packed plane (pk|r|s|k — sr_verify.prepare_sr_batch_packed) shards on
    lanes, the fixed-base table replicates, and ristretto decode + the
    shared-doubling ladder run shard-locally. Verification is
    embarrassingly parallel — no collective at all; the sharded mask
    feeds whatever reduction the caller wants."""
    from tmtpu.tpu import sr_verify as srv

    lane = NamedSharding(mesh, P(None, "sig"))
    flat = NamedSharding(mesh, P("sig"))
    repl = NamedSharding(mesh, P())

    def step(packed, table):
        return srv.sr_verify_core_compact(*tv.split_packed(packed), table)

    return jax.jit(step, in_shardings=(lane, repl), out_shardings=flat)


def sharded_verify_k1(mesh: Mesh):
    """Lane-sharded secp256k1 batch verify over ``mesh``: the [168, B]
    packed plane (k1_verify.prepare_k1_batch_packed) shards on lanes, the
    fixed-base table replicates; decompression, the Straus ladder and the
    projective x(R) ≡ r check run shard-locally with no collectives."""
    from tmtpu.tpu import k1_verify as kv

    lane = NamedSharding(mesh, P(None, "sig"))
    flat = NamedSharding(mesh, P("sig"))
    repl = NamedSharding(mesh, P())

    def step(packed, table):
        planes, parity = kv.split_packed_k1(packed)
        return kv.verify_core_compact(planes[0], parity, *planes[1:],
                                      table)

    return jax.jit(step, in_shardings=(lane, repl), out_shardings=flat)


# the single-device fused steps, jitted under their own names: the
# profile's module names and the compile cache's keys read them
verify_tally_packed_kernel_jit = jax.jit(verify_tally_packed_kernel)
verify_tally_packed_compact_jit = jax.jit(verify_tally_packed_compact)


def _tile(a, reps):
    return jnp.repeat(a, reps, axis=-1)


def example_batch(lanes: int):
    """Deterministic well-formed device args with ``lanes`` lanes (one real
    signature tiled), for compile checks and benchmarks (compact form)."""
    from tmtpu.crypto import ed25519_ref as ref

    seed = bytes(range(32))
    msg = b"tmtpu-example-vote-sign-bytes" * 4
    pk = ref.public_key(seed)
    sig = ref.sign(seed, msg)
    args, host_ok = tv.prepare_batch_compact([pk], [msg], [sig])
    assert host_ok.all()
    return tuple(_tile(a, lanes) for a in args)

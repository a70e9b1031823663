"""Multi-device sharding tests on the virtual 8-CPU mesh (conftest forces
xla_force_host_platform_device_count=8, mirroring the driver's dryrun)."""

import numpy as np
import pytest

from tmtpu.tpu import sharding as sh


def test_power_limbs_roundtrip():
    powers = [0, 1, 8191, 8192, 10**12, 2**62]
    limbs = sh.powers_to_limbs(powers)
    sums = limbs.sum(axis=1)
    assert sh.limb_sums_to_int(sums) == sum(powers)


def _powers_to_limbs_loop(powers) -> np.ndarray:
    """The double loop powers_to_limbs was until PR 33: the reference."""
    out = np.zeros((sh.POWER_LIMBS, len(powers)), dtype=np.int32)
    for i, p in enumerate(powers):
        v = int(p)
        for j in range(sh.POWER_LIMBS):
            out[j, i] = v & ((1 << sh.POWER_RADIX) - 1)
            v >>= sh.POWER_RADIX
        assert v == 0, "voting power exceeds 65 bits"
    return out


_EDGE_POWERS = [0, 1, 2**13 - 1, 2**13, 2**62, 2**63 - 1]


@pytest.mark.parametrize("powers", [
    *([v] for v in _EDGE_POWERS),
    _EDGE_POWERS,
    np.asarray(_EDGE_POWERS, dtype=np.int64),
    np.random.default_rng(33).integers(0, 2**53, 9500, dtype=np.int64),
    [],
], ids=[*(f"one-{v:#x}" for v in _EDGE_POWERS), "list", "int64-array",
        "9500-drawn", "empty"])
def test_power_limbs_equal_the_loop(powers):
    limbs = sh.powers_to_limbs(powers)
    assert limbs.dtype == np.int32
    assert limbs.shape == (sh.POWER_LIMBS, len(powers))
    assert np.array_equal(limbs, _powers_to_limbs_loop(powers))
    # the column sums (what the device reduces) give back the total
    sums = limbs.astype(np.int64).sum(axis=1)
    assert sh.limb_sums_to_int(sums) == sum(int(p) for p in powers)
    # written in place into the first lanes of a wider operand
    wide = np.zeros((sh.POWER_LIMBS, len(powers) + 7), dtype=np.int32)
    sh.powers_to_limbs(powers, out=wide[:, :len(powers)])
    assert np.array_equal(wide[:, :len(powers)], limbs)
    assert not wide[:, len(powers):].any()


@pytest.mark.parametrize("powers", [
    [5, -1], np.asarray([-2**63], dtype=np.int64), [2**65], [1, 2**66],
    np.asarray([2**63], dtype=np.uint64),
], ids=["negative", "int64-min", "65-bits", "66-bits", "uint64-top-bit"])
def test_power_limbs_refuse_what_no_int64_power_is(powers):
    with pytest.raises(ValueError):
        sh.powers_to_limbs(powers)


def test_dryrun_multichip_8():
    pytest.importorskip("cryptography")  # dryrun's vote-gen oracle
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_entry_shape():
    """entry() hands the driver a jittable (fn, args) pair with coherent
    lane shapes — checked WITHOUT compiling (the ~100s XLA:CPU compile
    plus full numeric run is the slow twin below, and the driver's own
    dryrun_multichip certifies the same entry at >=1k lanes against CPU
    oracles on every round)."""
    import __graft_entry__ as ge

    fn, args = ge.entry()
    assert callable(fn)
    pk_b, r_b, s_b, h_b, powers, table = args
    lanes = pk_b.shape[-1]
    assert r_b.shape[-1] == s_b.shape[-1] == h_b.shape[-1] == lanes
    assert powers.shape == (5, lanes)


@pytest.mark.slow  # one fresh XLA:CPU compile of the tally entry (~100s)
def test_entry_compiles():
    import jax

    import __graft_entry__ as ge

    fn, args = ge.entry()
    mask, power_sums, bits = jax.block_until_ready(jax.jit(fn)(*args))
    assert np.asarray(mask).all()
    assert sh.limb_sums_to_int(power_sums) == 1000 * 32


@pytest.mark.slow  # Pallas interpret-mode compile dominates (~2 min)
def test_sharded_kernel_step_cpu_mesh():
    """The pod-scale fused-kernel path (shard_map + Pallas interpret mode)
    agrees with the XLA-graph twin on an 8-device CPU mesh."""
    import jax
    import jax.numpy as jnp

    n = 8
    mesh = sh.make_mesh(n)
    lanes = 32 * n
    pk_b, r_b, s_b, h_b = sh.example_batch(lanes)
    # corrupt one lane per shard half to exercise the mask path
    bad = np.asarray(s_b).copy()
    bad[0, 5] ^= 1
    s_bad = jnp.asarray(bad)
    powers = jnp.asarray(sh.powers_to_limbs([7] * lanes))

    step = sh.sharded_verify_tally_kernel(mesh, tile=32, interpret=True)
    mask, power_sums, bits = jax.block_until_ready(
        step(pk_b, r_b, s_bad, h_b, powers))

    ref_step = sh.sharded_verify_tally_compact(mesh)
    from tmtpu.tpu import verify as tv

    table = tv.base_table_f32()
    rmask, rsums, rbits = jax.block_until_ready(
        ref_step(pk_b, r_b, s_bad, h_b, powers, table))

    assert np.array_equal(np.asarray(mask), np.asarray(rmask))
    assert not np.asarray(mask)[5]
    assert np.asarray(mask).sum() == lanes - 1
    assert sh.limb_sums_to_int(power_sums) == 7 * (lanes - 1)
    assert sh.limb_sums_to_int(rsums) == 7 * (lanes - 1)
    assert np.array_equal(np.asarray(bits), np.asarray(rbits))


@pytest.mark.slow  # two XLA:CPU curve-graph compiles (~3 min)
def test_sharded_sr_and_k1_cpu_mesh():
    """All three curves shard over the mesh: the lane-sharded sr25519 and
    secp256k1 steps agree with the unsharded batch verifiers on an
    8-device CPU mesh, mixed valid/corrupt lanes."""
    import hashlib

    import jax
    import jax.numpy as jnp

    from tmtpu.crypto import secp256k1 as k1
    from tmtpu.crypto import sr25519 as sr
    from tmtpu.tpu import dispatch
    from tmtpu.tpu import k1_verify as kv
    from tmtpu.tpu import sr_verify as srv
    from tmtpu.tpu import verify as tv

    n = 8
    mesh = sh.make_mesh(n)
    lanes = 2 * n  # 16 lanes, 2 per device

    sr_keys = [sr.gen_priv_key_from_secret(b"shard-sr-%d" % i)
               for i in range(lanes)]
    sr_msgs = [b"sharded-sr-%d" % i for i in range(lanes)]
    sr_sigs = [bytearray(k.sign(m)) for k, m in zip(sr_keys, sr_msgs)]
    sr_sigs[3][1] ^= 1  # corrupt one lane
    sr_sigs = [bytes(s) for s in sr_sigs]
    sr_pks = [k.pub_key().bytes() for k in sr_keys]

    packed, host_ok = srv.prepare_sr_batch_packed(sr_pks, sr_msgs, sr_sigs)
    assert host_ok.all()
    step = sh.sharded_verify_sr(mesh)
    mask = np.asarray(jax.block_until_ready(
        step(jnp.asarray(packed), tv.base_table_f32())))
    want, _ = dispatch.device_verify("sr25519", sr_pks, sr_msgs, sr_sigs)
    assert np.array_equal(mask, np.asarray(want))
    assert not mask[3] and mask.sum() == lanes - 1

    k1_keys = [
        k1.PrivKeySecp256k1(
            (int.from_bytes(hashlib.sha256(b"shard-k1-%d" % i).digest(),
                            "big") % (k1.N - 1) + 1).to_bytes(32, "big"))
        for i in range(lanes)
    ]
    k1_msgs = [b"sharded-k1-%d" % i for i in range(lanes)]
    k1_sigs = [bytearray(k.sign(m)) for k, m in zip(k1_keys, k1_msgs)]
    k1_sigs[6][40] ^= 1
    k1_sigs = [bytes(s) for s in k1_sigs]
    k1_pks = [k.pub_key().bytes() for k in k1_keys]

    packed, host_ok = kv.prepare_k1_batch_packed(k1_pks, k1_msgs, k1_sigs)
    kstep = sh.sharded_verify_k1(mesh)
    kmask = np.asarray(jax.block_until_ready(
        kstep(jnp.asarray(packed), kv.base_table_f32()))) & host_ok
    kwant, _ = dispatch.device_verify("secp256k1", k1_pks, k1_msgs,
                                     k1_sigs)
    assert np.array_equal(kmask, np.asarray(kwant))
    assert not kmask[6] and kmask.sum() == lanes - 1

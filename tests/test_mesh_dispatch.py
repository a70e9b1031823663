"""Mesh-dispatch tests on a forced 4-device CPU mesh (conftest forces
xla_force_host_platform_device_count=8; TMTPU_MESH_DEVICES=4 takes the
first four). ISSUE 6 acceptance: a sharded flush returns bit-exact
masks/tallies vs the single-device path, padding lanes never leak into
the tally, and killing the sharded path mid-flush degrades
mesh -> single-device -> CPU-serial with zero wrong results.

The non-slow tests share ONE padded mesh shape (128 lanes) so the whole
tier-1 portion costs a single fresh XLA:CPU compile; exactness is
checked against the serial CPU oracle (ed25519_ref), which tier-1
separately proves equal to the single-device device path
(test_tpu_verify differential tests at the same 64 bucket). The direct
mesh-vs-single-device graph comparison — two more curve-graph compiles
— rides the slow marker with sr25519/secp256k1, like
tests/test_sharding.py's sharded twins.
"""

import threading

import numpy as np
import pytest

from tmtpu.crypto import batch as crypto_batch
from tmtpu.crypto import ed25519 as ed
from tmtpu.crypto import ed25519_ref as ref
from tmtpu.crypto import sigcache
from tmtpu.libs import breaker as bk
from tmtpu.libs import metrics as _m
from tmtpu.tpu import dispatch
from tmtpu.tpu import mesh_dispatch as md


@pytest.fixture
def mesh4(monkeypatch):
    monkeypatch.setenv("TMTPU_MESH_DEVICES", "4")
    monkeypatch.setenv("TMTPU_SHARD_MIN_LANES", "1")
    # a SidecarServer started with mesh knobs writes them into the
    # process-wide overrides: whatever a test set goes back on every exit
    saved = dict(md._cfg)
    md.reset()
    md.breaker().reset()
    bk.get(crypto_batch.BREAKER_NAME).reset()
    yield
    md.set_overrides(**saved)
    md.reset()
    md.breaker().reset()
    bk.get(crypto_batch.BREAKER_NAME).reset()


def _ed_batch(n, tag, bad=()):
    """n distinct signed lanes (raw bytes) with per-lane powers; indices
    in ``bad`` get a flipped signature byte."""
    pks, msgs, sigs, powers = [], [], [], []
    for i in range(n):
        priv = ed.gen_priv_key_from_secret(b"%s-%d" % (tag, i))
        msg = b"%s msg %d" % (tag, i)
        sig = priv.sign(msg)
        if i in bad:
            flip = bytearray(sig)
            flip[0] ^= 0xFF
            sig = bytes(flip)
        pks.append(priv.pub_key().bytes())
        msgs.append(msg)
        sigs.append(sig)
        powers.append(100 + 7 * i)
    return pks, msgs, sigs, powers


def test_mesh_tally_bit_exact(mesh4):
    """THE acceptance scenario: a sharded flush returns exactly the
    per-lane mask and vote-power tally the serial CPU oracle computes
    (tier-1 proves oracle == single-device separately; the direct
    graph-vs-graph comparison is in the slow test below)."""
    pks, msgs, sigs, powers = _ed_batch(40, b"mesh-eq", bad={3, 17})
    mask_m, tally_m = dispatch.device_verify("ed25519", pks, msgs, sigs,
                                             powers)
    want = np.array([ref.verify(pk, m, s)
                     for pk, m, s in zip(pks, msgs, sigs)], dtype=bool)
    assert np.array_equal(np.asarray(mask_m), want)
    assert not mask_m[3] and not mask_m[17] and mask_m[0]
    assert tally_m == sum(p for i, p in enumerate(powers)
                          if i not in (3, 17))
    # the mask flush reuses the same sharded callable (zero powers)
    mask_v, none = dispatch.device_verify("ed25519", pks, msgs, sigs)
    assert none is None and len(md._state["fns"]) == 1
    assert np.array_equal(np.asarray(mask_v), want)
    snap = md.snapshot()
    assert snap["devices"] == 4
    assert snap["dispatches"] == 2
    # equal shards by construction: the quantum pads to 32 x n_devices
    occ = set(snap["occupancy_lanes"].values())
    assert len(snap["occupancy_lanes"]) == 4 and len(occ) == 1


def test_padding_lanes_never_enter_the_tally(mesh4):
    """A row's prepare replicates lane 0's BYTES into the pad lanes, so
    they VERIFY true on device — only their zeroed power limbs keep them
    out of the psum. 33 lanes pad to 128 on a 4-device mesh: 95 potential
    phantom contributions if the zeroing slips."""
    pks, msgs, sigs, powers = _ed_batch(33, b"mesh-pad")
    mask, tally = dispatch.device_verify("ed25519", pks, msgs, sigs, powers)
    assert md.dispatch_count() == 1
    assert len(mask) == 33 and bool(np.all(mask))
    assert tally == sum(powers)


def _prepare_then_pad(pks, msgs, sigs, padded):
    """Host prep as it was until PR 33, the reference: bytes() a lane, the
    lengths a lane, hashlib and Python ints for h, four transposed copies,
    then pad_packed's concatenate."""
    import hashlib

    B = len(sigs)
    pks_b = [bytes(p) for p in pks]
    sigs_b = [bytes(s) for s in sigs]
    len_ok = np.array([len(pks_b[i]) == 32 and len(sigs_b[i]) == 64
                       for i in range(B)], dtype=bool)
    pks_b = [p if ok else bytes(32) for p, ok in zip(pks_b, len_ok)]
    sigs_b = [s if ok else bytes(64) for s, ok in zip(sigs_b, len_ok)]
    host_ok = len_ok & np.array(
        [int.from_bytes(s[32:], "little") < ref.L for s in sigs_b])
    packed = np.zeros((128, B), dtype=np.uint8)
    for i, (p, s, m) in enumerate(zip(pks_b, sigs_b, msgs)):
        h = int.from_bytes(hashlib.sha512(s[:32] + p + bytes(m)).digest(),
                           "little") % ref.L
        lane = p + s[:32] + (s[32:] if host_ok[i] else bytes(32)) \
            + h.to_bytes(32, "little")
        packed[:, i] = np.frombuffer(lane, dtype=np.uint8)
        y = int.from_bytes(p, "little") & ((1 << 255) - 1)
        host_ok[i] &= y < ref.P
    return dispatch.pad_packed(packed, padded), host_ok


def _lanes_with_every_host_refusal(B, seed, as_type):
    """B signed lanes; where B allows, one each of: a short key, a long
    signature, s = L, s = 2^256 - 1, A.y = p (non-canonical) and A.y = p
    with the sign bit set. ``as_type`` wraps every lane's three fields."""
    pks, msgs, sigs, _ = _ed_batch(B, b"prep-%d" % seed)
    msgs = [m * (1 + i % 5) for i, m in enumerate(msgs)]
    msgs[0] = b""
    bad_y = ref.P.to_bytes(32, "little")
    edits = [
        lambda i: pks.__setitem__(i, pks[i][:31]),
        lambda i: sigs.__setitem__(i, sigs[i] + b"\x00"),
        lambda i: sigs.__setitem__(
            i, sigs[i][:32] + ref.L.to_bytes(32, "little")),
        lambda i: sigs.__setitem__(i, sigs[i][:32] + b"\xff" * 32),
        lambda i: pks.__setitem__(i, bad_y),
        lambda i: pks.__setitem__(i, bad_y[:31] + bytes([bad_y[31] | 0x80])),
    ]
    # lane 0 stays good where it can: it is the lane the pad replicates
    for k, edit in enumerate(edits):
        if B > 1 and 1 + 2 * k < B:
            edit(1 + 2 * k)
    if B == 1:
        edits[seed % len(edits)](0)
    return ([as_type(p) for p in pks], [as_type(m) for m in msgs],
            [as_type(s) for s in sigs])


@pytest.mark.parametrize("as_type", [bytes, bytearray, memoryview],
                         ids=["bytes", "bytearray", "memoryview"])
@pytest.mark.parametrize("bucket", [False, True], ids=["at-B", "at-bucket"])
@pytest.mark.parametrize("B", [1, 63, 64, 167, 300])
def test_prepare_at_padded_equals_prepare_then_pad(B, bucket, as_type,
                                                   monkeypatch):
    """The operand a flush now builds in one pass, at the padded width,
    is byte for byte the one it used to build and then pad — through the
    native library and through the numpy/hashlib path — and refuses the
    same lanes."""
    from tmtpu.tpu import verify as tv

    padded = dispatch.padded_lanes(B + 1 if bucket and B == 64 else B) \
        if bucket else B
    pks, msgs, sigs = _lanes_with_every_host_refusal(B, B, as_type)
    want, want_ok = _prepare_then_pad(pks, msgs, sigs, padded)
    assert B == 1 or not want_ok.all()
    for no_native in ("", "1"):
        monkeypatch.setenv("TMTPU_NO_NATIVE", no_native)
        got, got_ok = tv.prepare_batch_packed(pks, msgs, sigs, padded)
        assert got.dtype == np.uint8 and got.shape == (128, padded)
        assert np.array_equal(got_ok, want_ok)
        assert np.array_equal(got, want)
    # no width given: the lanes alone, as every other caller takes them
    got, got_ok = tv.prepare_batch_packed(pks, msgs, sigs)
    assert np.array_equal(got, want[:, :B]) and np.array_equal(got_ok,
                                                               want_ok)


@pytest.mark.parametrize("curve", list(dispatch.CURVES))
def test_pad_lanes_replicate_lane_0_with_zero_power_limbs(curve,
                                                          monkeypatch):
    """For every row of CURVES a flush hands its step the operand at the
    padded width with lanes >= B replicating lane 0, and — where it carries
    power limbs — zero limbs there and under every host-refused lane."""
    import dataclasses

    from tmtpu.tpu import sharding as sh

    row = dispatch.CURVES[curve]
    B, seen = 40, {}
    rng = np.random.default_rng(7)
    klen = 33 if curve == "secp256k1" else 32
    pks = [rng.integers(0, 256, klen, dtype=np.uint8).tobytes()
           for _ in range(B)]
    sigs = [rng.integers(0, 256, 64, dtype=np.uint8).tobytes()
            for _ in range(B)]
    sigs[3] = sigs[3][:63]  # one lane the host refuses on every curve
    msgs = [b"pad-lane msg %d" % i for i in range(B)]
    powers = [1000 + i for i in range(B)]

    def step(packed, limbs, *table):
        seen["packed"], seen["limbs"] = np.asarray(packed), np.asarray(limbs)
        return (np.ones(packed.shape[1], dtype=bool),
                np.zeros(sh.POWER_LIMBS, dtype=np.int32), None)

    fake = dataclasses.replace(row, tally_kernel=step, tally_xla=step,
                               table=lambda: None)
    monkeypatch.setenv("TMTPU_TPU_IMPL", "xla")
    mask, _tally = dispatch._flush(fake, pks, msgs, sigs, powers, 0, None)
    alone, host_ok = row.prepare(pks, msgs, sigs)
    padded = dispatch.padded_lanes(B)
    packed, limbs = seen["packed"], seen["limbs"]
    assert padded > B and packed.shape == (alone.shape[0], padded)
    assert np.array_equal(packed[:, :B], alone)
    assert np.array_equal(packed[:, B:],
                          np.repeat(alone[:, :1], padded - B, axis=1))
    assert limbs.shape == (sh.POWER_LIMBS, padded) and not limbs[:, B:].any()
    assert not host_ok[3] and not mask[3]
    assert np.array_equal(
        limbs[:, :B],
        sh.powers_to_limbs([p if ok else 0 for p, ok in zip(powers, host_ok)]))


def test_route_threshold_and_mesh_off(mesh4, monkeypatch):
    assert md.route("ed25519", 1)  # shard_min_lanes=1 via fixture
    monkeypatch.setenv("TMTPU_SHARD_MIN_LANES", "64")
    assert not md.route("ed25519", 63)
    assert md.route("ed25519", 64)
    # mesh_devices=1 is the off switch: no 2-device mesh can exist
    monkeypatch.setenv("TMTPU_MESH_DEVICES", "1")
    md.reset()
    assert not md.route("ed25519", 10_000)


def test_fallback_ladder_mesh_to_single_to_serial(mesh4, monkeypatch):
    """Killing the sharded path mid-flush degrades mesh -> single-device
    -> CPU-serial with zero wrong results, and a mesh failure never
    counts against the single-device crypto.tpu breaker."""
    monkeypatch.setattr(crypto_batch, "_TPU_MIN_BATCH", 1)
    monkeypatch.setattr(crypto_batch, "_tpu_usable", True)
    sigcache.DEFAULT.set_enabled(False)
    try:
        tpu_br = bk.get(crypto_batch.BREAKER_NAME)
        mesh_failures0 = _m.crypto_breaker_failures.summary_series().get(
            "breaker=crypto.mesh", 0)

        def flush(tag, bad=()):
            pks, msgs, sigs, powers = _ed_batch(40, tag, bad=bad)
            bv = crypto_batch.TPUBatchVerifier()
            for i in range(40):
                bv.add(ed.PubKeyEd25519(pks[i]), msgs[i], sigs[i],
                       powers[i])
            all_ok, mask, tallied = bv.verify_tally()
            want = sum(p for i, p in enumerate(powers) if i not in bad)
            return all_ok, mask, tallied, want

        # The single-device graph is stood in for by the serial oracle:
        # compiling verify_tally_packed for real here is a ~90s XLA:CPU
        # compile tier-1 can't afford, and the routing ladder under test
        # doesn't care what answers the single-device rung (the real
        # graph's exactness is the slow test's job).
        def single_oracle(pks, msgs, sigs, powers):
            ok = np.array([ref.verify(pk, m, s)
                           for pk, m, s in zip(pks, msgs, sigs)],
                          dtype=bool)
            return ok, sum(int(p) for p, o in zip(powers, ok) if o)

        mesh_calls = []

        def flush_with(single):
            """dispatch._flush with the mesh rung blowing up and the
            single-device rung answered by ``single``."""
            def fake_flush(row, pks, msgs, sigs, powers, min_lanes, mesh):
                if mesh is not None:
                    mesh_calls.append(1)
                    raise RuntimeError("collective blew up")
                return single(pks, msgs, sigs, powers)
            monkeypatch.setattr(dispatch, "_flush", fake_flush)

        # rung 1: mesh dispatch raises -> single-device answers, exact
        flush_with(single_oracle)
        all_ok, mask, tallied, want = flush(b"ladder-1", bad={5})
        assert not all_ok and mask[0] and not mask[5]
        assert tallied == want
        assert md.breaker().snapshot()["failures"] == 1
        # mesh failures stay mesh-local, never against crypto.tpu
        assert tpu_br.snapshot()["failures"] == 0
        assert _m.crypto_breaker_failures.summary_series().get(
            "breaker=crypto.mesh", 0) == mesh_failures0 + 1

        # rung 2: single-device ALSO raises -> CPU-serial, still exact
        def single_boom(*a, **kw):
            raise RuntimeError("device fell over")

        flush_with(single_boom)
        all_ok, mask, tallied, want = flush(b"ladder-2", bad={7})
        assert not all_ok and mask[0] and not mask[7]
        assert tallied == want
        assert tpu_br.snapshot()["failures"] == 1  # a real device failure

        # rung 3: an OPEN mesh breaker skips the mesh without an attempt
        # (trip_permanent pins the window open regardless of test timing)
        md.breaker().reset()
        md.breaker().trip_permanent("mesh declared down for rung 3")
        assert md.breaker().state == bk.OPEN
        del mesh_calls[:]
        flush_with(lambda pks, msgs, sigs, powers:
                   (np.ones(len(sigs), dtype=bool), sum(powers)))
        tpu_br.reset()
        all_ok, mask, tallied, want = flush(b"ladder-3")
        assert all_ok and tallied == want
        assert mesh_calls == []  # breaker-open: mesh never touched
    finally:
        sigcache.DEFAULT.set_enabled(True)


def test_sidecar_two_clients_split_across_shards(mesh4, monkeypatch,
                                                 tmp_path):
    """Sidecar acceptance: two clients' lanes coalesce into one joint
    dispatch AND that dispatch shards across the mesh — per-chip
    occupancy lands in the daemon's Stats."""
    from tmtpu.sidecar.client import SidecarClient
    from tmtpu.sidecar.server import SidecarServer

    monkeypatch.setattr(crypto_batch, "_TPU_MIN_BATCH", 1)
    monkeypatch.setattr(crypto_batch, "_tpu_usable", True)
    srv = SidecarServer(f"unix://{tmp_path}/mesh.sock", backend="tpu",
                        shard_min_lanes=1)
    srv.start()
    try:
        srv.coalescer.scheduler.gather_wait_s = lambda pending: 0.5
        results = {}
        barrier = threading.Barrier(2)

        def run(name, n, bad):
            pks, msgs, sigs, powers = _ed_batch(
                n, b"mesh-sc-%s" % name.encode(), bad=bad)
            lanes = list(zip(pks, msgs, sigs, powers))
            client = SidecarClient(srv.addr, client_id=name)
            try:
                barrier.wait(timeout=10)
                results[name] = client.verify("ed25519", lanes,
                                              tally=True, deadline_s=120)
            finally:
                client.close()

        ts = [threading.Thread(target=run, args=("a", 18, {1})),
              threading.Thread(target=run, args=("b", 22, {2}))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert set(results) == {"a", "b"}
        mask_a, _ta, info_a = results["a"]
        mask_b, _tb, info_b = results["b"]
        assert mask_a == [i != 1 for i in range(18)]
        assert mask_b == [i != 2 for i in range(22)]
        assert info_a["dispatch_id"] == info_b["dispatch_id"]
        assert info_a["dispatch_clients"] == 2
        stats = srv.snapshot()
        assert stats["coalescer"]["mesh_dispatches"] >= 1
        occ = stats["mesh"]["occupancy_lanes"]
        assert len(occ) == 4 and len(set(occ.values())) == 1
    finally:
        srv.stop()
        crypto_batch.set_default_backend("cpu")


@pytest.mark.slow  # three fresh curve-graph compiles (~minutes)
def test_mesh_exact_vs_single_device_all_curves(mesh4):
    """The direct graph-vs-graph acceptance: the sharded mesh path and
    the unsharded single-device path return identical masks (and, for
    ed25519, identical tallies) on mixed valid/corrupt lanes."""
    import hashlib

    from tmtpu.crypto import secp256k1 as k1
    from tmtpu.crypto import sr25519 as sr

    def both(curve, pks, msgs, sigs, powers=None):
        """The same flush lane-sharded over the mesh and on one device."""
        row = dispatch.CURVES[curve]
        return (dispatch._flush(row, pks, msgs, sigs, powers, 0,
                                md.get_mesh()),
                dispatch._flush(row, pks, msgs, sigs, powers, 0, None))

    pks, msgs, sigs, powers = _ed_batch(40, b"mesh-sd", bad={3, 17})
    (mask_m, tally_m), (mask_s, tally_s) = both("ed25519", pks, msgs, sigs,
                                                powers)
    assert np.array_equal(np.asarray(mask_m), np.asarray(mask_s))
    assert tally_m == tally_s

    n = 16
    sr_keys = [sr.gen_priv_key_from_secret(b"mesh-sr-%d" % i)
               for i in range(n)]
    sr_msgs = [b"mesh-sr-msg-%d" % i for i in range(n)]
    sr_sigs = [bytearray(k.sign(m)) for k, m in zip(sr_keys, sr_msgs)]
    sr_sigs[3][1] ^= 1
    sr_sigs = [bytes(s) for s in sr_sigs]
    sr_pks = [k.pub_key().bytes() for k in sr_keys]
    (mask, _), (want, _) = both("sr25519", sr_pks, sr_msgs, sr_sigs)
    assert np.array_equal(np.asarray(mask), np.asarray(want))
    assert not mask[3] and mask.sum() == n - 1

    k1_keys = [
        k1.PrivKeySecp256k1(
            (int.from_bytes(hashlib.sha256(b"mesh-k1-%d" % i).digest(),
                            "big") % (k1.N - 1) + 1).to_bytes(32, "big"))
        for i in range(n)
    ]
    k1_msgs = [b"mesh-k1-msg-%d" % i for i in range(n)]
    k1_sigs = [bytearray(k.sign(m)) for k, m in zip(k1_keys, k1_msgs)]
    k1_sigs[6][40] ^= 1
    k1_sigs = [bytes(s) for s in k1_sigs]
    k1_pks = [k.pub_key().bytes() for k in k1_keys]
    (kmask, _), (kwant, _) = both("secp256k1", k1_pks, k1_msgs, k1_sigs)
    assert np.array_equal(np.asarray(kmask), np.asarray(kwant))
    assert not kmask[6] and kmask.sum() == n - 1

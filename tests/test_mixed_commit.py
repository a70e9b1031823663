"""A commit of a validator set whose keys are of three types, through
``types/commit_verify.verify_commit`` on the device path (XLA:CPU here),
against the plain reference the benchmark's mixed cell is judged by
(benchmarks/reference/mixed_commits.py, which imports nothing of tmtpu).

30 validators, 10 a key type, one of each absent: every flush is 9 lanes a
type, above ``_TPU_MIN_BATCH``, and pads to the one 64-lane shape a type —
three XLA:CPU compiles (about 35 + 55 + 50 s cold, seconds from the
persistent cache) that the whole module shares.
"""
import hashlib
import re

import pytest

from benchmarks.reference import mixed_commits as ref
from tmtpu.crypto import batch as crypto_batch
from tmtpu.crypto import ed25519, secp256k1, sr25519
from tmtpu.libs import metrics
from tmtpu.types import commit_verify as cv
from tmtpu.types.block import BlockID, Commit, CommitSig
from tmtpu.types.validator import Validator, ValidatorSet

SEED, N, CHAIN = 38, 30, "mixed-commit-test"
ABSENT = ref.absent_by_curve(3)
PUB = {ref.ED25519: ed25519.PubKeyEd25519, ref.SR25519: sr25519.PubKeySr25519,
       ref.SECP256K1: secp256k1.PubKeySecp256k1}


@pytest.fixture(scope="module")
def vals():
    return ref.make_valset(SEED, N)


@pytest.fixture(scope="module")
def pvals(vals):
    return ValidatorSet([Validator(PUB[c](p), pw) for c, p, pw in
                         zip(vals.curves, vals.pubs, vals.powers)])


@pytest.fixture(scope="module")
def commit(vals):
    return ref.make_commit(vals, SEED, 0, CHAIN, ABSENT)


def program_outcome(pvals, vals, c):
    """The program's verdict on ``c`` in the reference's terms."""
    bid = BlockID(hash=c.block_hash, parts_total=c.parts_total,
                  parts_hash=c.parts_hash)
    sigs = [CommitSig(flag, vals.addrs[i] if flag != ref.ABSENT else b"",
                      ts, sig) for i, (flag, ts, sig) in enumerate(c.sigs)]
    try:
        cv.verify_commit(pvals, CHAIN, bid, c.height,
                         Commit(c.height, c.round, bid, sigs), backend="tpu")
    except cv.ErrNotEnoughVotingPowerSigned as e:
        return ("low_power", e.got, e.needed)
    except cv.VerificationError as e:
        return ("bad_sig", int(re.search(r"#(\d+)", str(e)).group(1)))
    return ("ok",)


def slot_of(vals, c, curve, nth=0):
    return [i for i in ref.present_slots(c) if vals.curves[i] == curve][nth]


def device_lanes():
    return {c: metrics.crypto_batch_size.totals(curve=c, backend="cpu")[1]
            for c in ref.CURVES}


# -- the reference's sr25519 against the public vectors -----------------------

# RFC 9496 appendix A.1: the encodings of 0*B .. 4*B
RISTRETTO_MULTIPLES = [
    "0000000000000000000000000000000000000000000000000000000000000000",
    "e2f2ae0a6abc4e71a884a961c500515f58e30b6aa582dd8db6a65945e08d2d76",
    "6a493210f7499cd17fecb510ae0cea23a110e8d5b901f8acadd3095c73a3b919",
    "94741f5d5d52755ece4f23f044ee27d5d1ea1e2bd196b462166b16152a9d0259",
    "da80862773358b466ffadfe0b3293ab3d9fd53c5ea6c955358f568322daf6a57",
]


@pytest.mark.parametrize("k", range(5))
def test_reference_ristretto_generator_multiples(k):
    want = bytes.fromhex(RISTRETTO_MULTIPLES[k])
    assert ref.ristretto_encode(ref.base_mul(k)) == want
    assert ref.ristretto_encode(ref.pt_mul(k, ref.BASE)) == want
    back = ref.ristretto_decode(want)
    assert back is not None and ref.ristretto_encode(back) == want


@pytest.mark.parametrize("bad", [
    # RFC 9496 appendix A.3: a non-canonical field element, a negative
    # one, and one whose square root does not exist
    "00ffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff",
    "0100000000000000000000000000000000000000000000000000000000000000",
    "26948d35ca62e643e26a83177332e6b6afeb9d08e4268b650f1f5bbd8d81d371",
])
def test_reference_ristretto_refuses_bad_encodings(bad):
    assert ref.ristretto_decode(bytes.fromhex(bad)) is None


def test_reference_merlin_simple_transcript():
    """merlin's own "simple transcript" vector (transcript.rs tests)."""
    t = ref.Transcript(b"test protocol")
    t.append(b"some label", b"some data")
    assert t.challenge(b"challenge", 32).hex() == \
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615"


@pytest.mark.parametrize("data", [b"", b"abc", b"\xa3" * 200, b"x" * 135,
                                  b"y" * 136])
def test_reference_keccak_and_ripemd_against_hashlib(data):
    assert ref.sha3_256(data) == hashlib.sha3_256(data).digest()
    try:
        want = hashlib.new("ripemd160", data).digest()
    except ValueError:
        pytest.skip("this host's OpenSSL has no ripemd160")
    assert ref._ripemd160_plain(data) == want


# -- keys, addresses, order ---------------------------------------------------

def program_priv(curve, secret):
    if curve == ref.ED25519:
        return ed25519.PrivKeyEd25519(secret)
    if curve == ref.SR25519:
        return sr25519.PrivKeySr25519(secret)
    d = int.from_bytes(secret, "big") % (secp256k1.N - 1) + 1
    return secp256k1.PrivKeySecp256k1(d.to_bytes(32, "big"))


@pytest.mark.parametrize("curve", ref.CURVES)
def test_keys_and_addresses_equal_the_references(curve):
    for i in range(6):
        secret = hashlib.sha256(b"key-%d" % i).digest()
        _priv, pub, addr = ref.make_key(curve, secret)
        ppub = program_priv(curve, secret).pub_key()
        assert ppub.bytes() == pub and ppub.address() == addr
        assert PUB[curve](pub).address() == addr


def test_set_order_equals_the_references(vals, pvals):
    assert [v.address for v in pvals.validators] == vals.addrs
    assert [v.pub_key.type_value() for v in pvals.validators] == vals.curves
    # the three types interleave: no third of the set is of one type
    assert all(len(set(vals.curves[k:k + 10])) > 1 for k in (0, 10, 20))
    assert sorted(vals.curves) == sorted(ref.CURVES * 10)


@pytest.mark.parametrize("curve", ref.CURVES)
def test_signatures_cross_verify(curve):
    secret = hashlib.sha256(b"cross-" + curve.encode()).digest()
    priv, pub, _addr = ref.make_key(curve, secret)
    pk, msg = PUB[curve](pub), b"a message both sides sign"
    sig = ref.sign(curve, priv, pub, msg)
    assert pk.verify_signature(msg, sig)
    assert not pk.verify_signature(msg + b"!", sig)
    theirs = program_priv(curve, secret).sign(msg)
    assert ref.verify_signature(curve, pub, msg, theirs)
    assert not ref.verify_signature(curve, pub, msg + b"!", theirs)


# -- verify_commit against the reference --------------------------------------

def test_accepted_commit_takes_the_device_a_key_type(vals, pvals, commit):
    before = device_lanes()
    assert ref.verify_commit(vals, commit) == ("ok",)
    assert program_outcome(pvals, vals, commit) == ("ok",)
    after = device_lanes()
    assert {c: after[c] - before[c] for c in ref.CURVES} == \
        ref.present_by_curve(vals, commit) == dict.fromkeys(ref.CURVES, 9)


@pytest.mark.parametrize("curve", ref.CURVES)
@pytest.mark.parametrize("nth", [0, 8])
def test_tampered_lane_is_refused_at_the_references_lane(vals, pvals, commit,
                                                         curve, nth):
    bad = ref.tamper_signature(commit, slot_of(vals, commit, curve, nth))
    want = ref.verify_commit(vals, bad)
    assert want[0] == "bad_sig"
    assert program_outcome(pvals, vals, bad) == want


def test_high_s_twin_is_refused_though_its_equation_holds(vals, pvals, commit):
    at = slot_of(vals, commit, ref.SECP256K1, 4)
    twin = ref.k1_high_s_twin(commit.sigs[at][2])
    assert ref.k1_equation_holds(vals.pubs[at], commit.sign_bytes(at), twin)
    bad = ref.replace_signature(commit, at, twin)
    want = ref.verify_commit(vals, bad)
    assert want == ("bad_sig", ref.present_slots(commit).index(at))
    assert program_outcome(pvals, vals, bad) == want


def test_missing_marker_bit_is_refused(vals, pvals, commit):
    at = slot_of(vals, commit, ref.SR25519, 3)
    sig = bytearray(commit.sigs[at][2])
    sig[63] &= 0x7F
    bad = ref.replace_signature(commit, at, bytes(sig))
    want = ref.verify_commit(vals, bad)
    assert want == ("bad_sig", ref.present_slots(commit).index(at))
    assert program_outcome(pvals, vals, bad) == want


@pytest.mark.parametrize("n_nil", [8, 12])
def test_nil_heavy_tally_is_the_sum_of_fused_and_host_tallies(vals, pvals,
                                                              n_nil):
    c = ref.make_commit(vals, SEED, 1, CHAIN, ABSENT, n_nil)
    want = ref.verify_commit(vals, c)
    assert want == ("low_power", N - 3 - n_nil, N * 2 // 3)
    # nil votes of every key type: each type's tally is short of its lanes
    assert {vals.curves[i] for i, s in enumerate(c.sigs)
            if s[0] == ref.NIL} == set(ref.CURVES)
    assert program_outcome(pvals, vals, c) == want


def test_control_that_trusts_a_key_type_differs(vals, commit):
    bad = ref.tamper_signature(commit, slot_of(vals, commit, ref.SR25519))
    assert ref.verify_commit(vals, bad)[0] == "bad_sig"
    assert ref.verify_commit(vals, bad, trust=ref.SR25519) == ("ok",)


def test_pool_of_workers_equals_the_serial_reference(vals, commit):
    pool = ref.Pool(SEED, N, 1, workers=2)
    try:
        c = ref.plan_commit(vals, SEED, 0, CHAIN, ABSENT)
        pool.sign_commits([c])()
        assert c.sigs == commit.sigs        # a seed gives the same bytes
        bad = ref.tamper_signature(c, slot_of(vals, c, ref.SECP256K1, 2))
        assert pool.verify_commits(vals, [c, bad]) == \
            [("ok",), ref.verify_commit(vals, bad)]
    finally:
        pool.close()


# -- the batch layer on an interleaved flush ----------------------------------

@pytest.mark.parametrize("order", ["interleaved", "grouped", "with_strangers"])
def test_split_equals_a_filter_a_key_type(vals, pvals, commit, order):
    from tmtpu.tpu import dispatch

    items = [(pvals.validators[i].pub_key, commit.sign_bytes(i),
              commit.sigs[i][2], 1 + i) for i in ref.present_slots(commit)]
    if order == "grouped":
        items.sort(key=lambda it: it[0].type_value())
    if order == "with_strangers":
        # a signature of another length goes to the serial path
        items[4] = items[4][:2] + (items[4][2] + b"\x00", items[4][3])
        items[11] = items[11][:2] + (b"", items[11][3])
    groups, cpu_idx = crypto_batch.TPUBatchVerifier._split(
        items, dispatch.CURVES)
    assert cpu_idx == [i for i, it in enumerate(items) if len(it[2]) != 64]
    assert list(groups) == list(dict.fromkeys(
        it[0].type_value() for it in items if len(it[2]) == 64))
    for curve, (idx, pks, msgs, sigs, powers) in groups.items():
        want = [i for i, it in enumerate(items)
                if it[0].type_value() == curve and len(it[2]) == 64]
        assert idx == want
        assert pks == [items[i][0].bytes() for i in want]
        assert msgs == [items[i][1] for i in want]
        assert sigs == [items[i][2] for i in want]
        assert powers == [items[i][3] for i in want]


def test_flush_counts_its_key_types(vals, pvals, commit):
    n0, s0 = metrics.crypto_flush_curves.totals()
    assert program_outcome(pvals, vals, commit) == ("ok",)
    n1, s1 = metrics.crypto_flush_curves.totals()
    assert (n1 - n0, s1 - s0) == (1, 3)


def python_walked():
    return metrics.crypto_sr_python_transcript_lanes.summary_series().get(
        "", 0.0)


def test_python_transcript_walk_is_counted(monkeypatch):
    from tmtpu.tpu import sr_verify

    priv = sr25519.gen_priv_key_from_secret(b"walk")
    lanes = ([priv.pub_key().bytes()] * 3, [b"m"] * 3, [priv.sign(b"m")] * 3)
    before = python_walked()
    packed, ok = sr_verify.prepare_sr_batch_packed(*lanes)
    native = python_walked() - before
    monkeypatch.setenv("TMTPU_NO_NATIVE", "1")
    walked, ok2 = sr_verify.prepare_sr_batch_packed(*lanes)
    assert python_walked() - before \
        == native + 3
    assert ok.all() and ok2.all() and (packed == walked).all()

"""The plain references against the program at a toy size: the same
sign-bytes and envelopes, the same verdicts — and the controls, which
break one stated guarantee each, come out different."""
import pytest

from benchmarks.drivers import commit_verify as drv
from benchmarks.reference import commits as ref
from benchmarks.reference import kvstore as kv

CHAIN = "ref-test"


@pytest.fixture(scope="module")
def vals():
    return ref.make_valset(11, 40)


def _pvals(vals):
    from tmtpu.crypto import ed25519 as ed
    from tmtpu.types.validator import Validator, ValidatorSet

    return ValidatorSet([Validator(ed.PubKeyEd25519(p), 1)
                         for p in vals.pubs])


def test_sign_bytes_and_set_order_equal_the_programs(vals):
    pvals = _pvals(vals)
    assert [v.address for v in pvals.validators] == vals.addrs
    c = ref.make_commit(vals, 11, 0, CHAIN, n_absent=3, n_nil=5)
    _bid, _h, pc = drv._program_commit(c, vals)
    for i, (flag, _ts, _sig) in enumerate(c.sigs):
        if flag != ref.ABSENT:
            assert pc.vote_sign_bytes(CHAIN, i) == c.sign_bytes(i)
    assert c.present() == 37


def test_the_seed_never_changes_how_many_sign(vals):
    for seed in (0, 1, 2**31 + 5):
        for k in range(4):
            assert ref.make_commit(vals, seed, k, CHAIN, 3).present() == 37


def test_reference_and_program_agree_on_every_adversarial_commit(vals):
    import tmtpu.types.commit_verify  # noqa: F401 — binds verify_commit
    from tmtpu.crypto import batch as crypto_batch

    crypto_batch.set_default_backend("cpu")
    pvals = _pvals(vals)
    good = ref.make_commit(vals, 11, 1, CHAIN, 3)
    assert ref.verify_commit(vals, good) == ("ok",)
    cases = [("good", good)] + drv.adversarial(vals, 11, 5, CHAIN, 3)
    kinds = set()
    for label, c in cases:
        want = ref.verify_commit(vals, c)
        got = drv.call_entry(pvals, CHAIN, drv._program_commit(c, vals))
        assert got == want, label
        kinds.add(want[0])
    assert kinds == {"ok", "bad_sig", "low_power"}


def test_the_control_stops_at_quorum_and_so_differs(vals):
    cases = dict(drv.adversarial(vals, 11, 5, CHAIN, 3))
    late = cases["tampered_late"]
    assert ref.verify_commit(vals, late)[0] == "bad_sig"
    assert ref.verify_commit(vals, late, stop_at_quorum=True) == ("ok",)
    early = cases["tampered_early"]
    assert ref.verify_commit(vals, early, stop_at_quorum=True) == \
        ref.verify_commit(vals, early)


def test_envelope_equals_the_programs_and_tampering_is_refused():
    from tmtpu.crypto import ed25519 as ed
    from tmtpu.mempool import signed_tx

    key = kv.sender_key(3, 7)
    pub = key.public_key().public_bytes_raw()
    payload = b"k=" + bytes(range(200))
    tx = kv.envelope(payload, key, pub)
    prog_key = ed.gen_priv_key_from_secret(b"x")
    assert len(signed_tx.encode(payload, prog_key)) == len(tx)
    assert signed_tx.is_signed(tx)
    ppub, sig, body = signed_tx.parse(tx)
    assert body == payload
    assert ppub.verify_signature(signed_tx.sign_bytes(body), sig)
    ppub, sig, body = signed_tx.parse(kv.tamper(tx))
    assert not ppub.verify_signature(signed_tx.sign_bytes(body), sig)


def test_kvstore_reference():
    txs = [b"a=1", b"b=2", b"a=3", b"noequals", b"x=y=z"]
    assert kv.final_state(txs) == {b"a": b"3", b"b": b"2",
                                   b"noequals": b"noequals", b"x": b"y=z"}
    assert kv.exactly_once([b"a=1", b"q=9", b"b=2"],
                           [b"a=1", b"b=2", b"b=2"]) == (1, 1)

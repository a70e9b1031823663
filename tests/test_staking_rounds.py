"""A live validator of a chain whose validator set moves every height
(tmtpu/e2e/flood_round.py Network), against the plain reference
(benchmarks/reference/staking.py): Zipf powers with one validator past
2^52, power changes every height, a leave and a join every second height,
votes in gossip order. The node has to commit the reference's blocks —
their headers carry both sets' hashes, the proposer and the power-weighted
median time — store its sets byte for byte, and tally the reference's
powers. Beside it: ValidatorSet against the reference over seeded change
sets, the fused device tally at powers up to MaxTotalVotingPower, and the
span and counters of the update path. The chain runs on the serial
backend; the device flush is called directly."""
import os
import random
import time

import numpy as np
import pytest

from benchmarks.reference import commits as rc
from benchmarks.reference import light as rl
from benchmarks.reference import staking as st
from tests.test_live_rounds import validate_block_paths_moved
from tmtpu.abci import types as abci
from tmtpu.config.config import CryptoConfig
from tmtpu.crypto import batch as crypto_batch
from tmtpu.crypto import ed25519 as ed
from tmtpu.e2e import flood_round
from tmtpu.libs import metrics, trace
from tmtpu.privval.file_pv import FilePV
from tmtpu.tpu import dispatch
from tmtpu.types.genesis import GenesisDoc, GenesisValidator
from tmtpu.types.params import ConsensusParams
from tmtpu.types.validator import (MAX_TOTAL_VOTING_POWER, Validator,
                                   ValidatorSet)

N_VAL, N_HEIGHTS = 30, 12
SPEC = st.StakingSpec(4141, "stake-test", 1_700_000_000 * 10**9, N_VAL,
                      total_power=1 << 56, changes_per_height=3,
                      join_every=2, txs_per_block=2)


@pytest.fixture(scope="module")
def chain():
    return st.make_chain(SPEC, N_HEIGHTS)


def genesis_of(chain):
    p = chain.spec.params()
    g = GenesisDoc(
        p.chain_id, genesis_time=p.genesis_time_ns,
        consensus_params=ConsensusParams(block_max_bytes=p.block_max_bytes,
                                         block_max_gas=p.block_max_gas),
        validators=[GenesisValidator(ed.PubKeyEd25519(chain.keys.pubs[k]),
                                     power)
                    for k, power in chain.plan.genesis])
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
    monkeypatch.setattr(crypto_batch, "_default_backend", "cpu")
    crypto_batch.configure(CryptoConfig())
    home = str(tmp_path)
    pv = FilePV(ed.PrivKeyEd25519(
        chain.keys.privs[chain.plan.node_key].private_bytes_raw()),
        os.path.join(home, "key.json"), os.path.join(home, "state.json"))
    pv.save()
    node = flood_round.build_node(home, genesis_of(chain), pv)
    net = flood_round.Network(node, ChainScript(chain))
    yield net
    net.stop()


def _series(name):
    return dict(getattr(metrics, name).summary_series())


# -- the chain, live --------------------------------------------------------------

def test_the_reference_chain_moves_its_set_as_the_deployment_assumes(chain):
    plan = chain.plan
    powers = [p for _k, p in plan.genesis]
    assert sum(powers) == SPEC.total_power and max(powers) >= 1 << 52
    assert plan.sets[1].total == SPEC.total_power
    # a leave and a join every second height, three power changes each
    assert sorted(plan.joins.values()) == list(range(2, N_HEIGHTS + 1, 2))
    assert sorted(plan.leaves.values()) == list(range(2, N_HEIGHTS + 1, 2))
    for h in range(1, N_HEIGHTS + 1):
        vals = [t for t in chain.plan.txs[h] if t.startswith(st.VAL_PREFIX)]
        assert len(vals) == 3 + 2 * (h % 2 == 0)
        assert len(plan.sets[h + 2].members) == N_VAL
    # the node proposes nothing and no tx names it
    assert plan.node_key not in plan.proposers(N_HEIGHTS + 1)
    assert all(k != plan.node_key for h in range(1, N_HEIGHTS + 1)
               for k, _p in plan.updates[h])


def test_live_heights_commit_the_references_blocks_sets_and_tallies(
        chain, live):
    cs = live.cs
    tallies = {}

    def on_block(item):
        if item.type == "NewBlock" and cs.rs.last_commit is not None:
            lc = cs.rs.last_commit
            tallies[lc.height] = (lc.sum_voting_power(),
                                  lc.bit_array().true_indices())
        return False
    live.node["event_bus"].subscribe("t", on_block)
    upd0 = _series("state_validator_updates")
    spans0 = dict(trace.span_totals())
    live.start()
    live.play(range(1, N_HEIGHTS + 1), timeout=60)
    store, state_store = live.node["block_store"], live.node["state_store"]
    for hd in chain.heights:
        h = hd.block.height
        block = store.load_block(h)
        assert bytes(store.load_block_meta(h).block_id.hash) \
            == hd.block.hash, h
        # the proposer IncrementProposerPriority picks, the weighted median
        assert bytes(block.header.proposer_address) \
            == hd.vals.addrs[hd.proposer], h
        assert block.header.time == hd.block.time_ns, h
    # both sets of every height, byte for byte: addresses, powers,
    # priorities, the proposer, the total
    for k in range(1, N_HEIGHTS + 3):
        assert state_store.load_validators(k).encode() \
            == chain.plan.sets[k].encode(chain.keys), k
    # every join and leave took effect two heights on
    members = {k: {m.key for m in chain.plan.sets[k].members}
               for k in range(1, N_HEIGHTS + 3)}
    for key, h in chain.plan.joins.items():
        assert key not in members[h + 1] and key in members[h + 2]
    for key, h in chain.plan.leaves.items():
        assert key in members[h + 1] and key not in members[h + 2]
    # the last height's late precommits are in once the commit wait holds
    # every one of them
    deadline = time.time() + 20
    while not cs.rs.last_commit.has_all() and time.time() < deadline:
        time.sleep(0.05)
    lc = cs.rs.last_commit
    tallies[N_HEIGHTS] = (lc.sum_voting_power(),
                          lc.bit_array().true_indices())
    assert sorted(tallies) == list(range(1, N_HEIGHTS + 1))
    for h, (power, indices) in tallies.items():
        vals = chain.vals(h)
        assert power == st.vote_set_power(vals, indices), h
        assert power > vals.total_power * 2 // 3
    # the app's validator table is the reference's
    table = chain.app(N_HEIGHTS).validators
    query = live.node["proxy_app"].query
    for k in range(len(chain.keys.pubs)):
        pub = chain.keys.pubs[k]
        got = bytes(query.query_sync(abci.RequestQuery(
            path="/val", data=app_key(pub))).value)
        want = st.validator_update(pub, table[pub]) if pub in table else b""
        assert got == want, k
    # the counter moved by the reference's counts, the span once a height
    moved = {key: v - upd0.get(key, 0)
             for key, v in _series("state_validator_updates").items()}
    assert moved == {"kind=power": 3 * N_HEIGHTS,
                     "kind=join": N_HEIGHTS // 2,
                     "kind=leave": N_HEIGHTS // 2}
    spans = trace.span_totals()
    assert spans["state.update_validators"][0] \
        - spans0.get("state.update_validators", (0, 0))[0] == N_HEIGHTS


def test_a_height_on_a_moving_set_validates_its_block_once(chain, live):
    """Every height hands its executor a new state with new sets; the
    block is still validated in full once, at prevote, and its three
    repeats are answered from the executor's slot."""
    before = _series("state_validate_block")
    live.start()
    live.play(range(1, 5), timeout=60)
    moved = validate_block_paths_moved(
        before, lambda: live.cs.state.last_block_height == 4)
    assert moved == {"path=full": 4, "path=repeat": 3 * 4}


def app_key(pub):
    """The app's key of a validator: its PublicKey's encoding."""
    return b"\x0a\x20" + pub


# -- the set against the reference, step by step -----------------------------------

def _program_set(keys, members):
    return ValidatorSet.restore([
        Validator(ed.PubKeyEd25519(keys.pubs[m.key]), m.power, m.priority)
        for m in members])


def _rows(vs):
    return [(v.address, v.voting_power, v.proposer_priority)
            for v in vs.validators]


def _ref_rows(s):
    return [(m.address, m.power, m.priority) for m in s.members]


SCENARIOS = {
    # (validators, total power, changes a step, a leave/join every)
    "four_validators": (4, 40, 2, 3),
    "zipf_ten_thousand_power": (25, 250_000_000, 4, 2),
    "powers_past_2_52": (30, 1 << 56, 3, 2),
    "forced_rescale": (12, 1 << 40, 2, 4),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_validator_set_updates_and_rotation_are_the_references(
        scenario, monkeypatch):
    """50 seeded change sets a scenario (power changes, joins, leaves; in
    ``forced_rescale`` the heaviest validator drops to power 1 now and
    then, so that the spread passes twice the new total): after every
    change set and turn, equal members, powers, priorities and proposer."""
    n, total, changes, every = SCENARIOS[scenario]
    rescales = []
    real = st._rescale

    def counting(priorities, diff_max):
        if diff_max > 0 and max(priorities) - min(priorities) > diff_max:
            rescales.append(diff_max)
        real(priorities, diff_max)
    monkeypatch.setattr(st, "_rescale", counting)
    spec = st.StakingSpec(100 + sorted(SCENARIOS).index(scenario), "p", 0, n,
                          total_power=total, changes_per_height=changes,
                          join_every=every, change_span=0.5)
    keys = st.Keys(spec.seed, n)
    genesis = list(enumerate(st.zipf_powers(spec)))
    ref = st.StakeSet.new([keys.member(k, p) for k, p in genesis])
    prog = ValidatorSet([Validator(ed.PubKeyEd25519(keys.pubs[k]), p)
                         for k, p in genesis])
    assert _rows(prog) == _ref_rows(ref)
    table = dict(genesis)
    rng = random.Random(spec.seed)
    joiner = n
    for step in range(1, 51):
        out = st._block_changes(spec, keys, table, -1, step, joiner)
        if scenario == "forced_rescale" and step % 7 == 0:
            heavy = max(table, key=lambda k: table[k])
            if heavy not in {k for k, _p in out}:
                out.append((heavy, 1))
        for k, p in out:
            keys.ensure(k)
            if k not in table:
                joiner += 1
            if p == 0:
                del table[k]
            else:
                table[k] = p
        changes_ = [keys.member(k, p) for k, p in out]
        rng.shuffle(changes_)
        ref.update_with_change_set(changes_)
        kinds = prog.update_with_change_set([
            Validator(ed.PubKeyEd25519(keys.pubs[m.key]), m.power)
            for m in changes_])
        assert _rows(prog) == _ref_rows(ref), step
        assert sum(kinds.values()) == len(changes_)
        ref.increment()
        prog.increment_proposer_priority(1)
        assert _rows(prog) == _ref_rows(ref), step
        assert prog.get_proposer().address \
            == ref.members[ref.proposer].address, step
    if scenario == "forced_rescale":
        assert rescales, "no change set passed the priority window"


def test_a_refused_change_set_is_refused_by_both():
    spec = st.StakingSpec(5, "p", 0, 6, total_power=600)
    keys = st.Keys(5, 6)
    genesis = list(enumerate(st.zipf_powers(spec)))
    ref = st.StakeSet.new([keys.member(k, p) for k, p in genesis])
    prog = _program_set(keys, ref.members)
    keys.ensure(6)
    for bad in ([keys.member(6, 0)],                         # not a member
                [keys.member(0, 3), keys.member(0, 4)],      # twice
                [keys.member(k, 0) for k in range(6)]):      # empties it
        with pytest.raises(ValueError):
            ref.update_with_change_set(bad)
        with pytest.raises(ValueError):
            prog.update_with_change_set([
                Validator(ed.PubKeyEd25519(keys.pubs[m.key]), m.power)
                for m in bad])


@pytest.mark.parametrize("n", [4, 16, 175])
def test_rotate_with_the_rescale_is_light_rotate_at_equal_power(n):
    """At equal powers the window is never passed: the same priorities and
    proposers, height after height, as the rotation light175.sequential's
    and valset10k.live-rounds' data were made with."""
    vals = rc.make_valset(9, n)
    a, b = [0] * n, [0] * n
    for _ in range(3 * n + 7):
        assert rl.rotate(vals, a) == st.rotate(vals, b)
        assert a == b


def test_rotate_rescales_where_light_rotate_did_not():
    """A spread past twice the total, as a set left by a heavy validator
    has: Go divides it down before the turn."""
    vals = rc.make_valset(9, 3, power=5)
    priorities = [100, -40, -60]
    lead = st.rotate(vals, priorities)
    # ratio ceil(160 / 30) = 6: 16, -6, -10; average 0; +5 each; 21 leads
    assert (lead, priorities) == (0, [21 - 15, -1, -5])


def test_the_reference_imports_nothing_of_the_program():
    src = open(st.__file__).read()
    assert "tmtpu" not in src.replace("``tmtpu``", "")


# -- the fused device tally at real powers --------------------------------------------

def test_the_fused_tally_sums_powers_up_to_max_total_voting_power(
        monkeypatch):
    """40 lanes pad to the 64-lane shape; lane 0, the heaviest, is what the
    24 pad lanes replicate. Powers fill every limb (one lane at 2^59, past
    2^52) and sum to exactly MaxTotalVotingPower; three tampered lanes and
    one of the wrong length count zero."""
    monkeypatch.setenv("TMTPU_MESH_DEVICES", "1")
    n = 40
    powers = [1 << 59, (1 << 58) + 12345, 1 << 53, (1 << 52) + 1] + \
        [8191, 8192, 1, 0] + [1000 * i + 7 for i in range(8, n)]
    powers[-1] += MAX_TOTAL_VOTING_POWER - sum(powers)
    assert sum(powers) == MAX_TOTAL_VOTING_POWER and powers[-1] > 0
    pks, msgs, sigs = [], [], []
    for i in range(n):
        priv = ed.gen_priv_key_from_secret(b"tally-%d" % i)
        msg = b"tally msg %d" % i
        pks.append(priv.pub_key().bytes())
        msgs.append(msg)
        sigs.append(priv.sign(msg))
    bad = {3, 17, 29}
    for i in bad:
        sigs[i] = bytes([sigs[i][0] ^ 0x40]) + sigs[i][1:]
    sigs[11] = sigs[11][:63]
    lanes0 = _series("crypto_tally_power_lanes")
    mask, tallied = dispatch.device_verify("ed25519", pks, msgs, sigs,
                                           powers)
    valid = [i for i in range(n) if i not in bad and i != 11]
    assert list(np.flatnonzero(mask)) == valid
    assert tallied == sum(powers[i] for i in valid)
    # the counter: every lane once, by the limbs its power fills; the
    # device, not the limbs, refuses a tampered lane, and the host prep
    # zeroes the power of the one it refused itself
    more = sum(1 for i in range(n) if i != 11 and powers[i] >= 1 << 13)
    moved = {k: v - lanes0.get(k, 0)
             for k, v in _series("crypto_tally_power_lanes").items()}
    assert moved == {"limbs=one": n - more, "limbs=more": more}


# -- the app's validator txs ------------------------------------------------------

def test_the_apps_validator_updates_are_the_references():
    """persistent_kvstore's `val:` txs: each an update EndBlock returns, the
    table answered through `/val`; a removal of a key the table lacks is
    refused and returns no update (an update for it would fail
    UpdateWithChangeSet and halt the chain)."""
    from tmtpu.abci.example.kvstore import KVStoreApplication
    from tmtpu.types import pb

    keys = st.Keys(3, 4)
    keys.ensure(5)
    genesis = [(keys.pubs[k], 10 + k) for k in range(4)]
    app = KVStoreApplication()
    app.init_chain(abci.RequestInitChain(validators=[
        abci.ValidatorUpdate(pub_key=pb.PublicKey(ed25519=pub), power=w)
        for pub, w in genesis]))
    ref = st.App(genesis)
    txs = [st.val_tx(keys.pubs[0], 0), st.val_tx(keys.pubs[4], 7),
           st.val_tx(keys.pubs[5], 0), st.val_tx(keys.pubs[1], 99), b"k=v"]
    app.begin_block(abci.RequestBeginBlock())
    codes = [app.deliver_tx(abci.RequestDeliverTx(tx=tx)).code for tx in txs]
    got = [(bytes(vu.pub_key.ed25519), vu.power) for vu in app.end_block(
        abci.RequestEndBlock(height=1)).validator_updates]
    assert got == ref.deliver_block(txs) == [
        (keys.pubs[0], 0), (keys.pubs[4], 7), (keys.pubs[1], 99)]
    assert codes == [0, 0, 1, 0, 0]
    for pub in keys.pubs:
        want = st.validator_update(pub, ref.validators[pub]) \
            if pub in ref.validators else b""
        assert app.query(abci.RequestQuery(
            path="/val", data=app_key(pub))).value == want
    assert app.state == ref.state == {b"k": b"v"}

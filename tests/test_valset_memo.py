"""A ValidatorSet keeps its Merkle hash and its protobuf encoding while the
content they cover stands (types/validator.py hash, encode). These tests
hold the kept bytes against the definitions computed with no memo — the
SimpleValidator Merkle root and ``to_proto().encode()`` — after every way
a set can change, and at the state store's bytes (validate_block's header
check with a warm memo is in tests/test_validation.py)."""

import pytest

from tmtpu.abci.example.kvstore import KVStoreApplication, make_validator_tx
from tmtpu.crypto.merkle import hash_from_byte_slices
from tmtpu.libs import metrics
from tmtpu.libs.db import MemDB
from tmtpu.proxy import AppConns, LocalClientCreator
from tmtpu.state import store as state_store_mod
from tmtpu.state.execution import BlockExecutor
from tmtpu.state.state import median_time, state_from_genesis
from tmtpu.state.store import StateStore
from tmtpu.types import pb
from tmtpu.types.block import BLOCK_ID_FLAG_COMMIT, Block, BlockID, Commit, \
    CommitSig
from tmtpu.types.genesis import GenesisDoc, GenesisValidator
from tmtpu.types.part_set import PartSet
from tmtpu.types.priv_validator import MockPV
from tmtpu.types.validator import Validator, ValidatorSet
from tmtpu.types.vote import PRECOMMIT, Vote

CHAIN_ID = "memo-chain"


def _plain_hash(vs):
    return hash_from_byte_slices([v.bytes() for v in vs.validators])


def _plain_encoding(vs):
    return vs.to_proto().encode()


def _fresh(vs):
    """The same validators in a set that has computed nothing yet."""
    fresh = ValidatorSet.restore(vs.validators)
    fresh.proposer = vs.proposer.copy() if vs.proposer else None
    fresh._total_voting_power = vs._total_voting_power
    assert not fresh._memo
    return fresh


def _warm(n=7):
    vs = ValidatorSet([Validator(MockPV().get_pub_key(), 10 + i)
                       for i in range(n)])
    assert vs.hash() == _plain_hash(vs)
    assert vs.encode() == _plain_encoding(vs)
    return vs


def _hits(what):
    return metrics.types_valset_memo_hits.summary_series().get(
        f"what={what}", 0)


def _misses(what):
    return metrics.types_valset_memo_misses.summary_series().get(
        f"what={what}", 0)


# -- every way a set can change ------------------------------------------------

def _add(vs):
    vs.update_with_change_set([Validator(MockPV().get_pub_key(), 5)])


def _remove(vs):
    vs.update_with_change_set([Validator(vs.validators[2].pub_key, 0)])


def _change_power(vs):
    vs.update_with_change_set([Validator(vs.validators[3].pub_key, 99)])


def _write_power(vs):
    vs.validators[1].voting_power += 1


def _write_priority(vs):
    vs.validators[1].proposer_priority += 1


def _write_key(vs):
    vs.validators[4].pub_key = MockPV().get_pub_key()


def _write_address(vs):
    vs.validators[4].address = b"\x07" * 20


def _append(vs):
    vs.validators.append(Validator(MockPV().get_pub_key(), 3))


def _replace_element(vs):
    vs.validators[0] = Validator(MockPV().get_pub_key(), 4)


def _reorder(vs):
    vs.validators.reverse()


def _replace_proposer(vs):
    vs.proposer = vs.validators[-1].copy()


def _drop_proposer(vs):
    vs.proposer = None


def _write_total(vs):
    vs._total_voting_power += 1


MUTATIONS = {
    # name: (mutation, the hash moves, the encoding moves)
    "increment_proposer_priority":
        (lambda vs: vs.increment_proposer_priority(1), False, True),
    "increment_proposer_priority_3":
        (lambda vs: vs.increment_proposer_priority(3), False, True),
    "update_add": (_add, True, True),
    "update_remove": (_remove, True, True),
    "update_change_power": (_change_power, True, True),
    "rescale_priorities": (lambda vs: vs.rescale_priorities(3), False, True),
    "shift_by_avg":
        (lambda vs: (_write_priority(vs),
                     vs._shift_by_avg_proposer_priority()), False, True),
    "write_voting_power": (_write_power, True, True),
    "write_proposer_priority": (_write_priority, False, True),
    "write_pub_key": (_write_key, True, True),
    "write_address": (_write_address, False, True),
    "append": (_append, True, True),
    "replace_element": (_replace_element, True, True),
    "reorder": (_reorder, True, True),
    "replace_proposer": (_replace_proposer, False, True),
    "drop_proposer": (_drop_proposer, False, True),
    "write_total": (_write_total, False, True),
    "nothing": (lambda vs: None, False, False),
}


@pytest.mark.parametrize("name", list(MUTATIONS))
def test_answers_follow_the_content(name):
    mutate, hash_moves, encoding_moves = MUTATIONS[name]
    vs = _warm()
    before = (vs.hash(), vs.encode())
    hits0 = (_hits("hash"), _hits("encode"))
    mutate(vs)
    fresh = _fresh(vs)
    assert vs.hash() == fresh.hash() == _plain_hash(vs)
    assert vs.encode() == fresh.encode() == _plain_encoding(vs)
    # the case does what its row says, and a kept answer was a hit
    assert (vs.hash() != before[0]) == hash_moves
    assert (vs.encode() != before[1]) == encoding_moves
    assert _hits("hash") - hits0[0] == (1 if hash_moves else 2)
    assert _hits("encode") - hits0[1] == (1 if encoding_moves else 2)
    # and what a set built from the bytes gives
    back = ValidatorSet.from_proto(pb.ValidatorSet.decode(vs.encode()))
    assert back.hash() == vs.hash()
    assert back.validators == vs.validators and back.proposer == vs.proposer
    # from_proto sums the total anew; a direct write leaves the set's stale
    if back.total_voting_power() == vs.total_voting_power():
        assert back.encode() == vs.encode()


@pytest.mark.parametrize("name", [n for n in MUTATIONS if n != "nothing"])
def test_mutated_copy_leaves_the_original_alone(name):
    mutate, hash_moves, _ = MUTATIONS[name]
    vs = _warm()
    want = (_plain_hash(vs), _plain_encoding(vs))
    hits0, misses0 = _hits("hash"), _misses("hash")
    c = vs.copy()
    assert (c.hash(), c.encode()) == want    # carried, not computed
    assert (_hits("hash") - hits0, _misses("hash") - misses0) == (1, 0)
    mutate(c)
    assert c.hash() == _plain_hash(c) and c.encode() == _plain_encoding(c)
    assert (vs.hash(), vs.encode()) == want
    assert (c.hash() != vs.hash()) == hash_moves
    assert c.encode() != vs.encode()


def test_counters_move_once_a_call():
    vs = ValidatorSet([Validator(MockPV().get_pub_key(), 1)
                       for _ in range(3)])
    h0, m0 = _hits("hash"), _misses("hash")
    e0, f0 = _hits("encode"), _misses("encode")
    vs.hash(), vs.hash(), vs.hash()
    vs.encode(), vs.encode()
    assert (_hits("hash") - h0, _misses("hash") - m0) == (2, 1)
    assert (_hits("encode") - e0, _misses("encode") - f0) == (1, 1)
    vs.increment_proposer_priority(1)
    vs.hash(), vs.encode()
    assert (_hits("hash") - h0, _misses("hash") - m0) == (3, 1)
    assert (_hits("encode") - e0, _misses("encode") - f0) == (1, 2)


def test_empty_set():
    vs = ValidatorSet()
    assert vs.hash() == vs.hash() == _plain_hash(vs)
    assert vs.encode() == vs.encode() == _plain_encoding(vs) == b""


# -- the two callers -------------------------------------------------------------

class _Chain:
    """A BlockExecutor over the kvstore app and in-memory stores, fed
    blocks that the genesis validators sign."""

    def __init__(self, n_val=5):
        self.pvs = {}
        for _ in range(n_val + 1):
            pv = MockPV()
            self.pvs[pv.get_pub_key().address()] = pv
        gen = GenesisDoc(
            chain_id=CHAIN_ID, genesis_time=1_700_000_000 * 10**9,
            validators=[GenesisValidator(pv.get_pub_key(), 10)
                        for pv in list(self.pvs.values())[:n_val]])
        self.spare = list(self.pvs.values())[n_val]
        self.db = MemDB()
        self.store = StateStore(self.db)
        self.state = state_from_genesis(gen)
        self.store.save(self.state)
        self.conns = AppConns(LocalClientCreator(KVStoreApplication()))
        self.conns.start()
        self.exec = BlockExecutor(self.store, self.conns.consensus)
        self.last_commit = None
        self.saved = []     # (the state, the bytes under stateKey) a block

    def stop(self):
        self.conns.stop()

    def make_block(self, txs=()):
        s = self.state
        height = s.last_block_height + 1 if s.last_block_height \
            else s.initial_height
        t = s.last_block_time if height == s.initial_height \
            else median_time(self.last_commit, s.last_validators)
        header = s.make_block_header(
            height, t, list(txs), self.last_commit, [],
            s.validators.get_proposer().address)
        block = Block(header, list(txs), [], self.last_commit)
        block.fill_header()
        parts = PartSet.from_data(block.encode())
        return block, BlockID(block.hash(), parts.total, parts.hash)

    def sign(self, block, block_id):
        sigs = []
        t = block.header.time + 10**9
        for idx, v in enumerate(self.state.validators.validators):
            vote = Vote(type=PRECOMMIT, height=block.header.height, round=0,
                        block_id=block_id, timestamp=t,
                        validator_address=v.address, validator_index=idx)
            self.pvs[v.address].sign_vote(CHAIN_ID, vote)
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, t,
                                  vote.signature))
        return Commit(block.header.height, 0, block_id, sigs)

    def apply(self, txs=()):
        block, block_id = self.make_block(txs)
        commit = self.sign(block, block_id)
        self.state, _ = self.exec.apply_block(self.state, block_id, block)
        self.last_commit = commit
        self.saved.append((self.state, self.db.get(b"stateKey")))


@pytest.fixture
def chain():
    c = _Chain()
    yield c
    c.stop()


def _memoless_state_bytes(state):
    """What the parent wrote: the state's message with fields 6-8 as
    messages built and encoded now."""
    m = state_store_mod._state_to_pb(state)
    for name in ("next_validators", "validators", "last_validators"):
        vs = getattr(m, name)
        if vs is not None:
            setattr(m, name, _fresh(vs).to_proto())
    return m.encode()


def test_store_bytes_equal_memoless_encodings(chain):
    sets = {1: chain.state.validators, 2: chain.state.next_validators}
    e0, f0 = _hits("encode"), _misses("encode")
    for i in range(8):
        txs = [b"k%d=v" % i]
        if i == 3:      # EndBlock of height 4: a new validator, power 7
            txs.append(make_validator_tx(
                chain.spare.get_pub_key().bytes(), 7))
        if i == 5:      # and a power changed
            first = chain.state.validators.validators[0]
            txs.append(make_validator_tx(first.pub_key.bytes(), 4))
        chain.apply(txs)
        sets[chain.state.last_block_height + 2] = chain.state.next_validators
    # a block: the new next_validators computed, the other three kept
    assert (_hits("encode") - e0, _misses("encode") - f0) == (24, 8)
    sizes = set()
    for state, raw in chain.saved:
        assert raw == _memoless_state_bytes(state)
        sizes.add((state.validators.size(), state.next_validators.size()))
        loaded = state_store_mod._state_from_pb(
            state_store_mod._StatePB.decode(raw))
        assert loaded.validators.hash() == state.validators.hash()
        assert loaded.next_validators.validators == \
            state.next_validators.validators
    assert sizes == {(5, 5), (5, 6), (6, 6)}       # the update took hold
    assert chain.state.last_height_validators_changed == 8
    for h in range(1, 11):
        raw = chain.db.get(b"validatorsKey:%d" % h)
        assert raw == _plain_encoding(_fresh(sets[h])), h
        assert chain.store.load_validators(h).validators == \
            sets[h].validators
    assert chain.db.get(b"validatorsKey:11") is None


def test_bootstrap_bytes_equal_memoless_encodings(chain):
    for i in range(3):
        chain.apply([b"b%d=v" % i])
    db = MemDB()
    StateStore(db).bootstrap(chain.state)
    assert db.get(b"stateKey") == _memoless_state_bytes(chain.state)
    for h, vs in ((3, chain.state.last_validators),
                  (4, chain.state.validators),
                  (5, chain.state.next_validators)):
        assert db.get(b"validatorsKey:%d" % h) == _plain_encoding(_fresh(vs))

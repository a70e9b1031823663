"""A scripted consensus network around one LIVE validator — the protocol's
widest rounds on one host, height after height.

The node is what ``node/node.py`` builds for a validator (``build_node``:
``ConsensusState`` + ``ConsensusReactor``, consensus WAL on disk, the block
and state stores, the kvstore app behind ``proxy.AppConns``, mempool and
evidence pool); every other validator of the chain is scripted. One
in-process peer, the ``Relay``, hands ``ConsensusReactor.receive`` the wire
bytes a gossiping peer would: for each height, once the node has entered
it, the proposer's signed ``Proposal`` and the block's parts on the data
channel, then every co-signer's prevote and precommit on the vote channel,
back to back on a thread of its own (``receive`` blocks on the bounded peer
queue while the consensus thread drains it). What is sent comes from a
*script*: any object with ``proposal(height) -> (proposal bytes, [part
bytes]) | None`` (None: the node itself proposes this height) and
``flood(height, block_id) -> ([prevote bytes], [precommit bytes])``. The
network calls no verify entry and no step of the state machine.

At 9,999 co-signers that is ``MaxVotesCount`` validators
(types/vote_set.go:18) and ≈20k votes a height through the receive loop's
batch-drain window. The benchmark's ``drivers/live_rounds.py`` plays a
chain its plain reference fabricated; ``run`` below is the one-height
caller shared by ``chip_smoke.py``, ``tools/tpu_live_round.py`` and
``tests/test_tpu_integration.py``: the live validator is given the power to
propose height 1 itself and ``MockPV`` co-signers answer its proposal.

The verify engine is chosen the way a node chooses it — through
``crypto.batch`` configuration, with the production bucket policy and
deadlines — never by patching the module: ``set_default_backend``,
``configure(CryptoConfig())`` and, for the in-process device backend,
``warm_validator_set`` before consensus starts (what ``Node.on_start``
does).
"""

from __future__ import annotations

import hashlib
import os
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

from tmtpu.config.config import ConsensusConfig, CryptoConfig
from tmtpu.crypto import batch as crypto_batch

CHAIN_ID = "flood-round-chain"


def co_signer(seed: int, i: int, mixed: bool):
    """Co-signer ``i``'s ``MockPV``, deterministic in ``seed``. With
    ``mixed`` the curves go round-robin ed25519 / sr25519 / secp256k1."""
    from tmtpu.crypto import ed25519 as ed
    from tmtpu.crypto import secp256k1 as k1
    from tmtpu.crypto import sr25519 as sr
    from tmtpu.types.priv_validator import MockPV

    secret = b"flood-round-%d-%d" % (seed, i)
    if mixed and i % 3 == 1:
        return MockPV(sr.gen_priv_key_from_secret(secret))
    if mixed and i % 3 == 2:
        d = int.from_bytes(hashlib.sha256(secret).digest(), "big")
        return MockPV(k1.PrivKeySecp256k1(
            (d % (k1.N - 1) + 1).to_bytes(32, "big")))
    return MockPV(ed.gen_priv_key_from_secret(secret))


def dispatch_totals() -> Dict[str, float]:
    """Cumulative dispatches, lanes and seconds inside the dispatch
    calls (prep through readback) the batch metric set has seen, all
    curves and backends; callers difference two readings."""
    from tmtpu.libs import metrics as _m

    series = _m.crypto_batch_size.summary_series().values()
    return {"dispatches": sum(s["count"] for s in series),
            "lanes": sum(s["sum"] for s in series),
            "seconds": sum(
                s["sum"] for s in
                _m.crypto_verify_latency.summary_series().values())}


# -- the node ------------------------------------------------------------------


def build_node(home: str, genesis, priv_validator, *,
               consensus_config: Optional[ConsensusConfig] = None,
               verify_backend: Optional[str] = None) -> Dict:
    """What node/node.py builds for a validator in consensus, from the
    same parts and in its order, under ``home``: stores on the shipped
    ``db_backend`` (SQLite files under ``<home>/data``), the kvstore app
    behind ``proxy.AppConns``, the handshake, mempool, evidence pool,
    ``BlockExecutor``, ``ConsensusState`` with its WAL at
    ``<home>/data/cs.wal/wal`` and ``ConsensusReactor`` (nothing to sync:
    ``wait_sync`` off). -> the parts by name; nothing is started."""
    from tmtpu.abci.example.kvstore import KVStoreApplication
    from tmtpu.consensus.reactor import ConsensusReactor
    from tmtpu.consensus.replay import Handshaker
    from tmtpu.consensus.state import ConsensusState
    from tmtpu.evidence.pool import EvidencePool
    from tmtpu.libs.db import SQLiteDB
    from tmtpu.mempool.clist_mempool import CListMempool
    from tmtpu.proxy import AppConns, default_client_creator
    from tmtpu.state.execution import BlockExecutor
    from tmtpu.state.state import state_from_genesis
    from tmtpu.state.store import StateStore
    from tmtpu.store.block_store import BlockStore
    from tmtpu.types.event_bus import EventBus

    config = consensus_config or ConsensusConfig()

    def db(name):
        os.makedirs(os.path.join(home, "data"), exist_ok=True)
        return SQLiteDB(os.path.join(home, "data", name + ".sqlite"))

    block_store = BlockStore(db("blockstore"))
    state_store = StateStore(db("state"))
    state = state_store.load()
    if state is None:
        state = state_from_genesis(genesis)
        state_store.save(state)
    proxy_app = AppConns(default_client_creator(KVStoreApplication(db("app"))))
    proxy_app.start()
    event_bus = EventBus()
    hs = Handshaker(state_store, state, block_store, genesis, event_bus)
    hs.handshake(proxy_app)
    mempool = CListMempool(proxy_app.mempool)
    evidence_pool = EvidencePool(db("evidence"), state_store, block_store)
    block_exec = BlockExecutor(state_store, proxy_app.consensus, mempool,
                               evidence_pool, event_bus)
    wal_path = os.path.join(home, config.wal_file)
    os.makedirs(os.path.dirname(wal_path), exist_ok=True)
    consensus = ConsensusState(
        config, hs.state, block_exec, block_store, mempool, evidence_pool,
        event_bus, priv_validator, wal_path, verify_backend=verify_backend)
    reactor = ConsensusReactor(consensus, wait_sync=False)
    return {"consensus": consensus, "reactor": reactor,
            "proxy_app": proxy_app, "event_bus": event_bus,
            "block_store": block_store, "state_store": state_store,
            "evidence_pool": evidence_pool, "mempool": mempool,
            "state": hs.state}


# -- the network ---------------------------------------------------------------


class Stalled(RuntimeError):
    """The node did not get where the script needs it in time."""


class Relay:
    """The node's one peer: as much of ``p2p.Peer`` as the reactor uses,
    and the sending side of a gossiping peer. What the node sends it
    (its own votes, HasVote, NewRoundStep) goes nowhere."""

    node_id = "relay"

    def __init__(self, reactor):
        from tmtpu.consensus.reactor import DATA_CHANNEL, VOTE_CHANNEL

        self.reactor = reactor
        self._data, self._vote = DATA_CHANNEL, VOTE_CHANNEL
        self._kv: Dict = {}
        self.sent_votes = 0

    def get(self, key):
        return self._kv.get(key)

    def set(self, key, value) -> None:
        self._kv[key] = value

    def send(self, channel_id: int, msg: bytes) -> bool:
        return True

    try_send = send

    def proposal(self, proposal: bytes, parts: List[bytes]) -> None:
        self.reactor.receive(self._data, self, proposal)
        for p in parts:
            self.reactor.receive(self._data, self, p)

    def votes(self, wire: List[bytes]) -> None:
        receive, ch = self.reactor.receive, self._vote
        for b in wire:
            receive(ch, self, b)
        self.sent_votes += len(wire)


class _Net:
    """Stands in for ``p2p.Switch`` as far as the reactor's broadcast
    routines use it: what a node tells all its peers reaches the relay."""

    def __init__(self, relay: Relay):
        self.peers = {relay.node_id: relay}

    def broadcast(self, channel_id: int, msg: bytes) -> None:
        for p in list(self.peers.values()):
            p.try_send(channel_id, msg)


class Network:
    """One live node and the script that plays the rest of the chain to
    it, a height at a time. ``play_height(h)`` waits for the node to
    enter ``h``, then sends ``h``'s proposal and parts, every prevote,
    every precommit, in that order, on the calling thread."""

    def __init__(self, node: Dict, script):
        self.node = node
        self.cs = node["consensus"]
        self.reactor = node["reactor"]
        self.script = script
        self.relay = Relay(self.reactor)
        self.reactor.switch = _Net(self.relay)
        self._own: Dict[int, object] = {}       # height -> own Proposal
        self._own_cv = threading.Condition()
        self.cs.on_own_proposal = self._on_own_proposal

    def _on_own_proposal(self, proposal, _parts) -> None:
        with self._own_cv:
            self._own.setdefault(proposal.height, proposal)
            self._own_cv.notify_all()

    def start(self) -> None:
        """As the switch starts its reactors and ``Node.on_start`` the
        state machine."""
        self.reactor.init_peer(self.relay)
        self.reactor.on_start()
        self.cs.start()

    def stop(self) -> None:
        self.cs.stop()
        self.reactor.on_stop()
        self.node["proxy_app"].stop()

    def wait_entered(self, height: int, timeout: float) -> None:
        """Until the node's round state is at ``height`` (it committed
        the block below); ``Stalled`` after ``timeout``."""
        if not self.cs.wait_for_height(height - 1, timeout=timeout):
            raise Stalled(
                f"height {height} not entered in {timeout:.0f}s: stuck at "
                f"{self.cs.rs.height_round_step()}")

    def _own_block_id(self, height: int, timeout: float):
        with self._own_cv:
            if not self._own_cv.wait_for(lambda: height in self._own,
                                         timeout):
                raise Stalled(
                    f"the node proposed nothing at height {height} in "
                    f"{timeout:.0f}s: stuck at "
                    f"{self.cs.rs.height_round_step()}")
            return self._own[height].block_id

    def play_height(self, height: int, timeout: float = 900.0,
                    marks: Optional[Dict] = None) -> None:
        self.wait_entered(height, timeout)
        sent = self.script.proposal(height)
        if sent is None:
            block_id = self._own_block_id(height, timeout)
        else:
            block_id = None
            self.relay.proposal(*sent)
        if marks is not None:
            marks["proposal"] = time.perf_counter()
        prevotes, precommits = self.script.flood(height, block_id)
        if marks is not None:
            marks["inject"] = time.perf_counter()
        self.relay.votes(prevotes)
        self.relay.votes(precommits)

    def play(self, heights: range, timeout: float = 900.0) -> None:
        """``heights`` in order, then the wait for the last to commit."""
        for h in heights:
            self.play_height(h, timeout)
        self.wait_entered(heights[-1] + 1, timeout)


def vote_wire(vote) -> bytes:
    """A vote as the vote channel carries it."""
    from tmtpu.consensus import msgs as cm

    return cm.ConsensusMessagePB(
        vote=cm.VotePB(vote=vote.to_proto())).encode()


class OwnProposalScript:
    """The script of a chain whose live validator proposes: ``MockPV``
    co-signers, who together hold the rest of the voting power, answer
    the node's own proposal with one prevote and one precommit each,
    signed when the proposal is known — signatures a real network makes
    concurrently on 10k machines, so signing is reported apart
    (``sign_s``)."""

    def __init__(self, chain_id: str, co_pvs: List, idx_by_addr: Dict):
        self.chain_id = chain_id
        self.co_pvs = co_pvs
        self.idx_by_addr = idx_by_addr
        self.sign_s = 0.0

    def proposal(self, height: int):
        return None

    def flood(self, height: int, block_id) -> Tuple[List[bytes], List[bytes]]:
        from tmtpu.types.vote import PRECOMMIT, PREVOTE, Vote

        t0 = time.perf_counter()
        out = []
        for vtype in (PREVOTE, PRECOMMIT):
            wire = []
            for pv in self.co_pvs:
                addr = pv.get_pub_key().address()
                v = Vote(type=vtype, height=height, round=0,
                         block_id=block_id, timestamp=time.time_ns(),
                         validator_address=addr,
                         validator_index=self.idx_by_addr[addr])
                pv.sign_vote(self.chain_id, v)
                wire.append(vote_wire(v))
            out.append(wire)
        self.sign_s += time.perf_counter() - t0
        return out[0], out[1]


def run(n_co: int, *, backend: str, seed: int = 0, mixed: bool = False,
        live_power: int = 40, timeout: float = 900.0,
        consensus_config: Optional[ConsensusConfig] = None) -> Dict:
    """Commit height 1 with one live validator and ``n_co`` co-signers:
    one height of the network above, the node its own proposer.

    Returns the measured round (``round_s`` proposal→commit,
    ``inject_to_commit_s`` from the first injected vote, ``sign_s`` for
    producing the flood — pre-signed, so the drain window stays
    full-sized), the batched dispatches the flood rode and the seconds
    spent inside them (from the crypto metric set; the rest of the round is
    the host's), the shapes warmed, and the stored ``commit`` with its
    ``validators`` and ``block_id`` for the caller to re-verify. Raises if
    the height does not commit."""
    from tmtpu.state.state import state_from_genesis
    from tmtpu.types.genesis import GenesisDoc, GenesisValidator

    crypto_batch.set_default_backend(backend)
    crypto_batch.configure(CryptoConfig())

    t0 = time.perf_counter()
    live_pv = co_signer(seed, -1, mixed=False)
    co_pvs = [co_signer(seed, i, mixed) for i in range(n_co)]
    keygen_s = time.perf_counter() - t0
    gen = GenesisDoc(
        chain_id=CHAIN_ID, genesis_time=time.time_ns(),
        validators=[GenesisValidator(live_pv.get_pub_key(), live_power)]
        + [GenesisValidator(pv.get_pub_key(), 1) for pv in co_pvs],
    )
    vals = state_from_genesis(gen).validators
    if not vals.get_proposer().pub_key.equals(live_pv.get_pub_key()):
        raise RuntimeError("the live validator must propose height 1: "
                           "raise live_power")
    idx_by_addr = {v.address: i for i, v in enumerate(vals.validators)}

    warmed: List[tuple] = []
    if backend == "tpu":
        warmed = crypto_batch.warm_validator_set(vals)

    home = tempfile.mkdtemp(prefix="flood-round-")
    marks: Dict[str, float] = {}
    script = OwnProposalScript(CHAIN_ID, co_pvs, idx_by_addr)
    net = Network(build_node(
        home, gen, live_pv, verify_backend=backend,
        consensus_config=consensus_config or ConsensusConfig.test_config()),
        script)
    cs = net.cs
    before = dispatch_totals()
    try:
        net.start()
        try:
            net.play_height(1, timeout, marks)
            net.wait_entered(2, timeout)
        except Stalled as e:
            raise RuntimeError(f"height 1 did not commit: {e}") from e
        done = time.perf_counter()
        after = dispatch_totals()
        commit = cs.block_store.load_seen_commit(1)
        block = cs.block_store.load_block(1)
    finally:
        net.stop()
        shutil.rmtree(home, ignore_errors=True)
    if commit is None or block is None or \
            len(commit.signatures) != n_co + 1:
        raise RuntimeError("height 1 stored no full-width seen commit")
    return {
        "validators": n_co + 1,
        "mixed_curves": mixed,
        "backend": backend,
        "keygen_s": keygen_s,
        "sign_s": script.sign_s,
        "round_s": done - marks["proposal"],
        "inject_to_commit_s": done - marks["inject"],
        "dispatches": int(after["dispatches"] - before["dispatches"]),
        "lanes_dispatched": int(after["lanes"] - before["lanes"]),
        "dispatch_s": after["seconds"] - before["seconds"],
        "precommits_in_commit": sum(
            1 for s in commit.signatures if not s.is_absent()),
        "warmed": warmed,
        "chain_id": CHAIN_ID,
        "vals": vals,
        "commit": commit,
        "block_id": commit.block_id,
        "height": 1,
    }

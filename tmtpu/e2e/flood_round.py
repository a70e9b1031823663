"""One LIVE consensus height with scripted co-signers — the protocol's
widest round on one host.

One running validator (a real ``ConsensusState`` with a kvstore app)
proposes height 1; ``n_co`` ``MockPV`` co-signers, who together hold the
rest of the voting power, answer with one prevote and one precommit
each, injected through ``add_vote_msg`` on a relay thread exactly as a
gossiping peer's receive loop would. At ``n_co = 9999`` that is
``MaxVotesCount`` validators (types/vote_set.go:18) and ≈20k votes
through the receive loop's batch-drain window.

The verify engine is chosen the way a node chooses it — through
``crypto.batch`` configuration, with the production bucket policy and
deadlines — never by patching the module: ``set_default_backend``,
``configure(CryptoConfig())`` and, for the in-process device backend,
``warm_validator_set`` before consensus starts (what ``Node.on_start``
does). Shared by ``chip_smoke.py``, ``tools/tpu_live_round.py`` and
``tests/test_tpu_integration.py``.
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Dict, List, Optional

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


def run(n_co: int, *, backend: str, seed: int = 0, mixed: bool = False,
        live_power: int = 40, timeout: float = 900.0,
        consensus_config: Optional[ConsensusConfig] = None) -> Dict:
    """Commit height 1 with one live validator and ``n_co`` co-signers.

    Returns the measured round (``round_s`` proposal→commit,
    ``inject_to_commit_s`` from the first injected vote, ``sign_s`` for
    producing the flood — signatures a real network makes concurrently
    on 10k machines are pre-signed here, so the drain window stays
    full-sized and signing is reported apart), the batched dispatches
    the flood rode and the seconds spent inside them (from the crypto
    metric set; the rest of the round is the host's), the shapes warmed, and
    the stored ``commit`` with its ``validators`` and ``block_id`` for
    the caller to re-verify. Raises if the height does not commit."""
    from tmtpu.abci.example.kvstore import KVStoreApplication
    from tmtpu.consensus.state import ConsensusState
    from tmtpu.libs.db import MemDB
    from tmtpu.proxy import AppConns, LocalClientCreator
    from tmtpu.state.execution import BlockExecutor
    from tmtpu.state.state import state_from_genesis
    from tmtpu.state.store import StateStore
    from tmtpu.store.block_store import BlockStore
    from tmtpu.types.event_bus import EventBus
    from tmtpu.types.genesis import GenesisDoc, GenesisValidator
    from tmtpu.types.vote import PRECOMMIT, PREVOTE, Vote

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
    genesis_state = state_from_genesis(gen)
    vals = genesis_state.validators
    if not vals.get_proposer().pub_key.equals(live_pv.get_pub_key()):
        raise RuntimeError("the live validator must propose height 1: "
                           "raise live_power")
    idx_by_addr = {v.address: i for i, v in enumerate(vals.validators)}

    warmed: List[tuple] = []
    if backend == "tpu":
        warmed = crypto_batch.warm_validator_set(vals)

    app = KVStoreApplication()
    conns = AppConns(LocalClientCreator(app))
    conns.start()
    state_store = StateStore(MemDB())
    state_store.save(genesis_state)
    bus = EventBus()
    exec_ = BlockExecutor(state_store, conns.consensus, event_bus=bus)
    cs = ConsensusState(
        consensus_config or ConsensusConfig.test_config(), genesis_state,
        exec_, BlockStore(MemDB()), event_bus=bus, priv_validator=live_pv,
        verify_backend=backend,
    )

    marks: Dict[str, float] = {}
    flood_err: List[BaseException] = []

    def flood(proposal):
        # own thread, like a relay peer's recv loop: add_vote_msg blocks
        # on the bounded peer queue while the consensus thread drains it
        try:
            t0 = time.perf_counter()
            votes = []
            for vtype in (PREVOTE, PRECOMMIT):
                for pv in co_pvs:
                    addr = pv.get_pub_key().address()
                    v = Vote(type=vtype, height=proposal.height,
                             round=proposal.round,
                             block_id=proposal.block_id,
                             timestamp=time.time_ns(),
                             validator_address=addr,
                             validator_index=idx_by_addr[addr])
                    pv.sign_vote(CHAIN_ID, v)
                    votes.append(v)
            marks["sign_s"] = time.perf_counter() - t0
            marks["inject"] = time.perf_counter()
            for v in votes:
                cs.add_vote_msg(v, peer_id="relay")
        except BaseException as e:  # noqa: BLE001 — reported by run()
            flood_err.append(e)

    def on_proposal(proposal, _parts):
        if proposal.height != 1 or "proposal" in marks:
            return
        marks["proposal"] = time.perf_counter()
        threading.Thread(target=flood, args=(proposal,), daemon=True,
                         name="vote-relay").start()

    cs.on_own_proposal = on_proposal
    before = dispatch_totals()
    try:
        cs.start()
        committed = cs.wait_for_height(1, timeout=timeout)
        done = time.perf_counter()
    finally:
        cs.stop()
        conns.stop()
    if flood_err:
        raise flood_err[0]
    if not committed:
        raise RuntimeError(
            f"height 1 did not commit in {timeout:.0f}s: stuck at "
            f"{cs.rs.height_round_step()}")
    after = dispatch_totals()
    commit = cs.block_store.load_seen_commit(1)
    block = cs.block_store.load_block(1)
    if commit is None or block is None or \
            len(commit.signatures) != n_co + 1:
        raise RuntimeError("height 1 stored no full-width seen commit")
    return {
        "validators": n_co + 1,
        "mixed_curves": mixed,
        "backend": backend,
        "keygen_s": keygen_s,
        "sign_s": marks.get("sign_s", 0.0),
        "round_s": done - marks["proposal"],
        "inject_to_commit_s": done - marks.get("inject", marks["proposal"]),
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

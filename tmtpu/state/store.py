"""State persistence (reference: state/store.go) — the per-height state,
validator sets, consensus params, and ABCI responses, on a libs.db KV."""

from __future__ import annotations

import json
from typing import List, Optional

from tmtpu.abci import types as abci
from tmtpu.libs.db import DB
from tmtpu.state.state import State
from tmtpu.types.block import BlockID
from tmtpu.types.params import ConsensusParams
from tmtpu.types.validator import ValidatorSet
from tmtpu.types import pb


def _k_state() -> bytes:
    return b"stateKey"


def _k_validators(height: int) -> bytes:
    return b"validatorsKey:%d" % height


def _k_params(height: int) -> bytes:
    return b"consensusParamsKey:%d" % height


def _k_abci_responses(height: int) -> bytes:
    return b"abciResponsesKey:%d" % height


class ABCIResponses:
    """state/store.go ABCIResponses — what the app said at a height."""

    def __init__(self, deliver_txs: Optional[List] = None,
                 begin_block=None, end_block=None):
        self.deliver_txs = deliver_txs or []
        self.begin_block = begin_block or abci.ResponseBeginBlock()
        self.end_block = end_block or abci.ResponseEndBlock()

    def encode(self) -> bytes:
        return _ABCIResponsesPB(
            deliver_txs=self.deliver_txs,
            end_block=self.end_block,
            begin_block=self.begin_block,
        ).encode()

    @classmethod
    def decode(cls, buf: bytes) -> "ABCIResponses":
        m = _ABCIResponsesPB.decode(buf)
        return cls(m.deliver_txs, m.begin_block, m.end_block)

    def results_hash(self) -> bytes:
        return results_hash(self.deliver_txs)


class _ABCIResponsesPB(pb.ProtoMessage):
    FIELDS = [
        (1, "deliver_txs", ("rep", ("msg!", abci.ResponseDeliverTx))),
        (2, "end_block", ("msg", abci.ResponseEndBlock)),
        (3, "begin_block", ("msg", abci.ResponseBeginBlock)),
    ]


def deterministic_deliver_tx(r: abci.ResponseDeliverTx) -> abci.ResponseDeliverTx:
    """types/results.go deterministicResponseDeliverTx — strip the
    non-deterministic fields before hashing."""
    return abci.ResponseDeliverTx(
        code=r.code, data=r.data, gas_wanted=r.gas_wanted, gas_used=r.gas_used,
    )


def results_hash(deliver_txs: List) -> bytes:
    """types/results.go ABCIResponsesResultsHash — merkle root over the
    deterministic encodings."""
    from tmtpu.crypto.merkle import hash_from_byte_slices

    return hash_from_byte_slices(
        [deterministic_deliver_tx(r).encode() for r in deliver_txs]
    )


class _StateVersionPB(pb.ProtoMessage):
    """proto/tendermint/state/types.proto Version."""

    FIELDS = [(1, "consensus", ("msg!", pb.Consensus)),
              (2, "software", "string")]


class _StatePB(pb.ProtoMessage):
    """proto/tendermint/state/types.proto State (subset, same field ids)."""

    FIELDS = [
        (1, "version", ("msg!", _StateVersionPB)),
        (2, "chain_id", "string"),
        (14, "initial_height", "int64"),
        (3, "last_block_height", "int64"),
        (4, "last_block_id", ("msg!", pb.BlockID)),
        (5, "last_block_time", ("msg!", pb.Timestamp)),
        (6, "next_validators", ("msg", pb.ValidatorSet)),
        (7, "validators", ("msg", pb.ValidatorSet)),
        (8, "last_validators", ("msg", pb.ValidatorSet)),
        (9, "last_height_validators_changed", "int64"),
        (10, "consensus_params", ("msg!", pb.ConsensusParams)),
        (11, "last_height_consensus_params_changed", "int64"),
        (12, "last_results_hash", "bytes"),
        (13, "app_hash", "bytes"),
    ]


def _state_to_pb(s: State) -> _StatePB:
    """The message to write, not one to read back: fields 6-8 hold the
    sets themselves, of which the encoder asks only ``encode()``, so a set
    whose content this process has encoded before hands over kept bytes
    (``validators`` and ``last_validators`` are copies of the last block's
    ``next_validators`` and ``validators``)."""
    from tmtpu.version import BlockProtocol, TMCoreSemVer

    return _StatePB(
        version=_StateVersionPB(
            consensus=pb.Consensus(block=BlockProtocol, app=s.app_version),
            software=TMCoreSemVer,
        ),
        chain_id=s.chain_id,
        initial_height=s.initial_height,
        last_block_height=s.last_block_height,
        last_block_id=s.last_block_id.to_proto(),
        last_block_time=pb.Timestamp.from_unix_nanos(s.last_block_time),
        next_validators=s.next_validators,
        validators=s.validators,
        last_validators=s.last_validators
        if s.last_validators and s.last_validators.size() else None,
        last_height_validators_changed=s.last_height_validators_changed,
        consensus_params=s.consensus_params.to_proto(),
        last_height_consensus_params_changed=
        s.last_height_consensus_params_changed,
        last_results_hash=s.last_results_hash,
        app_hash=s.app_hash,
    )


def _state_from_pb(m: _StatePB) -> State:
    return State(
        chain_id=m.chain_id,
        initial_height=m.initial_height,
        last_block_height=m.last_block_height,
        last_block_id=BlockID.from_proto(m.last_block_id),
        last_block_time=m.last_block_time.to_unix_nanos()
        if m.last_block_time else 0,
        next_validators=ValidatorSet.from_proto(m.next_validators)
        if m.next_validators else None,
        validators=ValidatorSet.from_proto(m.validators)
        if m.validators else None,
        last_validators=ValidatorSet.from_proto(m.last_validators)
        if m.last_validators else ValidatorSet(),
        last_height_validators_changed=m.last_height_validators_changed,
        consensus_params=ConsensusParams.from_proto(m.consensus_params),
        last_height_consensus_params_changed=
        m.last_height_consensus_params_changed,
        last_results_hash=bytes(m.last_results_hash),
        app_hash=bytes(m.app_hash),
        app_version=(m.version.consensus.app
                     if m.version and m.version.consensus else 0),
    )


class StateStore:
    def __init__(self, db: DB, discard_abci_responses: bool = False):
        self.db = db
        self.discard_abci_responses = discard_abci_responses

    def load(self) -> Optional[State]:
        raw = self.db.get(_k_state())
        if raw is None:
            return None
        return _state_from_pb(_StatePB.decode(raw))

    def save(self, state: State) -> None:
        """Persist state + the lookup tables for its next height
        (store.go saveState: validators at H+1, params history)."""
        next_height = state.last_block_height + 1
        if next_height == 1:
            next_height = state.initial_height
            self._save_validators(next_height, state.validators)
        self._save_validators(next_height + 1, state.next_validators)
        self._save_params(next_height, state.consensus_params)
        self.db.set(_k_state(), _state_to_pb(state).encode())

    def bootstrap(self, state: State) -> None:
        """store.go Bootstrap — used by statesync to plant a trusted state."""
        height = state.last_block_height + 1
        if height == 1:
            height = state.initial_height
        if state.last_validators and state.last_validators.size():
            self._save_validators(height - 1, state.last_validators)
        self._save_validators(height, state.validators)
        self._save_validators(height + 1, state.next_validators)
        self._save_params(height, state.consensus_params)
        self.db.set(_k_state(), _state_to_pb(state).encode())

    def _save_validators(self, height: int, vals: ValidatorSet) -> None:
        self.db.set(_k_validators(height), vals.encode())

    def _save_params(self, height: int, params: ConsensusParams) -> None:
        self.db.set(_k_params(height), params.to_proto().encode())

    def load_validators(self, height: int) -> Optional[ValidatorSet]:
        raw = self.db.get(_k_validators(height))
        if raw is None:
            return None
        return ValidatorSet.from_proto(pb.ValidatorSet.decode(raw))

    def load_consensus_params(self, height: int) -> Optional[ConsensusParams]:
        raw = self.db.get(_k_params(height))
        if raw is None:
            return None
        return ConsensusParams.from_proto(pb.ConsensusParams.decode(raw))

    def save_abci_responses(self, height: int, res: ABCIResponses) -> None:
        if self.discard_abci_responses:
            return
        self.db.set(_k_abci_responses(height), res.encode())

    def load_abci_responses(self, height: int) -> Optional[ABCIResponses]:
        raw = self.db.get(_k_abci_responses(height))
        if raw is None:
            return None
        return ABCIResponses.decode(raw)

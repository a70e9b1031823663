"""Canonical chain state (reference: state/state.go).

State is the deterministic function of the applied blocks: validator sets
for H-1/H/H+1, consensus params, last results, AppHash. Immutable-ish —
``copy()`` before mutation, like the reference's value semantics.
"""

from __future__ import annotations

from typing import Optional

from tmtpu.types.block import BlockID, Header
from tmtpu.types.genesis import GenesisDoc
from tmtpu.types.params import ConsensusParams
from tmtpu.types.validator import ValidatorSet
from tmtpu.version import BlockProtocol

# state.go InitStateVersion
STATE_VERSION = {"block": BlockProtocol, "app": 0}


class State:
    FIELDS = (
        "chain_id", "initial_height", "last_block_height", "last_block_id",
        "last_block_time", "next_validators", "validators", "last_validators",
        "last_height_validators_changed", "consensus_params",
        "last_height_consensus_params_changed", "last_results_hash",
        "app_hash", "app_version",
    )

    def __init__(self, **kw):
        self.chain_id: str = kw.pop("chain_id", "")
        self.initial_height: int = kw.pop("initial_height", 1)
        self.last_block_height: int = kw.pop("last_block_height", 0)
        self.last_block_id: BlockID = kw.pop("last_block_id", BlockID())
        self.last_block_time: int = kw.pop("last_block_time", 0)
        self.next_validators: Optional[ValidatorSet] = kw.pop(
            "next_validators", None)
        self.validators: Optional[ValidatorSet] = kw.pop("validators", None)
        self.last_validators: Optional[ValidatorSet] = kw.pop(
            "last_validators", None)
        self.last_height_validators_changed: int = kw.pop(
            "last_height_validators_changed", 0)
        self.consensus_params: ConsensusParams = kw.pop(
            "consensus_params", ConsensusParams())
        self.last_height_consensus_params_changed: int = kw.pop(
            "last_height_consensus_params_changed", 0)
        self.last_results_hash: bytes = kw.pop("last_results_hash", b"")
        self.app_hash: bytes = kw.pop("app_hash", b"")
        self.app_version: int = kw.pop("app_version", 0)
        if kw:
            raise TypeError(f"unknown State fields {list(kw)}")

    def copy(self) -> "State":
        s = State()
        s.chain_id = self.chain_id
        s.initial_height = self.initial_height
        s.last_block_height = self.last_block_height
        s.last_block_id = self.last_block_id
        s.last_block_time = self.last_block_time
        s.next_validators = self.next_validators.copy() \
            if self.next_validators else None
        s.validators = self.validators.copy() if self.validators else None
        s.last_validators = self.last_validators.copy() \
            if self.last_validators else None
        s.last_height_validators_changed = self.last_height_validators_changed
        s.consensus_params = self.consensus_params
        s.last_height_consensus_params_changed = \
            self.last_height_consensus_params_changed
        s.last_results_hash = self.last_results_hash
        s.app_hash = self.app_hash
        s.app_version = self.app_version
        return s

    def is_empty(self) -> bool:
        return self.validators is None

    def make_block_header(self, height: int, time_ns: int, txs,
                          last_commit, evidence, proposer_address: bytes
                          ) -> Header:
        """Header fields derivable from state (state.go MakeBlock)."""
        from tmtpu.types.evidence import evidence_list_hash
        from tmtpu.types.tx import txs_hash

        return Header(
            version_block=STATE_VERSION["block"],
            version_app=self.app_version,
            chain_id=self.chain_id,
            height=height,
            time=time_ns,
            last_block_id=self.last_block_id,
            last_commit_hash=last_commit.hash() if last_commit else b"",
            data_hash=txs_hash(txs),
            validators_hash=self.validators.hash(),
            next_validators_hash=self.next_validators.hash(),
            consensus_hash=self.consensus_params.hash(),
            app_hash=self.app_hash,
            last_results_hash=self.last_results_hash,
            evidence_hash=evidence_list_hash(evidence),
            proposer_address=proposer_address,
        )


def median_time(commit, validators) -> int:
    """state.go:268 MedianTime — weighted median (by voting power) of the
    non-absent commit sig timestamps; bounded by honest validators' clocks
    since >1/3 of the weight is honest. Returns unix nanos."""
    weighted = []
    total = 0
    # one pass over the set, not one a signature: at 10,000 validators
    # get_by_address a slot is 50 million address compares a call
    power_of = {v.address: v.voting_power for v in validators.validators}
    for cs in commit.signatures:
        if cs.is_absent():
            continue
        power = power_of.get(cs.validator_address)
        if power is not None:
            total += power
            weighted.append((cs.timestamp, power))
    weighted.sort()
    median = total // 2
    for t, w in weighted:
        if median <= w:
            return t
        median -= w
    return 0


def state_from_genesis(gen: GenesisDoc) -> State:
    """state.go MakeGenesisState."""
    val_set = gen.validator_set()
    next_vals = val_set.copy_increment_proposer_priority(1)
    return State(
        chain_id=gen.chain_id,
        initial_height=gen.initial_height,
        last_block_height=0,
        last_block_id=BlockID(),
        last_block_time=gen.genesis_time,
        next_validators=next_vals,
        validators=val_set,
        last_validators=ValidatorSet(),  # empty at genesis
        last_height_validators_changed=gen.initial_height,
        consensus_params=gen.consensus_params,
        last_height_consensus_params_changed=gen.initial_height,
        last_results_hash=b"",
        app_hash=gen.app_hash,
        app_version=gen.consensus_params.app_version,
    )

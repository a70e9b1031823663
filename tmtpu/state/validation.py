"""Block validation against state (reference: state/validation.go).

The LastCommit signature check at validation.go:93 — every ApplyBlock
re-verifies all LastCommit signatures — goes through the batch-first
verify_commit (one TPU dispatch per block).
"""

from __future__ import annotations

from tmtpu.crypto import batch as crypto_batch
from tmtpu.state.state import State, STATE_VERSION, median_time
from tmtpu.types import commit_verify  # noqa: F401 (binds ValidatorSet methods)
from tmtpu.types.block import Block


class BlockValidationError(Exception):
    pass


def validate_block(state: State, block: Block, verify_backend=None) -> None:
    block.validate_basic()
    h = block.header

    if h.version_block != STATE_VERSION["block"]:
        raise BlockValidationError(
            f"wrong Block.Header.Version.Block: {h.version_block}")
    if h.version_app != state.app_version:
        raise BlockValidationError(
            f"wrong Block.Header.Version.App: {h.version_app}")
    if h.chain_id != state.chain_id:
        raise BlockValidationError(f"wrong chain id {h.chain_id!r}")
    if state.last_block_height == 0:
        if h.height != state.initial_height:
            raise BlockValidationError(
                f"wrong initial block height {h.height}")
    elif h.height != state.last_block_height + 1:
        raise BlockValidationError(f"wrong block height {h.height}")
    if h.last_block_id != state.last_block_id:
        raise BlockValidationError("wrong Block.Header.LastBlockID")
    if h.app_hash != state.app_hash:
        raise BlockValidationError("wrong Block.Header.AppHash")
    if h.consensus_hash != state.consensus_params.hash():
        raise BlockValidationError("wrong Block.Header.ConsensusHash")
    if h.last_results_hash != state.last_results_hash:
        raise BlockValidationError("wrong Block.Header.LastResultsHash")
    if h.validators_hash != state.validators.hash():
        raise BlockValidationError("wrong Block.Header.ValidatorsHash")
    if h.next_validators_hash != state.next_validators.hash():
        raise BlockValidationError("wrong Block.Header.NextValidatorsHash")

    # LastCommit checks
    if state.last_block_height == 0 or \
            h.height == state.initial_height:
        if len(block.last_commit.signatures) != 0 if block.last_commit else False:
            raise BlockValidationError(
                "initial block can't have LastCommit signatures")
    else:
        if block.last_commit is None or \
                len(block.last_commit.signatures) != state.last_validators.size():
            raise BlockValidationError("wrong LastCommit signature count")
        try:
            # pinned to the whole set's shape, which the node compiled at
            # start (warm_validator_set): a LastCommit this process has not
            # verified whole — late precommits dropped after round 0 began,
            # a restart — then meets that shape, however few lanes are left
            n = state.last_validators.size()
            state.last_validators.verify_commit(
                state.chain_id, state.last_block_id,
                h.height - 1, block.last_commit, backend=verify_backend,
                min_lanes=crypto_batch.vote_flush_lanes(n, n),
            )
        except commit_verify.VerificationError as e:
            raise BlockValidationError(str(e)) from e

    if not state.validators.has_address(h.proposer_address):
        raise BlockValidationError(
            f"block proposer is not a validator: "
            f"{h.proposer_address.hex().upper()}"
        )

    # Block time (validation.go:114-143): for the initial block it must be
    # the genesis time; afterwards it must be strictly after LastBlockTime
    # and exactly the weighted median of the LastCommit timestamps.
    if h.height == state.initial_height:
        if h.time != state.last_block_time:
            raise BlockValidationError(
                f"block time {h.time} != genesis time {state.last_block_time}")
    else:
        if h.time <= state.last_block_time:
            raise BlockValidationError(
                f"block time {h.time} not greater than last block time "
                f"{state.last_block_time}")
        mt = median_time(block.last_commit, state.last_validators)
        if h.time != mt:
            raise BlockValidationError(
                f"invalid block time: expected median {mt}, got {h.time}")

    # Evidence size cap (validation.go:146)
    from tmtpu.types.evidence import evidence_to_proto

    ev_size = sum(len(evidence_to_proto(e).encode()) for e in block.evidence)
    if ev_size > state.consensus_params.evidence_max_bytes:
        raise BlockValidationError(
            f"evidence bytes {ev_size} exceed max "
            f"{state.consensus_params.evidence_max_bytes}")

"""Shared halves of the v0 and v2 blocksync reactors: block serving,
peer discipline, consensus handover, and the batched run verification
(reference: blockchain/v0/reactor.go + blockchain/v2/io.go — both
versions speak the identical blockchain channel protocol)."""

from __future__ import annotations

from typing import List, Optional, Tuple

from tmtpu.blocksync.msgs import (
    BlockResponsePB, BlocksyncMessagePB, NoBlockResponsePB,
    StatusRequestPB, StatusResponsePB,
)
from tmtpu.crypto import batch as crypto_batch
from tmtpu.libs import metrics, trace
from tmtpu.types import commit_verify
from tmtpu.types.block import BlockID
from tmtpu.types.part_set import PartSet

BLOCKCHAIN_CHANNEL = 0x40

# The v0 reactor and the sequential light client (light/client.py) size a
# run in lanes, not blocks: as many blocks as fit this many commit-signature
# slots (35 at 175 validators, 3 at 2,000, one above 3,072), so that a small
# and a large validator set both flush one device shape, which the caller
# compiles (``warm_run``) before it asks for a block.
# 6,144 is the bucket the old 32-block run of a 175-validator chain
# padded to (tpu/dispatch.py _pad_to_bucket).
RUN_LANES = 6144


def run_shape(validators) -> Tuple[int, int]:
    """(blocks a run holds, lanes its verify dispatch is pinned to) for a
    validator set: a whole commit always fits, so a set wider than
    RUN_LANES makes one-block runs at its own size's shape."""
    n = max(1, validators.size())
    return max(1, RUN_LANES // n), max(RUN_LANES, n)


def warm_run(validators, verify_backend: Optional[str]) -> None:
    """Compile the shape ``run_shape`` pins this set's runs to (a no-op
    off the device backend, or when the set has no ed25519 key: the pin
    is the ed25519 mask step's)."""
    if not any(v.pub_key.type_value() == crypto_batch.ED25519
               for v in validators.validators):
        return
    from tmtpu.libs import log

    warmed = crypto_batch.warm_pinned(run_shape(validators)[1],
                                      verify_backend)
    if warmed:
        log.default_logger().with_fields(module="blocksync").info(
            "run shape warmed", lanes=warmed[0][1],
            seconds=round(warmed[0][3], 1))


class BlockServingMixin:
    """Serving + handover shared by BlocksyncReactor (v0) and
    BlocksyncReactorV2. Requires: ``self.store``, ``self.switch``,
    ``self.state``, ``self.blocks_synced``, ``self.consensus_reactor``."""

    def _status_msg(self) -> bytes:
        return BlocksyncMessagePB(status_response=StatusResponsePB(
            height=self.store.height(), base=self.store.base())).encode()

    def _respond_to_peer(self, height: int, peer) -> None:
        block = self.store.load_block(height)
        if block is not None:
            m = BlocksyncMessagePB(
                block_response=BlockResponsePB(block=block.to_proto()))
        else:
            m = BlocksyncMessagePB(
                no_block_response=NoBlockResponsePB(height=height))
        peer.try_send(BLOCKCHAIN_CHANNEL, m.encode())

    def broadcast_status_request(self) -> None:
        if self.switch is not None:
            self.switch.broadcast(
                BLOCKCHAIN_CHANNEL,
                BlocksyncMessagePB(status_request=StatusRequestPB()).encode())

    def _stop_peer(self, peer_id: str, reason: str) -> None:
        if self.switch is None:
            return
        peer = self.switch.peers.get(peer_id)
        if peer is not None:
            self.switch.stop_peer_for_error(peer, reason)

    def _switch_to_consensus(self, state_synced: bool) -> None:
        if self.consensus_reactor is not None:
            self.consensus_reactor.switch_to_consensus(
                self.state, skip_wal=self.blocks_synced > 0 or state_synced)


def verify_block_run(state, blocks: List, successors: List,
                     verify_backend: Optional[str], min_lanes: int = 0
                     ) -> Tuple[List, List[Tuple[PartSet, BlockID]]]:
    """Verify block h against block h+1's LastCommit for a contiguous
    run, the WHOLE run's commit signatures in one batched dispatch
    (v0 reactor.go:366 does one VerifyCommitLight per block), padded as
    if it held ``min_lanes`` (``run_shape``; 0: by its own length).

    Returns (per-block error list, per-block (PartSet, BlockID)) — the
    parts/bid pairs are returned so callers reuse them for save/apply
    instead of encoding each block (up to block_max_bytes) a second
    time."""
    entries = []
    parts_bids: List[Tuple[PartSet, BlockID]] = []
    vals = state.validators
    chain_id = state.chain_id
    metrics.blocksync_run_blocks.observe(len(blocks))
    with trace.span("blocksync.verify_run", blocks=len(blocks)):
        for blk, nxt in zip(blocks, successors):
            parts = PartSet.from_data(blk.encode())
            bid = BlockID(blk.hash(), parts.total, parts.hash)
            parts_bids.append((parts, bid))
            entries.append((vals, chain_id, bid, blk.header.height,
                            nxt.last_commit))
        results = commit_verify.verify_commits_light_batch(
            entries, backend=verify_backend, min_lanes=min_lanes)
    return results, parts_bids

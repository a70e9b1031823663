"""Fast-sync (block sync) reactor (reference: blockchain/v0/reactor.go).

Serves blocks to catching-up peers and, when started in fast-sync mode,
drives the BlockPool: request blocks from taller peers, verify each block
with its successor's LastCommit, apply through the BlockExecutor, and hand
over to the consensus reactor once caught up (SwitchToConsensus,
reactor.go:303-330).

TPU-first deviation from the reference: instead of one VerifyCommitLight
per block (reactor.go:366), a contiguous run of fetched blocks is verified
with ONE batched dispatch over all their commits' signatures
(types.commit_verify.verify_commits_light_batch) — fast-sync replay is the
BASELINE "per-block Commit batch verification" config, batched further
across blocks. A run is sized in lanes (common.run_shape: as many blocks
as the validator set's size leaves of RUN_LANES), every run's dispatch is
padded to that one shape, and the pool routine compiles it before it asks
for the first block: no run, first, short or last, meets a shape the
process has not compiled, whatever the validator set's size.

Stricter than the reference, never weaker: block h is applied only if
more than 2/3 of its validators' power signed it in block h+1's LastCommit
and EVERY for-block signature of that commit verifies (VerifyCommitLight
stops at 2/3); validate_block's full verify_commit of LastCommit runs on
every block; block and state are saved before the next block is applied.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from tmtpu.blocksync.common import (
    BLOCKCHAIN_CHANNEL, BlockServingMixin, run_shape, verify_block_run,
    warm_run,
)
from tmtpu.blocksync.msgs import BlockRequestPB, BlocksyncMessagePB
from tmtpu.blocksync.pool import BlockPool
from tmtpu.libs import metrics, trace
from tmtpu.p2p.conn.connection import ChannelDescriptor
from tmtpu.p2p.switch import Peer, Reactor
from tmtpu.types.block import Block


TRY_SYNC_INTERVAL_S = 0.01          # trySyncIntervalMS
STATUS_UPDATE_INTERVAL_S = 10.0     # statusUpdateIntervalSeconds
SWITCH_TO_CONSENSUS_INTERVAL_S = 1.0


class BlocksyncReactor(BlockServingMixin, Reactor):
    def __init__(self, state, block_exec, block_store, fast_sync: bool,
                 consensus_reactor=None, verify_backend: Optional[str] = None):
        super().__init__("BLOCKSYNC")
        if state.last_block_height != block_store.height():
            raise ValueError(
                f"state ({state.last_block_height}) and store "
                f"({block_store.height()}) height mismatch")
        self.initial_state = state
        self.state = state
        self.block_exec = block_exec
        self.store = block_store
        self.fast_sync = fast_sync
        self.consensus_reactor = consensus_reactor
        self.verify_backend = verify_backend
        start = block_store.height() + 1
        if start == 1:
            start = state.initial_height
        self.pool = BlockPool(start, on_peer_error=self._stop_peer)
        self.blocks_synced = 0
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- reactor interface --------------------------------------------------

    def get_channels(self):
        return [ChannelDescriptor(BLOCKCHAIN_CHANNEL, priority=5,
                                  send_queue_capacity=1000)]

    def on_start(self) -> None:
        if self.fast_sync:
            self._thread = threading.Thread(
                target=self._pool_routine, daemon=True, name="blocksync-pool")
            self._thread.start()

    def on_stop(self) -> None:
        self._stopped.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2.0)

    def add_peer(self, peer: Peer) -> None:
        # reactor.go AddPeer: send our status so the peer can request
        peer.send(BLOCKCHAIN_CHANNEL, self._status_msg())

    def remove_peer(self, peer: Peer, reason) -> None:
        self.pool.remove_peer(peer.node_id)

    def receive(self, channel_id: int, peer: Peer, msg_bytes: bytes) -> None:
        # wire decode + the pool's bookkeeping, on the connection's thread
        with trace.span("blocksync.receive", bytes=len(msg_bytes)):
            msg = BlocksyncMessagePB.decode(msg_bytes)
            if msg.block_request is not None:
                self._respond_to_peer(msg.block_request.height, peer)
            elif msg.block_response is not None:
                block = Block.from_proto(msg.block_response.block)
                self.pool.add_block(peer.node_id, block, len(msg_bytes))
            elif msg.status_request is not None:
                peer.try_send(BLOCKCHAIN_CHANNEL, self._status_msg())
            elif msg.status_response is not None:
                self.pool.set_peer_range(peer.node_id,
                                         msg.status_response.base,
                                         msg.status_response.height)
            elif msg.no_block_response is not None:
                pass  # reactor.go just logs it

    # serving + handover (status/respond/stop-peer/switch-to-consensus)
    # come from BlockServingMixin — shared with BlocksyncReactorV2

    # -- the sync loop (reactor.go poolRoutine) -----------------------------

    def _pool_routine(self, state_synced: bool = False) -> None:
        # before the first request: a run's shape compiles for tens of
        # seconds at first sight, and this thread is the one that waits
        warm_run(self.state.validators, self.verify_backend)
        last_status = 0.0
        last_switch_check = 0.0
        while not self._stopped.is_set():
            now = time.monotonic()
            if now - last_status > STATUS_UPDATE_INTERVAL_S:
                last_status = now
                self.broadcast_status_request()
            # until the scheduler has nothing left to hand out: a peer
            # that answered while the last run was applied has room for
            # the next 20, and the pool should hold the next run whole
            while self._send_requests():
                pass
            if now - last_switch_check > SWITCH_TO_CONSENSUS_INTERVAL_S:
                last_switch_check = now
                if self.pool.is_caught_up():
                    self._switch_to_consensus(state_synced)
                    return
            if not self._try_sync_batch():
                self._stopped.wait(TRY_SYNC_INTERVAL_S)

    def _send_requests(self) -> bool:
        """One scheduling pass of the pool; True if a request went out."""
        sent = False
        for peer_id, height in self.pool.make_requests():
            peer = self.switch.peers.get(peer_id) if self.switch else None
            if peer is not None:
                sent |= bool(peer.try_send(
                    BLOCKCHAIN_CHANNEL,
                    BlocksyncMessagePB(
                        block_request=BlockRequestPB(height=height)
                    ).encode()))
        return sent

    def _try_sync_batch(self) -> bool:
        """Verify + apply a contiguous run of fetched blocks. The commits of
        the whole run are batch-verified in one dispatch, padded to the one
        shape ``run_shape`` gives this validator set; the verified prefix
        is applied, the first failure re-requested. Returns True if any
        block was applied."""
        n_blocks, lanes = run_shape(self.state.validators)
        run = self.pool.peek_run(n_blocks + 1)
        if len(run) < 2:
            return False
        # block h is verified by block h+1's LastCommit (reactor.go:366)
        # against ONE valset, the state's: the run stops short of the
        # first block that names another set (rare; a first block that
        # does is refused by validate_block below, whoever signed it)
        vals_hash = self.state.validators.hash()
        for i in range(1, len(run) - 1):
            if run[i].header.validators_hash != vals_hash:
                run = run[:i + 1]
                break
        blocks, successors = run[:-1], run[1:]
        results, parts_bids = verify_block_run(
            self.state, blocks, successors, self.verify_backend, lanes)
        applied = False
        for blk, nxt, err, (parts, bid) in zip(blocks, successors, results,
                                               parts_bids):
            if err is not None:
                self._handle_bad_block(blk.header.height, err)
                return applied
            if not self._apply_one(blk, nxt, parts, bid):
                return applied
            applied = True
        return applied

    def _apply_one(self, block: Block, successor: Block, parts, bid) -> bool:
        with trace.span("blocksync.apply", height=block.header.height):
            try:
                self.block_exec.validate_block(self.state, block)
            except Exception as e:  # noqa: BLE001
                self._handle_bad_block(block.header.height, e)
                return False
            self.pool.pop_request()
            with trace.span("blocksync.save_block"):
                self.store.save_block(block, parts, successor.last_commit)
            self.state, _ = self.block_exec.apply_block(self.state, bid,
                                                        block)
        self.blocks_synced += 1
        metrics.blocksync_blocks_applied.inc()
        return True

    def _handle_bad_block(self, height: int, err) -> None:
        # punish the server of the bad block and its successor's server
        # (either could have lied — reactor.go:377-390)
        metrics.blocksync_bad_blocks.inc()
        for h in (height, height + 1):
            bad = self.pool.redo_request(h)
            if bad is not None:
                self._stop_peer(bad, f"blocksync validation error: {err}")

    # -- statesync handoff (reactor.go SwitchToFastSync) --------------------

    def switch_to_fast_sync(self, state) -> None:
        self.state = state
        self.initial_state = state
        self.fast_sync = True
        self.pool.height = state.last_block_height + 1
        # restart the caught-up grace period: both the wall clock AND the
        # start height, or is_caught_up()'s height > _start_height check
        # passes instantly with a stale _max_peer_height and we'd hand over
        # to consensus without fetching the tail
        self.pool._started_at = time.monotonic()
        self.pool._start_height = self.pool.height
        self._thread = threading.Thread(
            target=self._pool_routine, args=(True,), daemon=True,
            name="blocksync-pool")
        self._thread.start()

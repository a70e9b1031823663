"""BlockExecutor (reference: state/execution.go).

ApplyBlock (:131): validate → exec over the consensus ABCI conn
(BeginBlock / DeliverTx×N pipelined / EndBlock, :259) → save responses →
updateState (:403, valset + params changes) → app Commit under mempool lock
(:211) → save state → fire events.
"""

from __future__ import annotations

import operator
import threading
import time
from typing import List, Optional, Tuple

from tmtpu.abci import types as abci
from tmtpu.crypto.encoding import pubkey_from_proto
from tmtpu.libs import faultinject, trace
from tmtpu.libs import metrics as _metrics
from tmtpu.state.state import State, median_time
from tmtpu.state.store import ABCIResponses, StateStore
from tmtpu.state.validation import validate_block
from tmtpu.types import pb
from tmtpu.types.block import Block, BlockID
from tmtpu.types.validator import Validator


class BlockExecutionError(Exception):
    pass


# chaos hook on the app-Commit boundary: an injected error here models a
# crashed/hung ABCI app at the worst moment (state updated, app_hash not
# yet durable) — the handshake/replay path must reconverge
_FAULT_ABCI_COMMIT = faultinject.register("abci.commit")

# chaos hook at the top of the async ApplyBlock worker: a crash here dies
# AFTER the WAL ENDHEIGHT barrier but BEFORE any app/state mutation — the
# widest window the overlap opens — and recovery must replay the block via
# handshake exactly like the serial executor's post_endheight crash
_FAULT_ASYNC_APPLY = faultinject.register("exec.async_apply")


class BlockExecutor:
    def __init__(self, state_store: StateStore, proxy_app, mempool=None,
                 evidence_pool=None, event_bus=None, verify_backend=None):
        self.store = state_store
        self.proxy_app = proxy_app  # consensus-connection abci client
        self.mempool = mempool
        self.evidence_pool = evidence_pool
        self.event_bus = event_bus
        self.verify_backend = verify_backend
        self._exec_pool = None  # lazy single-worker pool for async apply
        self._exec_pool_mtx = threading.Lock()
        # The (state, block) that last passed validate_block, held strongly,
        # with what state.validation.validate_block read of them. Go's
        # ValidateBlock runs at the same four places a height (prevote,
        # precommit's lock, finalize, ApplyBlock; the blocksync reactor and
        # ApplyBlock a block) on the same two objects, and it reads nothing
        # but its two arguments, so a repeat returns what the first call
        # returned. Replaced as one tuple: apply_block_async validates on
        # the executor's thread.
        self._validated: Optional[tuple] = None

    # -- proposal -----------------------------------------------------------

    def create_proposal_block(self, height: int, state: State,
                              last_commit, proposer_address: bytes,
                              time_ns: Optional[int] = None) -> Block:
        """execution.go:94 CreateProposalBlock — reap mempool + evidence."""
        max_bytes = state.consensus_params.block_max_bytes
        max_gas = state.consensus_params.block_max_gas
        evidence = (self.evidence_pool.pending_evidence(
            state.consensus_params.evidence_max_bytes)
            if self.evidence_pool else [])
        txs = (self.mempool.reap_max_bytes_max_gas(max_bytes, max_gas)
               if self.mempool else [])
        if time_ns is None:
            # state.go:244-249 — genesis time for the initial block, else
            # the weighted median of the LastCommit timestamps
            if height == state.initial_height:
                time_ns = state.last_block_time
            else:
                time_ns = median_time(last_commit, state.last_validators)
        header = state.make_block_header(
            height, time_ns, txs, last_commit, evidence, proposer_address
        )
        block = Block(header, txs, evidence, last_commit)
        block.fill_header()
        return block

    # -- apply --------------------------------------------------------------

    def validate_block(self, state: State, block: Block) -> None:
        """execution.go:117 ValidateBlock — structural/state checks, then
        every piece of block evidence is verified through the pool
        (execution.go:122 evpool.CheckEvidence). Without this a byzantine
        proposer could embed fabricated evidence framing honest validators.

        A repeat of the last (state, block) that passed — the same two
        objects, reading as they did then — skips the pure part; the
        evidence pool, whose state is no argument, is asked every call."""
        with trace.span("state.validate_block",
                        height=block.header.height) as sp:
            held, reads = _validation_reads(state, block)
            slot = self._validated
            repeat = slot is not None and \
                all(map(operator.is_, slot[0], held)) and slot[1] == reads
            sp.set(repeat=repeat)
            _metrics.state_validate_block.inc(
                path="repeat" if repeat else "full")
            if not repeat:
                validate_block(state, block,
                               verify_backend=self.verify_backend)
            if self.evidence_pool is not None and block.evidence:
                from tmtpu.evidence.pool import EvidenceError

                try:
                    self.evidence_pool.check_evidence(block.evidence)
                except EvidenceError as e:
                    raise BlockExecutionError(
                        f"invalid evidence: {e}") from e
            self._validated = (held, reads)

    def apply_block(self, state: State, block_id: BlockID, block: Block
                    ) -> Tuple[State, int]:
        """execution.go:131 ApplyBlock. Returns (new_state, retain_height)."""
        import time as _time

        from tmtpu.libs import fail

        t0 = _time.perf_counter()
        self.validate_block(state, block)
        # ABCI-handoff stamp on the height's root trace: the instant the
        # committed block crosses into the application
        trace.mark_height(block.header.height, "abci.handoff",
                          txs=len(block.txs))
        with trace.span("state.exec_app", txs=len(block.txs)):
            abci_responses = self._exec_block_on_proxy_app(state, block)
        # execution.go:149 — after exec, before saving
        fail.fail_point("exec.post_exec")
        with trace.span("state.save_responses"):
            self.store.save_abci_responses(block.header.height,
                                           abci_responses)

        # validate validator updates per consensus params
        val_updates = []
        for vu in abci_responses.end_block.validator_updates:
            pk = pubkey_from_proto(vu.pub_key)
            if pk.type_value() not in state.consensus_params.pub_key_types:
                raise BlockExecutionError(
                    f"validator update with forbidden key type "
                    f"{pk.type_value()!r}"
                )
            if vu.power < 0:
                raise BlockExecutionError("validator update with negative power")
            val_updates.append(Validator(pk, vu.power))

        new_state = update_state(state, block_id, block.header,
                                 abci_responses, val_updates)

        fail.fail_point("exec.pre_app_commit")  # execution.go:180
        # Commit: lock mempool, flush, app Commit, update mempool
        with trace.span("state.commit_app"):
            app_hash, retain_height = self._commit(
                new_state, block, abci_responses.deliver_txs)
        # execution.go:196 — app committed, state unsaved
        fail.fail_point("exec.post_app_commit")
        if self.evidence_pool:
            self.evidence_pool.update(new_state, block.evidence)
        new_state.app_hash = app_hash
        with trace.span("state.save"):
            self.store.save(new_state)

        if self.event_bus:
            self._fire_events(block, block_id, abci_responses, val_updates)
        from tmtpu.libs import timeline, txlat

        timeline.record(block.header.height, timeline.EVENT_APPLY_BLOCK,
                        txs=len(block.txs),
                        seconds=round(_time.perf_counter() - t0, 6))
        # apply checkpoint (async or serial executor alike): commit→apply
        # is exactly the span the async_exec overlap hides
        txlat.stamp_height(block.header.height, "apply")
        trace.mark_height(block.header.height, "height.apply",
                          txs=len(block.txs))
        return new_state, retain_height

    def apply_block_async(self, state: State, block_id: BlockID,
                          block: Block, done) -> None:
        """Run apply_block on a dedicated single-worker executor and call
        ``done(result, error)`` when it finishes (exactly one is None).

        The single worker preserves apply ordering by construction;
        consensus additionally guarantees one apply in flight (it holds
        the committed block at STEP_COMMIT until the done-message drains
        through its receive loop). The caller owns the WAL barrier: this
        must only be invoked after ENDHEIGHT(H) is durable, so a crash
        anywhere in here recovers through the handshake replay path the
        serial executor already exercises."""
        def _run():
            try:
                faultinject.fire(_FAULT_ASYNC_APPLY)
                result = self.apply_block(state, block_id, block)
            except BaseException as e:
                done(None, e)
            else:
                done(result, None)

        with self._exec_pool_mtx:
            if self._exec_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._exec_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="apply-block")
            pool = self._exec_pool
        pool.submit(_run)

    def _exec_block_on_proxy_app(self, state: State, block: Block
                                 ) -> ABCIResponses:
        """execution.go:259 — BeginBlock, pipelined DeliverTxs, EndBlock."""
        commit_info = self._begin_block_commit_info(state, block)
        byz_vals = self._abci_evidence(state, block)
        rbb = self.proxy_app.begin_block_sync(abci.RequestBeginBlock(
            hash=block.hash() or b"",
            header=block.header.to_proto(),
            last_commit_info=commit_info,
            byzantine_validators=byz_vals,
        ))
        # one batched enqueue + one flush for the whole block: amortizes
        # the per-frame mutex/socket round trip (clients without the
        # batch surface keep the per-tx async enqueue)
        batch = getattr(self.proxy_app, "deliver_tx_batch_async", None)
        reqs = [abci.RequestDeliverTx(tx=tx) for tx in block.txs]
        if batch is not None:
            reqres = batch(reqs)
        else:
            reqres = [self.proxy_app.deliver_tx_async(r) for r in reqs]
        self.proxy_app.flush_sync()
        deliver_txs = [rr.wait(timeout=60.0).deliver_tx for rr in reqres]
        if any(dt is None for dt in deliver_txs):
            raise BlockExecutionError("DeliverTx failed")
        rend = self.proxy_app.end_block_sync(
            abci.RequestEndBlock(height=block.header.height))
        return ABCIResponses(deliver_txs, rbb, rend)

    def _begin_block_commit_info(self, state: State, block: Block
                                 ) -> abci.LastCommitInfo:
        """execution.go getBeginBlockValidatorInfo."""
        votes = []
        if block.header.height > state.initial_height:
            last_vals = self.store.load_validators(block.header.height - 1) \
                or state.last_validators
            for i, cs in enumerate(block.last_commit.signatures):
                val = last_vals.validators[i]
                votes.append(abci.VoteInfo(
                    validator=abci.Validator(address=val.address,
                                             power=val.voting_power),
                    signed_last_block=not cs.is_absent(),
                ))
            round = block.last_commit.round
        else:
            round = 0
        return abci.LastCommitInfo(round=round, votes=votes)

    def _abci_evidence(self, state: State, block: Block) -> List[abci.Evidence]:
        from tmtpu.types.evidence import DuplicateVoteEvidence

        out = []
        for ev in block.evidence:
            if isinstance(ev, DuplicateVoteEvidence):
                out.append(abci.Evidence(
                    type=abci.EVIDENCE_TYPE_DUPLICATE_VOTE,
                    validator=abci.Validator(
                        address=ev.vote_a.validator_address,
                        power=ev.validator_power),
                    height=ev.height(),
                    time=pb.Timestamp.from_unix_nanos(ev.time()),
                    total_voting_power=ev.total_voting_power,
                ))
            else:
                out.append(abci.Evidence(
                    type=abci.EVIDENCE_TYPE_LIGHT_CLIENT_ATTACK,
                    height=ev.height(),
                    time=pb.Timestamp.from_unix_nanos(ev.time()),
                    total_voting_power=ev.total_voting_power,
                ))
        return out

    def _commit(self, state: State, block: Block, deliver_txs
                ) -> Tuple[bytes, int]:
        """execution.go:211 Commit — mempool locked around app commit."""
        if self.mempool:
            self.mempool.lock()
        try:
            faultinject.fire(_FAULT_ABCI_COMMIT)
            res = self.proxy_app.commit_sync()
            if self.mempool:
                self.mempool.update(
                    block.header.height, block.txs, deliver_txs
                )
        finally:
            if self.mempool:
                self.mempool.unlock()
        return bytes(res.data), res.retain_height

    def _fire_events(self, block, block_id, abci_responses, val_updates):
        self.event_bus.publish_new_block(block, block_id,
                                         abci_responses.begin_block,
                                         abci_responses.end_block)
        self.event_bus.publish_new_block_header(block.header)
        for i, tx in enumerate(block.txs):
            self.event_bus.publish_tx(abci.TxResult(
                height=block.header.height, index=i, tx=tx,
                result=abci_responses.deliver_txs[i],
            ))
        if val_updates:
            self.event_bus.publish_validator_set_updates(val_updates)


def _validation_reads(state: State, block: Block) -> Tuple[tuple, tuple]:
    """What state.validation.validate_block reads of its two arguments:
    the objects, to be compared by identity (the two themselves, the
    block's LastCommit, txs and evidence, State's params and three sets),
    then what is compared by value: the header by its hash (computed anew:
    ``Block.hash()`` keeps the first) and State's fields, which the
    handshake writes in place (consensus/replay.py)."""
    bid = state.last_block_id
    return ((state, block, block.last_commit, block.txs, block.evidence,
             state.consensus_params, state.validators,
             state.next_validators, state.last_validators),
            (block.header.hash(), state.chain_id, state.app_version,
             state.initial_height, state.last_block_height,
             (bid.hash, bid.parts_total, bid.parts_hash),
             state.last_block_time, state.app_hash, state.last_results_hash))


def update_state(state: State, block_id: BlockID, header,
                 abci_responses: ABCIResponses, val_updates: List[Validator]
                 ) -> State:
    """execution.go:403 updateState."""
    last_height_vals_changed = state.last_height_validators_changed
    with trace.span("state.update_validators", changes=len(val_updates)):
        n_val_set = state.next_validators.copy()
        if val_updates:
            kinds = n_val_set.update_with_change_set(val_updates)
            for kind, n in kinds.items():
                if n:
                    _metrics.state_validator_updates.inc(n, kind=kind)
            last_height_vals_changed = header.height + 1 + 1
        n_val_set.increment_proposer_priority(1)

    params = state.consensus_params
    app_version = state.app_version
    last_height_params_changed = state.last_height_consensus_params_changed
    if abci_responses.end_block.consensus_param_updates is not None:
        updates = abci_responses.end_block.consensus_param_updates
        params = params.update(updates)
        params.validate_basic()
        if updates.version is not None:
            app_version = params.app_version
        last_height_params_changed = header.height + 1

    return State(
        chain_id=state.chain_id,
        initial_height=state.initial_height,
        last_block_height=header.height,
        last_block_id=block_id,
        last_block_time=header.time,
        next_validators=n_val_set,
        validators=state.next_validators.copy(),
        last_validators=state.validators.copy(),
        last_height_validators_changed=last_height_vals_changed,
        consensus_params=params,
        last_height_consensus_params_changed=last_height_params_changed,
        last_results_hash=abci_responses.results_hash(),
        app_hash=b"",  # set by caller after app Commit
        app_version=app_version,
    )

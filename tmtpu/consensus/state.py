"""The consensus state machine (reference: consensus/state.go).

Single-writer event loop exactly like the reference's receiveRoutine
(:707): peer messages, internal (own) messages and timeouts are drained by
one thread; every message is WAL'd before processing; step transitions
follow the two-phase Tendermint BFT algorithm — enterNewRound (:976),
enterPropose (:1060), enterPrevote (:1226), enterPrecommit (:1322),
enterCommit (:1476), finalizeCommit (:1567).

TPU-first difference: the receive loop drains ALL queued messages per
iteration and groups the votes, so signature verification for a burst of
votes is ONE BatchVerifier dispatch (the batching window for the TPU
backend) instead of per-vote serial verifies (:1947 tryAddVote).
"""

from __future__ import annotations

import os
import queue
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

from tmtpu.config.config import ConsensusConfig
from tmtpu.consensus.ticker import TimeoutInfo, TimeoutTicker
from tmtpu.consensus.types import (
    STEP_COMMIT, STEP_NEW_HEIGHT, STEP_NEW_ROUND, STEP_PRECOMMIT,
    STEP_PRECOMMIT_WAIT, STEP_PREVOTE, STEP_PREVOTE_WAIT, STEP_PROPOSE,
    HeightVoteSet, RoundState,
)
from tmtpu.consensus.wal import (
    EndHeightPB, EventRoundStatePB, MsgInfoPB, TimeoutInfoPB, WAL,
    vote_record_template,
)
from tmtpu.crypto import batch as _crypto_batch
from tmtpu.libs import metrics as _m
from tmtpu.libs import timeline, trace, txlat
from tmtpu.libs import valstats as _valstats
from tmtpu.libs.service import BaseService
from tmtpu.types import pb
from tmtpu.types.block import BlockID, Commit
from tmtpu.types.evidence import DuplicateVoteEvidence
from tmtpu.types.part_set import Part, PartSet
from tmtpu.types.vote import (
    ErrVoteConflictingVotes, PRECOMMIT, PREVOTE, Proposal, Vote, VoteError,
)
from tmtpu.types.vote_set import VoteSet


class MsgInfo:
    # enq_s: perf_counter at the put on the peers' queue (0.0: not put
    # there — an internal message, or a test's own)
    __slots__ = ("msg", "peer_id", "enq_s")

    def __init__(self, msg, peer_id: str = ""):
        self.msg = msg
        self.peer_id = peer_id
        self.enq_s = 0.0


class ProposalMessage:
    __slots__ = ("proposal",)

    def __init__(self, proposal: Proposal):
        self.proposal = proposal


class BlockPartMessage:
    __slots__ = ("height", "round", "part")

    def __init__(self, height: int, round: int, part: Part):
        self.height = height
        self.round = round
        self.part = part


class VoteMessage:
    __slots__ = ("vote",)

    def __init__(self, vote: Vote):
        self.vote = vote


class RetrySignMessage:
    """Internal: re-attempt our own vote after a transient signing failure
    (remote signer reconnecting). Never hits the WAL or the wire."""

    __slots__ = ("height", "round", "vote_type", "block_hash", "parts")

    def __init__(self, height: int, round: int, vote_type: int,
                 block_hash: bytes, parts):
        self.height = height
        self.round = round
        self.vote_type = vote_type
        self.block_hash = block_hash
        self.parts = parts


class ApplyBlockDoneMessage:
    """Internal: the async ApplyBlock worker finished height ``height``
    (consensus.async_exec). Carries the executor's (new_state,
    retain_height) result or the error that halted it. Never hits the
    WAL or the wire — on crash-recovery the WAL's ENDHEIGHT barrier plus
    handshake replay reconstruct the apply instead."""

    __slots__ = ("height", "block", "result", "error")

    def __init__(self, height: int, block, result, error):
        self.height = height
        self.block = block
        self.result = result
        self.error = error


class ConsensusState(BaseService):
    def __init__(self, config: ConsensusConfig, state, block_exec,
                 block_store, mempool=None, evidence_pool=None,
                 event_bus=None, priv_validator=None, wal_path: str = "",
                 verify_backend=None):
        super().__init__("ConsensusState")
        self.config = config
        self.block_exec = block_exec
        self.block_store = block_store
        self.mempool = mempool
        self.evidence_pool = evidence_pool
        self.event_bus = event_bus
        self.priv_validator = priv_validator
        self.priv_validator_pub_key = (
            priv_validator.get_pub_key() if priv_validator else None
        )
        self.verify_backend = verify_backend

        self.rs = RoundState()
        self.state = None  # sm.State, set by update_to_state

        # the bound is also the most one drain takes, and so what sizes a
        # vote flush's device shape (crypto/batch.py vote_flush_lanes)
        self.peer_msg_queue: "queue.Queue[MsgInfo]" = queue.Queue(
            maxsize=_crypto_batch.DRAIN_LANES)
        self.internal_msg_queue: "queue.Queue[MsgInfo]" = queue.Queue(maxsize=1000)
        self._timeout_queue: "queue.Queue[TimeoutInfo]" = queue.Queue()
        self.ticker = TimeoutTicker(self._timeout_queue.put)
        self.wal = WAL(wal_path) if wal_path else None
        self._mtx = threading.RLock()
        self._thread: Optional[threading.Thread] = None
        self._done_first_block = threading.Event()
        self.replay_mode = False
        # cleared by the blocksync/statesync handover (SwitchToConsensus
        # with skipWAL): the WAL predates the synced blocks
        self.do_wal_catchup = True
        # block parts that arrived before their parts header was known —
        # replayed by _flush_pending_parts once it is (see
        # _add_proposal_block_part)
        self._pending_parts: dict = {}
        # test/byzantine hook: replaces decide_proposal when set
        self.decide_proposal_override = None
        # maverick-style misbehavior schedule {height: name}
        # (tmtpu.consensus.misbehavior; reference test/maverick)
        self.misbehaviors: dict = {}
        # outbound hooks, wired by the reactor (or in-proc test harnesses):
        # fired for our own signed votes / proposals so they reach peers
        self.on_own_vote = None  # callable(Vote)
        self.on_own_proposal = None  # callable(Proposal, PartSet)
        # new-height listeners (e.g. tests waiting for commits)
        self._height_cv = threading.Condition(self._mtx)
        # async ApplyBlock overlap (config.async_exec): True between the
        # handoff to the executor thread and the done-message draining
        # back through the receive loop; finalize paths no-op while set
        self._apply_inflight = False
        self._apply_started_s = 0.0

        self.update_to_state(state)
        self._sync_timeout_commit = True

    # ------------------------------------------------------------------ API

    def on_start(self) -> None:
        # crash recovery: rebuild LastCommit from the stored seen commit
        # (state.go reconstructLastCommit), then re-feed WAL messages for
        # the in-progress height (replay.go:93 catchupReplay)
        self._reconstruct_last_commit()
        if self.do_wal_catchup:
            self.catchup_replay()
        self.ticker.start()
        self._thread = threading.Thread(
            target=self._receive_routine, daemon=True, name="cs-receive")
        self._thread.start()
        # start the height's round 0 (state.go OnStart -> scheduleRound0)
        self._schedule_round0()

    def _reconstruct_last_commit(self) -> None:
        state = self.state
        if state.last_block_height == 0 or self.rs.last_commit is not None:
            return
        seen = self.block_store.load_seen_commit(state.last_block_height)
        if seen is None:
            raise RuntimeError(
                f"failed to reconstruct last commit: no seen commit for "
                f"height {state.last_block_height}"
            )
        from tmtpu.types.vote_set import commit_to_vote_set

        vs = commit_to_vote_set(state.chain_id, seen, state.last_validators)
        if not vs.has_two_thirds_majority():
            raise RuntimeError("reconstructed commit lacks +2/3 majority")
        self.rs.last_commit = vs

    def catchup_replay(self, on_msg=None, live_redrive: bool = True) -> None:
        """Replay the in-progress height from the WAL (replay.go:93
        catchupReplay). ``on_msg(wal_msg)`` — when given — is invoked
        before each message is applied; `tmtpu replay-console` uses it
        to step interactively (commands/replay.go replay-console).
        ``live_redrive=False`` suppresses the post-replay round re-drive
        — an INSPECTION caller must never sign proposals/votes or append
        to the WAL it is examining."""
        if self.wal is None:
            return
        msgs = list(WAL.iter_messages(self.wal.path))
        start = 0
        found_marker = False
        for i, m in enumerate(msgs):
            if m.end_height is not None:
                if m.end_height.height >= self.rs.height:
                    raise RuntimeError(
                        f"WAL contains #ENDHEIGHT for {m.end_height.height} "
                        f">= current height {self.rs.height}"
                    )
                if m.end_height.height == self.rs.height - 1:
                    start = i + 1
                    found_marker = True
        if not found_marker and any(m.end_height is not None for m in msgs):
            return  # markers exist but not height-1: nothing to catch up
        self.replay_mode = True
        self.rs.metrics_paused = True  # replay-speed steps aren't real
        try:
            for m in msgs[start:]:
                if on_msg is not None:
                    on_msg(m)
                with self._mtx:
                    if m.msg_info is not None:
                        self._replay_msg_info(m.msg_info)
                    elif m.timeout is not None:
                        self._handle_timeout(TimeoutInfo(
                            m.timeout.duration_ns, m.timeout.height,
                            m.timeout.round, m.timeout.step))
        finally:
            self.replay_mode = False
            self.rs.metrics_paused = False
        if not live_redrive:
            return
        # Liveness after a mid-round crash: replay may have advanced the
        # step past actions we never performed (e.g. the step reached
        # Precommit but our own precommit was never signed before the
        # crash). Re-drive the round live — _sign_add_vote is idempotent
        # against votes already present, so nothing double-signs.
        with self._mtx:
            rs = self.rs
            if rs.step > STEP_NEW_ROUND:
                rs.step = STEP_NEW_ROUND
                self._enter_propose(rs.height, rs.round)
                self._check_vote_transitions()

    def _replay_msg_info(self, info) -> None:
        if info.proposal is not None:
            self._set_proposal_safe(Proposal.from_proto(info.proposal))
        elif info.block_part is not None:
            self._add_proposal_block_part(BlockPartMessage(
                info.block_part_height, info.block_part_round,
                Part.from_proto(info.block_part)), info.peer_id)
        elif info.vote is not None:
            self._try_add_votes([(Vote.from_proto(info.vote), info.peer_id)])

    def on_stop(self) -> None:
        self.ticker.stop()
        self.peer_msg_queue.put(None)
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self.wal is not None:
            self.wal.close()

    def get_round_state(self) -> RoundState:
        with self._mtx:
            return self.rs

    def round_state_nolock(self) -> RoundState:
        """The live RoundState WITHOUT taking the consensus mutex — for
        gossip/query threads (reference reactor.go:403
        updateRoundStateRoutine keeps a lock-free snapshot for exactly
        this). ``self.rs`` is a single object mutated in place, so the
        locked getter returns the same reference anyway; all it adds is
        blocking — during finalize-commit (ABCI + stores, held under the
        mutex for the whole block) every gossip thread would stall, peers
        would miss parts/votes, and under tx load the net livelocks on
        failed rounds. Readers must tolerate field-level races (take
        local refs; fields may flip to None)."""
        return self.rs

    def wait_for_height(self, height: int, timeout: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout
        with self._height_cv:
            while self.rs.height <= height:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._height_cv.wait(left)
        return True

    # -- inbound ------------------------------------------------------------

    def _put_peer_msg(self, mi: MsgInfo) -> None:
        """The reactor's hand-over. A full queue blocks the peer's thread
        until the consensus thread has drained, and only that wait is
        timed: the put that finds room pays for one clock read."""
        mi.enq_s = t = time.perf_counter()
        try:
            self.peer_msg_queue.put_nowait(mi)
        except queue.Full:
            self.peer_msg_queue.put(mi)
            _m.consensus_peer_queue_blocked.observe(time.perf_counter() - t)

    def add_proposal(self, proposal: Proposal, peer_id: str = "") -> None:
        self._put_peer_msg(MsgInfo(ProposalMessage(proposal), peer_id))

    def add_block_part(self, height: int, round: int, part: Part,
                       peer_id: str = "") -> None:
        self._put_peer_msg(
            MsgInfo(BlockPartMessage(height, round, part), peer_id))

    def add_vote_msg(self, vote: Vote, peer_id: str = "") -> None:
        self._put_peer_msg(MsgInfo(VoteMessage(vote), peer_id))

    # ------------------------------------------------- state initialization

    def update_to_state(self, state) -> None:
        """state.go:1683 updateToState — advance RoundState to the height
        after ``state``'s last block."""
        with self._mtx:
            if self.rs.commit_round > -1 and 0 < self.rs.height and \
                    self.rs.height != state.last_block_height:
                raise RuntimeError(
                    f"updateToState expected height {self.rs.height}, "
                    f"state at {state.last_block_height}"
                )
            # the set of the height being entered (state.go:1683
            # `validators := state.Validators`): ``update_state`` has
            # already moved next_validators into validators
            validators = state.validators.copy()

            last_precommits = None
            if self.rs.commit_round > -1 and self.rs.votes is not None:
                pc = self.rs.votes.precommits(self.rs.commit_round)
                if pc is None or not pc.has_two_thirds_majority():
                    raise RuntimeError(
                        "updateToState called with no +2/3 precommits")
                last_precommits = pc

            height = state.last_block_height + 1
            if height == 1:
                height = state.initial_height

            self.rs.height = height
            self.rs.round = 0
            self.rs.step = STEP_NEW_HEIGHT
            if self.config.skip_timeout_commit:
                # no commit wait: next round starts immediately (but always
                # via the ticker — entering the next height synchronously
                # would recurse one Python stack level per height)
                self.rs.start_time = time.time_ns()
            elif self.rs.commit_time == 0:
                self.rs.start_time = time.time_ns() + \
                    self.config.timeout_commit_ns
            else:
                self.rs.start_time = self.rs.commit_time + \
                    self.config.timeout_commit_ns
            self.rs.validators = validators
            self.rs.proposal = None
            self.rs.proposal_block = None
            self.rs.proposal_block_parts = None
            self.rs.locked_round = -1
            self.rs.locked_block = None
            self.rs.locked_block_parts = None
            self.rs.valid_round = -1
            self.rs.valid_block = None
            self.rs.valid_block_parts = None
            self.rs.votes = HeightVoteSet(state.chain_id, height, validators,
                                          self.verify_backend)
            self.rs.commit_round = -1
            self.rs.last_commit = last_precommits
            self.rs.last_validators = state.last_validators.copy() \
                if state.last_validators else None
            self.rs.triggered_timeout_precommit = False
            self.state = state
            self._height_cv.notify_all()

    def _schedule_round0(self) -> None:
        sleep_ns = max(0, self.rs.start_time - time.time_ns())
        self.ticker.schedule_timeout(TimeoutInfo(
            sleep_ns, self.rs.height, 0, STEP_NEW_HEIGHT))

    # ------------------------------------------------------- receive loop

    def _receive_routine(self) -> None:
        while self.is_running() or not self._quit.is_set():
            try:
                batch = self._drain_messages()
                if batch is None:
                    return  # stop sentinel
                msgs, timeouts = batch
                with self._mtx:
                    # the whole handling cycle runs under the current
                    # height's root trace context: every span recorded
                    # on this thread (step transitions, batch verifies,
                    # sidecar client requests) carries the height's
                    # trace id — None (unsampled) is a no-op
                    with trace.activate(
                            trace.height_context(self.rs.height)):
                        with trace.span("consensus.wal",
                                        msgs=len(msgs)) as sp:
                            sp.set(template=self._wal_write_msgs(msgs))
                        self._handle_msgs(msgs)
                        for ti in timeouts:
                            if self.wal is not None:
                                self.wal.write(self.wal.make(
                                    timeout=TimeoutInfoPB(
                                        duration_ns=ti.duration_ns,
                                        height=ti.height, round=ti.round,
                                        step=ti.step)))
                            self._handle_timeout(ti)
                        self._flush_pending_parts()
            except Exception:
                # consensus failures halt the node by design
                # (state.go:722-735); keep the WAL so the operator can replay
                traceback.print_exc()
                if self.wal is not None:
                    self.wal.flush_and_sync()
                return

    def _drain_messages(self):
        """Block for one message/timeout, then drain what is pending — the
        TPU batching window. Of the peers' messages a drain takes at most
        the queue's bound (the rest is the next drain's), so that a vote
        flush has a known widest size (crypto/batch.py vote_flush_lanes)."""
        msgs: List[MsgInfo] = []
        timeouts: List[TimeoutInfo] = []
        cap = self.peer_msg_queue.maxsize

        def first(wait_s: float) -> Optional[bool]:
            """One look at the three queues, blocking ``wait_s`` on the
            peers': True = got one, None = stop."""
            try:
                timeouts.append(self._timeout_queue.get_nowait())
                return True
            except queue.Empty:
                pass
            for q, wait in ((self.internal_msg_queue, 0.0),
                            (self.peer_msg_queue, wait_s)):
                try:
                    mi = q.get(timeout=wait) if wait else q.get_nowait()
                except queue.Empty:
                    continue
                if mi is None:
                    return None
                msgs.append(mi)
                return True
            return False

        # the loop asking for its next message: no time at all while the
        # queues hold one, else the commit wait or a peer's silence
        with trace.span("consensus.idle"):
            got = first(0.0)
            while got is False:
                got = first(0.02)
                if got is False and self._quit.is_set():
                    return None
        if got is None:
            return None
        # drain the rest without blocking
        for q, most in ((self.internal_msg_queue, None),
                        (self.peer_msg_queue, cap)):
            while most is None or len(msgs) < most:
                try:
                    mi = q.get_nowait()
                except queue.Empty:
                    break
                if mi is None:
                    return None
                msgs.append(mi)
        # adaptive gather (crypto/batch.py SCHEDULER): when rate×RTT data
        # says the pending vote count is below the amortization target,
        # linger a bounded few ms draining more — one fuller dispatch
        # instead of two sparse ones. Inert (0.0 wait) until real device
        # RTT samples exist, so CPU-only nodes keep the legacy window;
        # never delays a timeout.
        if not timeouts and len(msgs) < cap:
            n_votes = sum(1 for mi in msgs
                          if isinstance(mi.msg, VoteMessage))
            if n_votes:
                wait = _crypto_batch.SCHEDULER.gather_wait_s(n_votes)
                if wait > 0:
                    _m.crypto_flush_gather_waits.inc()
                    deadline = time.monotonic() + wait
                    while len(msgs) < cap:
                        left = deadline - time.monotonic()
                        if left <= 0:
                            break
                        try:
                            mi = self.peer_msg_queue.get(timeout=left)
                        except queue.Empty:
                            break
                        if mi is None:
                            return None
                        msgs.append(mi)
        while True:
            try:
                timeouts.append(self._timeout_queue.get_nowait())
            except queue.Empty:
                break
        # how long the peers' messages lay in their queue, once a drain
        now = time.perf_counter()
        waits = [now - mi.enq_s for mi in msgs if mi.enq_s]
        if waits:
            _m.consensus_peer_queue_wait.observe(sum(waits), len(waits))
        return msgs, timeouts

    def _wal_write_msgs(self, msgs: List[MsgInfo]) -> int:
        """A drain's records, every one handed to the file before
        ``_handle_msgs`` sees any of the drain's messages; returns how many
        came from a template. The votes that share (peer, type, height,
        round, block id) are encoded from one template (wal.py
        ``vote_record_template``), every other kind by the reflective
        encoder; each record has its own time. The records go to the file
        a run at a time (``WAL.write_records``), and a run ends at every
        message of the node's own, which is fsync'd before it is processed
        (state.go:763)."""
        wal = self.wal
        if wal is None or self.replay_mode:
            return 0
        templates: Dict[tuple, Callable[[int, Vote], bytes]] = {}
        run: List[bytes] = []
        n_template = n_reflective = 0
        for mi in msgs:
            m = mi.msg
            if isinstance(m, VoteMessage):
                v = m.vote
                bid = v.block_id
                key = (mi.peer_id, v.type, v.height, v.round,
                       bid.hash, bid.parts_total, bid.parts_hash)
                payload = templates.get(key)
                if payload is None:
                    payload = templates[key] = vote_record_template(
                        mi.peer_id, v.type, v.height, v.round, bid)
                run.append(payload(time.time_ns(), v))
                n_template += 1
            else:
                if isinstance(m, ProposalMessage):
                    info = MsgInfoPB(peer_id=mi.peer_id,
                                     proposal=m.proposal.to_proto())
                elif isinstance(m, BlockPartMessage):
                    info = MsgInfoPB(peer_id=mi.peer_id,
                                     block_part_height=m.height,
                                     block_part_round=m.round,
                                     block_part=m.part.to_proto())
                else:
                    continue
                run.append(wal.make(msg_info=info).encode())
                n_reflective += 1
            if mi.peer_id == "":
                wal.write_records(run)
                wal.flush_and_sync()
                run = []
        if run:
            wal.write_records(run)
        if n_template:
            _m.consensus_wal_records.inc(n_template, path="template")
        if n_reflective:
            _m.consensus_wal_records.inc(n_reflective, path="reflective")
        return n_template

    def _handle_msgs(self, msgs: List[MsgInfo]) -> None:
        """Group votes for batch verification; other messages in order."""
        vote_batch: List[Tuple[Vote, str]] = []
        for mi in msgs:
            if isinstance(mi.msg, VoteMessage):
                vote_batch.append((mi.msg.vote, mi.peer_id))
            else:
                # flush pending votes first to preserve ordering semantics
                if vote_batch:
                    self._try_add_votes(vote_batch)
                    vote_batch = []
                if isinstance(mi.msg, ProposalMessage):
                    self._set_proposal_safe(mi.msg.proposal)
                elif isinstance(mi.msg, BlockPartMessage):
                    self._add_proposal_block_part(mi.msg, mi.peer_id)
                elif isinstance(mi.msg, ApplyBlockDoneMessage):
                    self._finalize_commit_resume(mi.msg)
                elif isinstance(mi.msg, RetrySignMessage):
                    m = mi.msg
                    # only while the round that wanted the vote is current
                    if self.rs.height == m.height and \
                            self.rs.round == m.round:
                        self._sign_add_vote(m.vote_type, m.block_hash,
                                            m.parts)
        if vote_batch:
            self._try_add_votes(vote_batch)

    def _handle_timeout(self, ti: TimeoutInfo) -> None:
        """state.go:744 handleTimeout."""
        rs = self.rs
        if ti.height != rs.height or ti.round < rs.round or \
                (ti.round == rs.round and ti.step < rs.step):
            return  # stale
        if ti.step == STEP_NEW_HEIGHT:
            self._enter_new_round(ti.height, 0)
        elif ti.step == STEP_NEW_ROUND:
            self._enter_propose(ti.height, 0)
        elif ti.step == STEP_PROPOSE:
            if self.event_bus:
                self.event_bus.publish_timeout_propose(rs)
            if rs.proposal is None:
                # the scheduled proposer never delivered: charge the
                # missed proposal to it (validator forensics ledger)
                prop = rs.validators.get_proposer()
                if prop is not None:
                    _valstats.on_missed_proposal(rs.height, rs.round,
                                                 prop.address)
            self._enter_prevote(ti.height, ti.round)
        elif ti.step == STEP_PREVOTE_WAIT:
            if self.event_bus:
                self.event_bus.publish_timeout_wait(rs)
            self._enter_precommit(ti.height, ti.round)
        elif ti.step == STEP_PRECOMMIT_WAIT:
            if self.event_bus:
                self.event_bus.publish_timeout_wait(rs)
            self._enter_precommit(ti.height, ti.round)
            self._enter_new_round(ti.height, ti.round + 1)

    # ------------------------------------------------------ step functions

    @trace.traced("consensus.enter_new_round")
    def _enter_new_round(self, height: int, round: int) -> None:
        """state.go:976."""
        rs = self.rs
        if rs.height != height or round < rs.round or \
                (rs.round == round and rs.step != STEP_NEW_HEIGHT):
            return
        if rs.start_time > time.time_ns():
            pass  # "need to set a buffer and log message here"
        validators = rs.validators
        if rs.round < round:
            validators = validators.copy()
            validators.increment_proposer_priority(round - rs.round)
        rs.round = round
        rs.step = STEP_NEW_ROUND
        timeline.record(height, "consensus.enter_new_round", round=round)
        rs.validators = validators
        if round != 0:
            # round 0 keeps the proposal from NewHeight; later rounds reset
            rs.proposal = None
            rs.proposal_block = None
            rs.proposal_block_parts = None
        rs.votes.set_round(round + 1)
        rs.triggered_timeout_precommit = False
        if self.event_bus:
            self.event_bus.publish_new_round(rs)
        wait_for_txs = (not self.config.create_empty_blocks and round == 0
                        and self.mempool is not None
                        and self.mempool.is_empty())
        if wait_for_txs:
            if self.config.create_empty_blocks_interval_ns > 0:
                self.ticker.schedule_timeout(TimeoutInfo(
                    self.config.create_empty_blocks_interval_ns, height,
                    round, STEP_NEW_ROUND))
            # else: wait for the mempool's txs_available notification
        else:
            self._enter_propose(height, round)

    @trace.traced("consensus.enter_propose")
    def _enter_propose(self, height: int, round: int) -> None:
        """state.go:1060."""
        rs = self.rs
        if rs.height != height or round < rs.round or \
                (rs.round == round and rs.step >= STEP_PROPOSE):
            return
        rs.round = round
        rs.step = STEP_PROPOSE
        timeline.record(height, "consensus.enter_propose", round=round)
        _valstats.begin_step(height, round, "propose")
        self._new_step()
        # propose-step timeout -> prevote nil
        self.ticker.schedule_timeout(TimeoutInfo(
            self.config.propose_timeout(round), height, round, STEP_PROPOSE))
        if self.priv_validator is not None and self._is_proposer():
            self._decide_proposal(height, round)
        if self._is_proposal_complete():
            self._enter_prevote(height, round)

    def _is_proposer(self) -> bool:
        prop = self.rs.validators.get_proposer()
        return prop is not None and \
            prop.address == self.priv_validator_pub_key.address()

    def _decide_proposal(self, height: int, round: int) -> None:
        """state.go defaultDecideProposal — create/reuse block, sign the
        proposal, feed proposal+parts through the internal queue."""
        if self.replay_mode:
            return  # in replay, the proposal comes back through the WAL
        if self.decide_proposal_override is not None:
            self.decide_proposal_override(self, height, round)
            return
        rs = self.rs
        if rs.valid_block is not None:
            block, parts = rs.valid_block, rs.valid_block_parts
        else:
            commit = None
            if height == self.state.initial_height:
                commit = Commit(height=0, round=0, block_id=BlockID(),
                                signatures=[])
            elif rs.last_commit is not None and \
                    rs.last_commit.has_two_thirds_majority():
                commit = rs.last_commit.make_commit()
            else:
                return  # no commit for previous block yet
            proposer_addr = self.priv_validator_pub_key.address()
            block = self.block_exec.create_proposal_block(
                height, self.state, commit, proposer_addr)
            parts = PartSet.from_data(block.encode())
        block_id = BlockID(block.hash(), parts.total, parts.hash)
        proposal = Proposal(height, round, rs.valid_round, block_id,
                            timestamp=time.time_ns())
        try:
            self.priv_validator.sign_proposal(self.state.chain_id, proposal)
        except (RecursionError, MemoryError):
            raise
        except Exception:
            return
        # WAL-then-process inline: we are already inside the receive loop
        # (the reference round-trips via internalMsgQueue; same ordering)
        mi = MsgInfo(ProposalMessage(proposal), "")
        self._wal_write_msgs([mi])
        self._set_proposal_safe(proposal)
        for i in range(parts.total):
            bpm = BlockPartMessage(height, round, parts.get_part(i))
            self._wal_write_msgs([MsgInfo(bpm, "")])
            self._add_proposal_block_part(bpm, "")
        if self.on_own_proposal is not None:
            self.on_own_proposal(proposal, parts)

    def _is_proposal_complete(self) -> bool:
        rs = self.rs
        if rs.proposal is None or rs.proposal_block is None:
            return False
        if rs.proposal.pol_round < 0:
            return True
        prevotes = rs.votes.prevotes(rs.proposal.pol_round)
        return prevotes is not None and prevotes.has_two_thirds_majority()

    @trace.traced("consensus.enter_prevote")
    def _enter_prevote(self, height: int, round: int) -> None:
        """state.go:1226."""
        rs = self.rs
        if rs.height != height or round < rs.round or \
                (rs.round == round and rs.step >= STEP_PREVOTE):
            return
        rs.round = round
        rs.step = STEP_PREVOTE
        timeline.record(height, "consensus.enter_prevote", round=round)
        _valstats.begin_step(height, round, "prevote")
        self._new_step()
        # sign and broadcast prevote (defaultDoPrevote :1252)
        if rs.locked_block is not None:
            self._sign_add_vote(PREVOTE, rs.locked_block.hash(),
                                rs.locked_block_parts)
        elif rs.proposal_block is None:
            self._sign_add_vote(PREVOTE, b"", None)
        else:
            try:
                self.block_exec.validate_block(self.state, rs.proposal_block)
                self._sign_add_vote(
                    PREVOTE, rs.proposal_block.hash(), rs.proposal_block_parts)
            except Exception:
                self._sign_add_vote(PREVOTE, b"", None)

    def _enter_prevote_wait(self, height: int, round: int) -> None:
        rs = self.rs
        if rs.height != height or round < rs.round or \
                (rs.round == round and rs.step >= STEP_PREVOTE_WAIT):
            return
        prevotes = rs.votes.prevotes(round)
        if prevotes is None or not prevotes.has_two_thirds_any():
            return
        rs.round = round
        rs.step = STEP_PREVOTE_WAIT
        self._new_step()
        self.ticker.schedule_timeout(TimeoutInfo(
            self.config.prevote_timeout(round), height, round,
            STEP_PREVOTE_WAIT))

    @trace.traced("consensus.enter_precommit")
    def _enter_precommit(self, height: int, round: int) -> None:
        """state.go:1322."""
        rs = self.rs
        if rs.height != height or round < rs.round or \
                (rs.round == round and rs.step >= STEP_PRECOMMIT):
            return
        rs.round = round
        rs.step = STEP_PRECOMMIT
        timeline.record(height, "consensus.enter_precommit", round=round)
        _valstats.begin_step(height, round, "precommit")
        self._new_step()
        prevotes = rs.votes.prevotes(round)
        block_id, has_polka = (prevotes.two_thirds_majority()
                               if prevotes else (BlockID(), False))
        if not has_polka:
            # no polka: precommit nil
            self._sign_add_vote(PRECOMMIT, b"", None)
            return
        if self.event_bus:
            self.event_bus.publish_polka(rs)
        # polka for nil: unlock
        if block_id.is_zero():
            if rs.locked_block is not None:
                rs.locked_round = -1
                rs.locked_block = None
                rs.locked_block_parts = None
                if self.event_bus:
                    self.event_bus.publish_lock(rs)
            self._sign_add_vote(PRECOMMIT, b"", None)
            return
        # polka for our locked block: re-lock at this round
        if rs.locked_block is not None and \
                rs.locked_block.hash() == block_id.hash:
            rs.locked_round = round
            if self.event_bus:
                self.event_bus.publish_lock(rs)
            self._sign_add_vote(PRECOMMIT, block_id.hash,
                                rs.locked_block_parts)
            return
        # polka for the proposal block: lock it
        if rs.proposal_block is not None and \
                rs.proposal_block.hash() == block_id.hash:
            try:
                self.block_exec.validate_block(self.state, rs.proposal_block)
            except Exception as e:
                raise RuntimeError(
                    f"precommit step: +2/3 prevoted an invalid block: {e}")
            rs.locked_round = round
            rs.locked_block = rs.proposal_block
            rs.locked_block_parts = rs.proposal_block_parts
            if self.event_bus:
                self.event_bus.publish_lock(rs)
            self._sign_add_vote(PRECOMMIT, block_id.hash,
                                rs.proposal_block_parts)
            return
        # polka for an unknown block: unlock, fetch it, precommit nil
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        if rs.proposal_block_parts is None or \
                not _parts_header_matches(rs.proposal_block_parts, block_id):
            rs.proposal_block = None
            rs.proposal_block_parts = PartSet(block_id.parts_total,
                                              block_id.parts_hash)
        if self.event_bus:
            self.event_bus.publish_lock(rs)
        self._sign_add_vote(PRECOMMIT, b"", None)

    def _enter_precommit_wait(self, height: int, round: int) -> None:
        rs = self.rs
        if rs.height != height or round != rs.round or \
                rs.triggered_timeout_precommit:
            return
        precommits = rs.votes.precommits(round)
        if precommits is None or not precommits.has_two_thirds_any():
            return
        rs.triggered_timeout_precommit = True
        self._new_step()
        self.ticker.schedule_timeout(TimeoutInfo(
            self.config.precommit_timeout(round), height, round,
            STEP_PRECOMMIT_WAIT))

    @trace.traced("consensus.enter_commit")
    def _enter_commit(self, height: int, commit_round: int) -> None:
        """state.go:1476."""
        rs = self.rs
        if rs.height != height or rs.step >= STEP_COMMIT:
            return
        rs.round = commit_round
        rs.step = STEP_COMMIT
        rs.commit_round = commit_round
        rs.commit_time = time.time_ns()
        timeline.record(height, "consensus.enter_commit",
                        round=commit_round)
        self._new_step()
        precommits = rs.votes.precommits(commit_round)
        block_id, ok = precommits.two_thirds_majority()
        if not ok:
            raise RuntimeError("enterCommit expects +2/3 precommits")
        # locked block == committed block? move it over
        if rs.locked_block is not None and \
                rs.locked_block.hash() == block_id.hash:
            rs.proposal_block = rs.locked_block
            rs.proposal_block_parts = rs.locked_block_parts
        if rs.proposal_block is None or \
                rs.proposal_block.hash() != block_id.hash:
            if rs.proposal_block_parts is None or \
                    not _parts_header_matches(rs.proposal_block_parts, block_id):
                rs.proposal_block = None
                rs.proposal_block_parts = PartSet(block_id.parts_total,
                                                  block_id.parts_hash)
            # tell peers which parts we actually hold (none, typically) so
            # their gossip serves us the committed block
            # (state.go:1521 PublishEventValidBlock -> NewValidBlockMessage)
            if self.event_bus:
                self.event_bus.publish_valid_block(rs)
            return  # wait for block parts
        self._try_finalize_commit(height)

    def _try_finalize_commit(self, height: int) -> None:
        rs = self.rs
        if rs.height != height or self._apply_inflight:
            return
        precommits = rs.votes.precommits(rs.commit_round)
        if precommits is None:
            return
        block_id, ok = precommits.two_thirds_majority()
        if not ok or block_id.is_zero():
            return
        if rs.proposal_block is None or \
                rs.proposal_block.hash() != block_id.hash:
            return
        self._finalize_commit(height)

    @trace.traced("consensus.finalize_commit")
    def _finalize_commit(self, height: int) -> None:
        """state.go:1567 — fail points mirror the reference's crash
        injection sites around commit (state.go:1605-1685)."""
        from tmtpu.libs import fail

        rs = self.rs
        if rs.height != height or rs.step != STEP_COMMIT or \
                self._apply_inflight:
            return
        precommits = rs.votes.precommits(rs.commit_round)
        block_id, _ = precommits.two_thirds_majority()
        block, parts = rs.proposal_block, rs.proposal_block_parts
        self.block_exec.validate_block(self.state, block)
        fail.fail_point("cs.finalize.pre_save_block")  # 0
        seen_commit = precommits.make_commit()
        if self.block_store.height() < block.header.height:
            self.block_store.save_block(block, parts, seen_commit)
        # 1: block saved, WAL has no ENDHEIGHT yet
        fail.fail_point("cs.finalize.post_save_block")
        if self.wal is not None:
            self.wal.write_end_height(height)
        # 2: ENDHEIGHT written, app not yet committed
        fail.fail_point("cs.finalize.post_endheight")
        # the commit checkpoint: block saved + ENDHEIGHT is the point the
        # tx is durably committed on this node (async apply still pending)
        txlat.stamp_height(height, "commit")
        trace.mark_height(height, "height.commit",
                          round=rs.commit_round, txs=len(block.txs))
        if self.config.async_exec and not self.replay_mode and \
                self.wal is not None:
            # async ApplyBlock overlap: the WAL's ENDHEIGHT is the commit
            # barrier (a crash anywhere past it replays block H through
            # the handshake, identical to a serial post_endheight crash),
            # so the ABCI execution can run on the executor thread while
            # THIS loop keeps draining next-height proposal/vote gossip.
            # rs stays parked at STEP_COMMIT for height H until the
            # done-message arrives — nothing signs, so nothing can
            # double-sign; finalize re-entry is fenced by _apply_inflight
            self._apply_inflight = True
            self._apply_started_s = time.monotonic()
            fail.fail_point("cs.finalize.async_handoff")

            def _done(result, error, _h=height, _blk=block):
                self.internal_msg_queue.put(MsgInfo(
                    ApplyBlockDoneMessage(_h, _blk, result, error), ""))

            self.block_exec.apply_block_async(self.state, block_id, block,
                                              _done)
            return
        new_state, retain_height = self.block_exec.apply_block(
            self.state, block_id, block)
        self._finalize_commit_tail(height, block, new_state, retain_height)

    def _finalize_commit_resume(self, m: ApplyBlockDoneMessage) -> None:
        """Second half of an async _finalize_commit, dispatched from the
        receive loop when the executor's done-message drains."""
        from tmtpu.libs import fail, metrics as _m

        if not self._apply_inflight or self.rs.height != m.height:
            return  # stale (e.g. duplicate after a test reset)
        self._apply_inflight = False
        fail.fail_point("cs.finalize.pre_resume")
        if m.error is not None:
            # same contract as a serial apply_block raise: consensus halts
            # (receive loop catches, syncs the WAL, exits)
            raise m.error
        _m.consensus_async_apply_overlap.observe(
            time.monotonic() - self._apply_started_s)
        new_state, retain_height = m.result
        self._finalize_commit_tail(m.height, m.block, new_state,
                                   retain_height)

    def _finalize_commit_tail(self, height: int, block, new_state,
                              retain_height: int) -> None:
        from tmtpu.libs import fail

        rs = self.rs
        fail.fail_point("cs.finalize.post_apply")  # 3: app committed
        if retain_height > 0:
            try:
                self.block_store.prune_blocks(retain_height)
            except Exception:
                pass
        self._record_metrics(block, rs.proposal_block_parts,
                             rs.commit_round, new_state)
        timeline.record(height, "consensus.finalize_commit",
                        round=rs.commit_round, txs=len(block.txs))
        # per-validator rollup, deferred ONE height: judge height-1 from
        # last_commit, which kept absorbing straggler precommits through
        # this height's commit wait (_try_add_votes). Judging the current
        # height's own vote set here would charge the unneeded-for-quorum
        # 4th..Nth precommits still in flight as misses and smear honest
        # validators (missed-vote counters + scorecard, libs/valstats).
        if rs.last_commit is not None:
            _valstats.finalize_height(rs.last_commit.height,
                                      rs.last_commit.round,
                                      rs.last_commit.val_set,
                                      rs.last_commit)
        self.update_to_state(new_state)
        self._schedule_round0()
        self._done_first_block.set()

    def _record_metrics(self, block, parts, commit_round: int,
                        new_state) -> None:
        """consensus/metrics.go:18 metric set, updated per commit."""
        from tmtpu.libs import metrics as m

        m.consensus_height.set(block.header.height)
        m.consensus_rounds.set(commit_round)
        m.consensus_num_txs.set(len(block.txs))
        m.consensus_total_txs.inc(len(block.txs))
        if parts is not None:  # avoid a second full block encode
            m.consensus_block_size.set(parts.byte_size())
        if new_state.validators is not None:
            m.consensus_validators.set(new_state.validators.size())
            m.consensus_validators_power.set(
                new_state.validators.total_voting_power())
        prev = getattr(self, "_last_commit_time_ns", 0)
        if prev:
            m.consensus_block_interval.observe(
                (block.header.time - prev) / 1e9)
        self._last_commit_time_ns = block.header.time

    def _new_step(self) -> None:
        if self.wal is not None:
            self.wal.write(self.wal.make(event_round_state=EventRoundStatePB(
                height=self.rs.height, round=self.rs.round,
                step=self.rs.step_name())))
        if self.event_bus:
            self.event_bus.publish_new_round_step(self.rs)

    # --------------------------------------------------------- proposals

    def _set_proposal_safe(self, proposal: Proposal) -> None:
        try:
            with trace.span("consensus.proposal", height=proposal.height):
                self._set_proposal(proposal)
        except VoteError:
            pass

    def _set_proposal(self, proposal: Proposal) -> None:
        """state.go defaultSetProposal (:1843)."""
        rs = self.rs
        if rs.proposal is not None:
            return
        if proposal.height != rs.height or proposal.round != rs.round:
            return
        if proposal.pol_round < -1 or \
                (proposal.pol_round >= 0 and
                 proposal.pol_round >= proposal.round):
            raise VoteError("error invalid proposal POL round")
        proposer = rs.validators.get_proposer()
        if not _crypto_batch.verify_one(
                proposer.pub_key,
                proposal.sign_bytes(self.state.chain_id), proposal.signature):
            raise VoteError("error invalid proposal signature")
        rs.proposal = proposal
        timeline.record(rs.height, timeline.EVENT_PROPOSAL_RECEIVED,
                        round=rs.round)
        _valstats.on_proposal(rs.height, rs.round, proposer.address)
        if rs.proposal_block_parts is None:
            rs.proposal_block_parts = PartSet(
                proposal.block_id.parts_total, proposal.block_id.parts_hash)

    def _flush_pending_parts(self) -> None:
        """Re-feed parts buffered before their header existed; called at
        the end of every receive cycle (any step in the cycle may have
        created rs.proposal_block_parts). Stale heights are dropped;
        still-unanchored parts re-buffer via _add_proposal_block_part."""
        if not self._pending_parts:
            return
        rs = self.rs
        pend = self._pending_parts
        self._pending_parts = {}
        for (h, _idx), msg in pend.items():
            if h != rs.height:
                continue
            if rs.proposal_block_parts is None:
                self._pending_parts[(h, _idx)] = msg  # keep waiting
            else:
                self._add_proposal_block_part(msg, "")

    def _add_proposal_block_part(self, msg: BlockPartMessage, peer_id: str
                                 ) -> None:
        """state.go:1890 addProposalBlockPart."""
        from tmtpu.types.block import Block

        rs = self.rs
        if msg.height != rs.height:
            return
        if rs.proposal_block_parts is None:
            # No parts header yet (no proposal seen / commit not entered):
            # we can't verify the part — but DON'T lose it. Gossip peers
            # mark parts delivered on send and never resend, so a part
            # arriving before its header (catchup to a just-restarted
            # node, out-of-order delivery) would otherwise be gone for
            # good and the commit wedges one part short. Buffer and
            # replay once the header is known.
            if len(self._pending_parts) < 128:
                self._pending_parts[(msg.height, msg.part.index)] = msg
            return
        # parts -> block: the proof of each part, then the whole decoded
        with trace.span("consensus.proposal", part=msg.part.index):
            try:
                added = rs.proposal_block_parts.add_part(msg.part)
            except ValueError:
                return
            if not added or not rs.proposal_block_parts.is_complete():
                return
            data = rs.proposal_block_parts.assemble()
            rs.proposal_block = Block.decode(data)
        # proposal checkpoint for every tx in the block — proposer and
        # followers both complete their parts through this path; the
        # noted hashes also serve the later height-keyed stamps
        # (quorums, commit, apply) without re-hashing the block
        txlat.note_block(msg.height, rs.proposal_block.txs)
        txlat.stamp_height(msg.height, "proposal")
        # per-node proposal-complete milestone on the height's root trace
        # (the causal chain's first on-node edge endpoint)
        trace.mark_height(msg.height, "height.proposal",
                          txs=len(rs.proposal_block.txs))
        if self.event_bus:
            self.event_bus.publish_complete_proposal(rs)
        prevotes = rs.votes.prevotes(rs.round)
        block_id, has_polka = (prevotes.two_thirds_majority()
                               if prevotes else (BlockID(), False))
        if has_polka and not block_id.is_zero() and rs.valid_round < rs.round:
            if rs.proposal_block.hash() == block_id.hash:
                rs.valid_round = rs.round
                rs.valid_block = rs.proposal_block
                rs.valid_block_parts = rs.proposal_block_parts
        if rs.step <= STEP_PROPOSE and self._is_proposal_complete():
            self._enter_prevote(rs.height, rs.round)
        elif rs.step == STEP_COMMIT:
            self._try_finalize_commit(rs.height)

    # ------------------------------------------------------------- votes

    def _sign_add_vote(self, vote_type: int, block_hash: bytes,
                       parts: Optional[PartSet]) -> None:
        """state.go:2227 signAddVote."""
        if self.priv_validator is None or self.replay_mode:
            return  # in replay, own votes come back through the WAL
        rs = self.rs
        if not rs.validators.has_address(self.priv_validator_pub_key.address()):
            return
        idx, _ = rs.validators.get_by_address(
            self.priv_validator_pub_key.address())
        # idempotent: if our vote for this (round, type) is already in the
        # set (e.g. re-driving after WAL replay), don't sign again
        vs = rs.votes.prevotes(rs.round) if vote_type == PREVOTE \
            else rs.votes.precommits(rs.round)
        if vs is not None and vs.get_by_index(idx) is not None:
            return
        misbehavior = self.misbehaviors.get(rs.height) \
            if self.misbehaviors else None
        if misbehavior == "absent-prevote" and vote_type == PREVOTE:
            return
        if block_hash:
            block_id = BlockID(block_hash, parts.total, parts.hash)
        else:
            block_id = BlockID()
        vote = Vote(
            type=vote_type, height=rs.height, round=rs.round,
            block_id=block_id, timestamp=self._vote_time(),
            validator_address=self.priv_validator_pub_key.address(),
            validator_index=idx,
        )
        try:
            self.priv_validator.sign_vote(self.state.chain_id, vote)
        except (RecursionError, MemoryError):
            raise  # never mask interpreter-level failures as "can't sign"
        except Exception:
            # transient failure (remote signer mid-reconnect): retry while
            # this round lasts — the reference just logs and loses the
            # vote, which permanently wedges any net where this validator
            # is pivotal. Idempotence above + the signer's HRS protection
            # make re-attempts safe; stale retries are dropped by the
            # height/round check in _handle_msgs. Capture height/round NOW
            # (default args): rs mutates in place, and a late-bound read
            # would stamp the old block onto a new round.
            threading.Timer(
                0.5,
                lambda h=rs.height, r=rs.round: self.internal_msg_queue.put(
                    MsgInfo(RetrySignMessage(h, r, vote_type, block_hash,
                                             parts), ""))).start()
            return
        mi = MsgInfo(VoteMessage(vote), "")
        self._wal_write_msgs([mi])
        self._try_add_votes([(vote, "")])
        if self.on_own_vote is not None:
            self.on_own_vote(vote)
        if (misbehavior == "double-prevote" and vote_type == PREVOTE
                and block_hash):
            # equivocate: also sign a conflicting nil prevote and gossip it
            # (reference maverick's double-prevote; the raw-key sign bypasses
            # our own HRS protection — byzantine by construction)
            from tmtpu.consensus import misbehavior as mb

            if getattr(self.priv_validator, "priv_key", None) is None:
                return  # remote signer: no raw key to equivocate with
            evil = Vote(
                type=vote_type, height=rs.height, round=rs.round,
                block_id=BlockID(), timestamp=vote.timestamp,
                validator_address=vote.validator_address,
                validator_index=idx,
            )
            mb.unsafe_sign_vote(self.priv_validator,
                                self.state.chain_id, evil)
            if self.on_own_vote is not None:
                self.on_own_vote(evil)
        if (misbehavior == "garbage-sig" and vote_type == PREVOTE
                and self.on_own_vote is not None):
            # invalid-signature spam: a burst of otherwise-plausible
            # votes whose 64-byte signatures are random noise, aimed at
            # honest nodes' batch-verify admission (sigcache/sidecar).
            # Distinct timestamps keep the lanes distinct through dedup.
            # No evidence can come of these — rejection is the test.
            from tmtpu.consensus.misbehavior import GARBAGE_SIG_BURST

            for i in range(GARBAGE_SIG_BURST):
                junk = Vote(
                    type=vote_type, height=rs.height, round=rs.round,
                    block_id=block_id, timestamp=vote.timestamp + 1 + i,
                    validator_address=vote.validator_address,
                    validator_index=idx,
                    signature=os.urandom(64),
                )
                self.on_own_vote(junk)

    def _vote_time(self) -> int:
        """state.go voteTime: monotonic over last block time."""
        now = time.time_ns()
        min_vote_time = self.state.last_block_time + 1 \
            if self.state.last_block_time else now
        return max(now, min_vote_time)

    def _try_add_votes(self, votes: List[Tuple[Vote, str]]) -> None:
        """tryAddVote (:1947) over a batch — one BatchVerifier dispatch a
        vote set."""
        rs = self.rs
        # late precommits for the previous height extend LastCommit
        current, last = [], []
        for v, peer in votes:
            if v.height + 1 == rs.height and v.type == PRECOMMIT:
                last.append(v)
            elif v.height == rs.height:
                current.append((v, peer))
            # other heights: ignore (reactor handles catchup)
        if len(current) + len(last) < len(votes):
            _m.consensus_votes_dropped.inc(
                len(votes) - len(current) - len(last), reason="height")
        if last:
            # state.go addVote's branch for vote.Height+1 == cs.Height:
            # only while the commit wait lasts, all of a drain's in one flush
            if rs.step == STEP_NEW_HEIGHT and rs.last_commit is not None:
                self._add_group(rs.last_commit.add_votes, last,
                                "late_precommit")
                if self.config.skip_timeout_commit and \
                        rs.last_commit.has_all():
                    self._schedule_round0()
            else:
                _m.consensus_votes_dropped.inc(len(last), reason="late")
        if not current:
            return
        # group by peer so the per-peer catchup-round budget in
        # HeightVoteSet is charged to the right peer
        by_peer = {}
        for v, peer in current:
            by_peer.setdefault(peer, []).append(v)
        for peer, group in by_peer.items():
            self._add_group(
                lambda g, _p=peer: rs.votes.add_votes(g, peer_id=_p), group)
        self._check_vote_transitions()

    def _add_group(self, add_votes, group: List[Vote],
                   counted_as: str = "") -> None:
        """One ``add_votes`` call and what tryAddVote does with its
        outcome: a conflicting pair goes to the evidence pool
        (state.go:1971; the batch was still processed, the per-vote added
        flags kept), each added vote is published and counted."""
        try:
            added_mask = add_votes(group)
        except ErrVoteConflictingVotes as e:
            if self.evidence_pool is not None:
                try:
                    self.evidence_pool.report_conflicting_votes(
                        e.vote_a, e.vote_b)
                except Exception:
                    pass
            added_mask = e.results or [False] * len(group)
        except VoteError:
            added_mask = [False] * len(group)
        n_added = {}
        with trace.span("consensus.publish", votes=len(group)):
            for v, added in zip(group, added_mask):
                if not added:
                    continue
                kind = counted_as or \
                    ("prevote" if v.type == PREVOTE else "precommit")
                n_added[kind] = n_added.get(kind, 0) + 1
                if self.event_bus:
                    self.event_bus.publish_vote(v)
        for kind, n in n_added.items():
            _m.consensus_votes_added.inc(n, type=kind)
        refused = len(group) - sum(n_added.values())
        if refused:
            _m.consensus_votes_dropped.inc(refused, reason="refused")

    def _check_vote_transitions(self) -> None:
        """The post-addVote step logic (state.go:2054-2160), run once per
        batch instead of per vote."""
        rs = self.rs
        height = rs.height
        # prevote-driven transitions
        for r in range(rs.round, rs.votes.round() + 1):
            prevotes = rs.votes.prevotes(r)
            if prevotes is None:
                continue
            block_id, has_polka = prevotes.two_thirds_majority()
            if has_polka:
                # unlock if polka at higher round than lock
                if rs.locked_block is not None and rs.locked_round < r and \
                        rs.locked_block.hash() != block_id.hash:
                    rs.locked_round = -1
                    rs.locked_block = None
                    rs.locked_block_parts = None
                    if self.event_bus:
                        self.event_bus.publish_lock(rs)
                if not block_id.is_zero() and rs.valid_round < r and \
                        r == rs.round:
                    if rs.proposal_block is not None and \
                            rs.proposal_block.hash() == block_id.hash:
                        rs.valid_round = r
                        rs.valid_block = rs.proposal_block
                        rs.valid_block_parts = rs.proposal_block_parts
                    elif rs.proposal_block_parts is None or not \
                            _parts_header_matches(rs.proposal_block_parts,
                                                  block_id):
                        rs.proposal_block = None
                        rs.proposal_block_parts = PartSet(
                            block_id.parts_total, block_id.parts_hash)
                    if self.event_bus:
                        self.event_bus.publish_valid_block(rs)
            if r == rs.round:
                if rs.step < STEP_PREVOTE and has_polka and \
                        not block_id.is_zero():
                    pass  # will prevote it when we get there
                if rs.step == STEP_PREVOTE:
                    # nil polka precommits IMMEDIATELY (state.go:2103
                    # `ok && (HashesTo(...) || blockID.IsZero())`) — without
                    # this a node replaying a peer's past rounds pays a
                    # prevote-wait timeout per round and can never catch up
                    if has_polka and (block_id.is_zero() or (
                            rs.proposal_block is not None and
                            rs.proposal_block.hash() == block_id.hash)):
                        self._enter_precommit(height, r)
                    elif prevotes.has_two_thirds_any():
                        self._enter_prevote_wait(height, r)
                if rs.step >= STEP_PREVOTE and has_polka and \
                        not block_id.is_zero() and rs.proposal is not None \
                        and rs.proposal.pol_round == r:
                    pass
            elif r > rs.round and prevotes.has_two_thirds_any():
                # skip to the round with 2/3 any
                self._enter_new_round(height, r)
        # precommit-driven transitions
        for r in range(rs.round, rs.votes.round() + 1):
            precommits = rs.votes.precommits(r)
            if precommits is None:
                continue
            block_id, has_maj = precommits.two_thirds_majority()
            if has_maj:
                if block_id.is_zero():
                    # 2/3 precommitted nil: the round is dead — go straight
                    # to the next one (state.go:2135), no precommit-wait
                    self._enter_new_round(height, r + 1)
                    continue
                self._enter_new_round(height, r)
                self._enter_precommit(height, r)
                self._enter_commit(height, r)
                if self.config.skip_timeout_commit and \
                        precommits.has_all():
                    self._schedule_round0()
            elif r >= rs.round and precommits.has_two_thirds_any():
                if r > rs.round:
                    self._enter_new_round(height, r)
                self._enter_precommit_wait(height, r)


def _parts_header_matches(parts: PartSet, block_id: BlockID) -> bool:
    return parts.total == block_id.parts_total and \
        parts.hash == block_id.parts_hash

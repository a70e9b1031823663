"""Consensus reactor (reference: consensus/reactor.go).

Four p2p channels (:142): State (0x20), Data (0x21), Vote (0x22),
VoteSetBits (0x23). Per-peer gossip threads (:199-201 — data and votes)
push what each peer is missing, tracked in a PeerState updated from
NewRoundStep/HasVote/VoteSetMaj23 messages; catchup feeds lagging peers
block parts + commit votes from the block store.
"""

from __future__ import annotations

import random
import threading
import time
import traceback
from typing import Dict, Optional

from tmtpu.consensus import msgs as cm
from tmtpu.consensus.state import ConsensusState
from tmtpu.consensus.types import (
    STEP_COMMIT, STEP_NEW_HEIGHT, STEP_PRECOMMIT, STEP_PREVOTE,
)
from tmtpu.libs import metrics as _metrics
from tmtpu.libs import trace as _trace
from tmtpu.libs.bits import BitArray
from tmtpu.p2p.conn.connection import ChannelDescriptor
from tmtpu.p2p.switch import Peer, Reactor
from tmtpu.types import pb
from tmtpu.types.block import BlockID
from tmtpu.types.part_set import Part
from tmtpu.types.vote import PRECOMMIT, PREVOTE, Proposal, Vote
from tmtpu.types.vote_set import commit_to_vote_set

STATE_CHANNEL = 0x20
DATA_CHANNEL = 0x21
VOTE_CHANNEL = 0x22
VOTE_SET_BITS_CHANNEL = 0x23

GOSSIP_SLEEP_S = 0.01  # peerGossipSleepDuration (100ms in ref; faster here)


def _encode_bits(ba: BitArray) -> bytes:
    """BitArray wire form for VoteSetBits: LE uint32 bit-count + packed
    64-bit words."""
    import struct

    return struct.pack("<I", ba.size()) + ba.words().tobytes()


def _decode_bits(data: bytes):
    import struct

    import numpy as np

    if len(data) < 4 or (len(data) - 4) % 8 != 0:
        return None
    (n,) = struct.unpack("<I", data[:4])
    words = np.frombuffer(data[4:], dtype=np.uint64)
    if n > len(words) * 64 or n > (1 << 24):
        return None
    return BitArray.from_words(n, words.copy())


class PeerState:
    """consensus/reactor.go PeerState — what we know the peer knows."""

    def __init__(self):
        self.height = 0
        self.round = -1
        self.step = 0
        self.proposal = False
        self.proposal_block_parts: Optional[BitArray] = None
        self.proposal_parts_total = 0
        self.prevotes: Dict[int, BitArray] = {}
        self.precommits: Dict[int, BitArray] = {}
        self.catchup_commit: Optional[BitArray] = None
        self.catchup_height = 0
        self.lock = threading.RLock()

    def apply_new_round_step(self, m: cm.NewRoundStepPB) -> None:
        with self.lock:
            if m.height != self.height or m.round != self.round:
                self.proposal = False
                self.proposal_block_parts = None
                self.proposal_parts_total = 0
            if m.height != self.height:
                self.prevotes.clear()
                self.precommits.clear()
                self.catchup_commit = None
                self.catchup_height = 0
            self.height = m.height
            self.round = m.round
            self.step = m.step

    def vote_bits(self, round: int, vote_type: int, n: int) -> BitArray:
        with self.lock:
            table = self.prevotes if vote_type == PREVOTE else self.precommits
            ba = table.get(round)
            if ba is None:
                ba = BitArray(n)
                table[round] = ba
            elif ba.size() != n:
                # resize keeping surviving marks — a HasVote that arrived
                # before we knew the validator count must not be forgotten
                grown = BitArray(n)
                for i in ba.true_indices():
                    if i < n:
                        grown.set_index(i, True)
                table[round] = ba = grown
            return ba

    def set_has_vote(self, height: int, round: int, vote_type: int,
                     index: int, n: int = 0) -> None:
        with self.lock:
            if height != self.height:
                if height == self.catchup_height and \
                        self.catchup_commit is not None:
                    self.catchup_commit.set_index(index, True)
                return
            table = self.prevotes if vote_type == PREVOTE else self.precommits
            ba = table.get(round)
            if ba is None:
                ba = BitArray(max(n, index + 1))
                table[round] = ba
            if index >= ba.size():
                grown = BitArray(index + 1)
                for i in ba.true_indices():
                    grown.set_index(i, True)
                table[round] = ba = grown
            ba.set_index(index, True)

    def apply_new_valid_block(self, height: int, round: int, total: int,
                              bits: BitArray, is_commit: bool) -> None:
        """reactor.go ApplyNewValidBlockMessage — the peer's OWN statement
        of which parts it holds; overwrites our optimistic send marks."""
        with self.lock:
            if height != self.height:
                return
            if round != self.round and not is_commit:
                return
            if bits.size() != total:
                return
            self.proposal_parts_total = total
            self.proposal_block_parts = bits

    def set_has_part(self, height: int, index: int, total: int) -> None:
        with self.lock:
            if height != self.height:
                return
            if self.proposal_block_parts is None or \
                    self.proposal_parts_total != total:
                self.proposal_block_parts = BitArray(total)
                self.proposal_parts_total = total
            self.proposal_block_parts.set_index(index, True)

    def ensure_catchup(self, height: int, n_vals: int) -> BitArray:
        with self.lock:
            if self.catchup_height != height or self.catchup_commit is None:
                self.catchup_commit = BitArray(n_vals)
                self.catchup_height = height
            return self.catchup_commit


class ConsensusReactor(Reactor):
    def __init__(self, cs: ConsensusState, wait_sync: bool = False):
        super().__init__("CONSENSUS")
        self.cs = cs
        self.wait_sync = wait_sync  # true while block sync is running
        # idle-poll pace of the gossip routines, from config so big-net
        # profiles can slow it (the send path never sleeps, so this only
        # trades idle-wakeup CPU against worst-case relay latency)
        self.gossip_sleep_s = getattr(
            cs.config, "gossip_sleep_ns", int(GOSSIP_SLEEP_S * 1e9)) / 1e9
        self._peer_threads: Dict[str, list] = {}
        self._stopped = threading.Event()
        # the vote channel's decoder: it keeps the heads (type, height,
        # round, block id) that the votes of a step share
        self._vote_decoder = cm.VoteDecoder()
        self._count_hand = _metrics.consensus_vote_decode.bound(path="hand")
        self._count_reflective = _metrics.consensus_vote_decode.bound(
            path="reflective")
        # outbound hooks from the state machine
        cs.on_own_vote = self._broadcast_own_vote
        cs.on_own_proposal = self._broadcast_own_proposal
        # step-change broadcast
        if cs.event_bus is not None:
            self._step_sub = cs.event_bus.subscribe_type(
                "reactor-steps", "NewRoundStep")
            # every ADDED vote (not just our own) is announced as HasVote
            # so peers skip re-gossiping it to us (reactor.go:390
            # broadcastHasVoteMessage on the state's Vote event)
            self._vote_sub = cs.event_bus.subscribe_type(
                "reactor-hasvote", "Vote")
            # valid-block / commit-entry announcements carry the parts
            # header + our ACTUAL parts bitarray, overwriting peers' stale
            # optimistic marks (reactor.go:364 broadcastNewValidBlock)
            self._valid_sub = cs.event_bus.subscribe_type(
                "reactor-validblock", "ValidBlock")
        else:
            self._step_sub = None
            self._vote_sub = None
            self._valid_sub = None

    # -- reactor interface --------------------------------------------------

    def get_channels(self):
        return [
            ChannelDescriptor(STATE_CHANNEL, priority=6,
                              send_queue_capacity=100),
            ChannelDescriptor(DATA_CHANNEL, priority=10,
                              send_queue_capacity=100),
            ChannelDescriptor(VOTE_CHANNEL, priority=7,
                              send_queue_capacity=100),
            ChannelDescriptor(VOTE_SET_BITS_CHANNEL, priority=1,
                              send_queue_capacity=2),
        ]

    def on_start(self) -> None:
        if self._step_sub is not None:
            t = threading.Thread(target=self._step_broadcast_routine,
                                 daemon=True, name="cs-step-bcast")
            t.start()
        if self._vote_sub is not None:
            t = threading.Thread(target=self._has_vote_broadcast_routine,
                                 daemon=True, name="cs-hasvote-bcast")
            t.start()
        if self._valid_sub is not None:
            t = threading.Thread(target=self._valid_block_broadcast_routine,
                                 daemon=True, name="cs-validblock-bcast")
            t.start()

    def on_stop(self) -> None:
        self._stopped.set()

    def switch_to_consensus(self, state, skip_wal: bool = False) -> None:
        """blockchain reactor hands over after catchup
        (consensus/reactor.go:108 SwitchToConsensus). skip_wal: blocks were
        sync'd past the WAL's heights, so WAL catchup must not run — the
        stale records are for heights consensus already moved past (and a
        restarted validator's WAL can even hold an #ENDHEIGHT for the new
        starting height, which catchup treats as corruption)."""
        self.wait_sync = False
        self.cs.update_to_state(state)
        if skip_wal:
            self.cs.do_wal_catchup = False
        try:
            self.cs.start()
        except Exception:
            # surface the failure — this runs on the blocksync pool thread,
            # and a silent death here wedges the whole node (state.go would
            # panic); consensus not running IS fatal
            traceback.print_exc()
            raise
        # peers heard nothing from us while we were syncing (see add_peer);
        # tell them where we actually are so vote/data gossip starts
        if self.switch is not None:
            self.switch.broadcast(STATE_CHANNEL,
                                  self._new_round_step_msg().encode())

    def init_peer(self, peer: Peer) -> None:
        # before the conn delivers: receive() needs this immediately
        peer.set("consensus_peer_state", PeerState())

    def add_peer(self, peer: Peer) -> None:
        ps = peer.get("consensus_peer_state")
        if ps is None:  # switch without init_peer support (tests)
            ps = PeerState()
            peer.set("consensus_peer_state", ps)
        # announce our current state (reactor.go AddPeer sendNewRoundStep)
        # — but NOT while block/state sync runs (reactor.go:197
        # `if !conR.WaitSync()`): advertising a live round while the
        # wait_sync guard still DROPS incoming votes makes peers gossip
        # votes to us, optimistically mark them delivered in their
        # PeerState, and never resend them after we switch — a permanent
        # vote-gossip wedge (observed: restarted validator stuck one vote
        # short of every polka)
        if not self.wait_sync:
            peer.send(STATE_CHANNEL, self._new_round_step_msg().encode())
        threads = []
        for fn, name in ((self._gossip_data_routine, "gossip-data"),
                         (self._gossip_votes_routine, "gossip-votes"),
                         (self._query_maj23_routine, "query-maj23")):
            t = threading.Thread(target=fn, args=(peer, ps), daemon=True,
                                 name=f"{name}-{peer.node_id[:8]}")
            t.start()
            threads.append(t)
        self._peer_threads[peer.node_id] = threads

    def remove_peer(self, peer: Peer, reason) -> None:
        self._peer_threads.pop(peer.node_id, None)

    def _wire_ctx(self, height: int) -> bytes:
        """Encoded trace context for an outbound envelope of ``height``
        (b"" when the height is unsampled — field stays absent)."""
        raw = _trace.wire_context(height)
        if raw:
            _metrics.trace_context_tx.inc(transport="gossip")
        return raw

    @staticmethod
    def _rx_ctx(m: "cm.ConsensusMessagePB"):
        """Adopt the envelope's piggybacked context; garbage decodes to
        None (untraced) and is counted, never raised."""
        raw = bytes(m.trace_ctx) if m.trace_ctx else b""
        if not raw:
            return None
        ctx = _trace.adopt(raw)
        if ctx is None:
            _metrics.trace_context_invalid.inc(transport="gossip")
        else:
            _metrics.trace_context_rx.inc(transport="gossip")
        return ctx

    @staticmethod
    def _peer_state(peer: Peer) -> PeerState:
        ps: Optional[PeerState] = peer.get("consensus_peer_state")
        if ps is None:
            # never drop: a lost one-shot NewRoundStep wedges vote gossip
            ps = PeerState()
            peer.set("consensus_peer_state", ps)
        return ps

    def _vote_received(self, ps: PeerState, peer: Peer, vote: Vote):
        vals = self.cs.round_state_nolock().validators
        n = vals.size() if vals else 0
        ps.set_has_vote(vote.height, vote.round, vote.type,
                        vote.validator_index, n)
        return self.cs.add_vote_msg, vote, peer.node_id

    def receive(self, channel_id: int, peer: Peer, msg_bytes: bytes) -> None:
        # decode and PeerState under the span; the hand-over to the state
        # machine after it, because a full queue blocks there and that wait
        # is the consensus thread's work, not this one's
        with _trace.span("consensus.receive"):
            enqueue = self._receive(channel_id, peer, msg_bytes)
        if enqueue is not None:
            enqueue[0](*enqueue[1:])

    def _receive(self, channel_id: int, peer: Peer, msg_bytes: bytes):
        """-> what to hand the state machine, as (method, *args), or None."""
        if channel_id == VOTE_CHANNEL:
            # a canonical vote message is decoded by hand; any other shape
            # is "not mine" and takes the reflective path below
            vote = self._vote_decoder.decode(msg_bytes)
            if vote is not None:
                self._count_hand()
                ps = self._peer_state(peer)
                if self.wait_sync:
                    return
                return self._vote_received(ps, peer, vote)
            self._count_reflective()
        m = cm.ConsensusMessagePB.decode(msg_bytes)
        ps = self._peer_state(peer)
        kind = m.which()
        if channel_id == STATE_CHANNEL:
            if kind == "new_round_step":
                ps.apply_new_round_step(m.new_round_step)
            elif kind == "new_valid_block":
                nv = m.new_valid_block
                bits = _decode_bits(bytes(nv.block_parts))
                if bits is not None:
                    ps.apply_new_valid_block(
                        nv.height, nv.round,
                        nv.block_part_set_header.total, bits, nv.is_commit)
            elif kind == "has_vote":
                hv = m.has_vote
                vals = self.cs.round_state_nolock().validators
                n = vals.size() if vals else 0
                # n sizes the BitArray correctly up front — a default-sized
                # (index+1) array would be discarded by the gossip loop's
                # vote_bits(round, type, n) size check, losing the mark
                ps.set_has_vote(hv.height, hv.round, hv.type, hv.index, n)
            elif kind == "vote_set_maj23":
                vm = m.vote_set_maj23
                rs = self.cs.round_state_nolock()
                if rs.height == vm.height and rs.votes is not None:
                    try:
                        rs.votes.set_peer_maj23(
                            vm.round, vm.type, peer.node_id,
                            BlockID.from_proto(vm.block_id))
                    except Exception:
                        pass
                    # respond with OUR votes for that set so the peer can
                    # reconcile its PeerState (reactor.go:310-330)
                    vs = rs.votes.votes(vm.round, vm.type)
                    if vs is not None:
                        ours = vs.bit_array_by_block_id(
                            BlockID.from_proto(vm.block_id)) \
                            or BitArray(vs.size())
                        peer.try_send(
                            VOTE_SET_BITS_CHANNEL, cm.ConsensusMessagePB(
                                vote_set_bits=cm.VoteSetBitsPB(
                                    height=vm.height, round=vm.round,
                                    type=vm.type, block_id=vm.block_id,
                                    votes=_encode_bits(ours))).encode())
        elif channel_id == DATA_CHANNEL:
            if self.wait_sync:
                return
            if kind == "proposal":
                prop = Proposal.from_proto(m.proposal.proposal)
                ctx = self._rx_ctx(m)
                if ctx is not None:
                    _trace.mark("gossip.proposal_rx", ctx=ctx,
                                height=prop.height, peer=peer.node_id)
                with ps.lock:
                    ps.proposal = True
                return self.cs.add_proposal, prop, peer.node_id
            elif kind == "block_part":
                bp = m.block_part
                part = Part.from_proto(bp.part)
                ctx = self._rx_ctx(m)
                if ctx is not None:
                    _trace.mark("gossip.block_part_rx", ctx=ctx,
                                height=bp.height, index=part.index,
                                peer=peer.node_id)
                ps.set_has_part(bp.height, part.index, part.proof.total)
                return (self.cs.add_block_part, bp.height, bp.round, part,
                        peer.node_id)
        elif channel_id == VOTE_CHANNEL:
            if self.wait_sync:
                return
            if kind == "vote":
                vote = Vote.from_proto(m.vote.vote)
                ctx = self._rx_ctx(m)
                if ctx is not None:
                    _trace.mark("gossip.vote_rx", ctx=ctx,
                                height=vote.height, type=vote.type,
                                peer=peer.node_id)
                return self._vote_received(ps, peer, vote)
        elif channel_id == VOTE_SET_BITS_CHANNEL:
            if kind == "vote_set_bits":
                vb = m.vote_set_bits
                rs = self.cs.round_state_nolock()
                vals = rs.validators
                if rs.height != vb.height or vals is None:
                    return
                n = vals.size()
                bits = _decode_bits(bytes(vb.votes))
                if bits is None or bits.size() != n:
                    return  # size is OUR valset's, never peer-controlled
                # reactor.go ApplyVoteSetBitsMessage: where WE hold the vote
                # (could resend it), the peer's reply is authoritative —
                # clearing stale optimistic marks; outside our own set we
                # keep whatever we knew
                vs = rs.votes.votes(vb.round, vb.type) if rs.votes else None
                ours = vs.bit_array_by_block_id(
                    BlockID.from_proto(vb.block_id)) if vs else None
                with ps.lock:
                    known = ps.vote_bits(vb.round, vb.type, n)
                    if ours is None:
                        known.update(bits)
                    else:
                        known.update(known.sub(ours).or_(bits))

    # -- outbound -----------------------------------------------------------

    def _new_round_step_msg(self) -> cm.ConsensusMessagePB:
        rs = self.cs.round_state_nolock()
        lc_round = -1
        lc = rs.last_commit
        if lc is not None:
            lc_round = lc.round
        return cm.ConsensusMessagePB(new_round_step=cm.NewRoundStepPB(
            height=rs.height, round=rs.round, step=rs.step,
            seconds_since_start_time=max(
                0, (time.time_ns() - rs.start_time) // 10**9),
            last_commit_round=lc_round,
        ))

    def _step_broadcast_routine(self) -> None:
        while not self._stopped.is_set():
            item = self._step_sub.next(timeout=0.2)
            if item is None:
                continue
            if self.switch is not None:
                self.switch.broadcast(STATE_CHANNEL,
                                      self._new_round_step_msg().encode())

    def _has_vote_broadcast_routine(self) -> None:
        while not self._stopped.is_set():
            item = self._vote_sub.next(timeout=0.2)
            if item is None:
                continue
            vote = item.data.get("vote")
            if vote is None or self.switch is None:
                continue
            self.switch.broadcast(STATE_CHANNEL, cm.ConsensusMessagePB(
                has_vote=cm.HasVotePB(
                    height=vote.height, round=vote.round, type=vote.type,
                    index=vote.validator_index)).encode())

    def _valid_block_broadcast_routine(self) -> None:
        while not self._stopped.is_set():
            item = self._valid_sub.next(timeout=0.2)
            if item is None or self.switch is None:
                continue
            rs = self.cs.round_state_nolock()
            parts = rs.proposal_block_parts
            if parts is None:
                continue
            self.switch.broadcast(STATE_CHANNEL, cm.ConsensusMessagePB(
                new_valid_block=cm.NewValidBlockPB(
                    height=rs.height, round=rs.round,
                    block_part_set_header=pb.PartSetHeader(
                        total=parts.total, hash=parts.hash),
                    block_parts=_encode_bits(parts.bit_array()),
                    is_commit=rs.step >= STEP_COMMIT)).encode())

    def _broadcast_own_vote(self, vote: Vote) -> None:
        if self.switch is None:
            return
        ctx = self._wire_ctx(vote.height)
        if ctx:
            _trace.mark_height(vote.height, "gossip.vote_tx",
                               type=vote.type)
        msg = cm.ConsensusMessagePB(vote=cm.VotePB(vote=vote.to_proto()),
                                    trace_ctx=ctx)
        self.switch.broadcast(VOTE_CHANNEL, msg.encode())
        # HasVote announcement rides the event-driven
        # _has_vote_broadcast_routine (adding the vote published a Vote
        # event), matching reactor.go's single broadcastHasVoteMessage

    def _broadcast_own_proposal(self, proposal: Proposal, parts) -> None:
        if self.switch is None:
            return
        ctx = self._wire_ctx(proposal.height)
        if ctx:
            _trace.mark_height(proposal.height, "gossip.proposal_tx",
                               parts=parts.total)
        self.switch.broadcast(DATA_CHANNEL, cm.ConsensusMessagePB(
            proposal=cm.ProposalPB(proposal=proposal.to_proto()),
            trace_ctx=ctx).encode())
        for i in range(parts.total):
            self.switch.broadcast(DATA_CHANNEL, cm.ConsensusMessagePB(
                block_part=cm.BlockPartPB(
                    height=proposal.height, round=proposal.round,
                    part=parts.get_part(i).to_proto()),
                trace_ctx=ctx).encode())

    # -- gossip routines (reactor.go:559 gossipDataRoutine, :716
    # gossipVotesRoutine) ---------------------------------------------------

    def _gossip_data_routine(self, peer: Peer, ps: PeerState) -> None:
        while peer.is_running() and not self._stopped.is_set():
            rs = self.cs.round_state_nolock()
            with ps.lock:
                prs_h, prs_r = ps.height, ps.round
                has_proposal = ps.proposal
                peer_parts = ps.proposal_block_parts
            if prs_h == 0:
                time.sleep(self.gossip_sleep_s)
                continue
            # catchup: peer is on an older height -> send stored block parts
            if 0 < prs_h < rs.height and \
                    prs_h >= self.cs.block_store.base():
                self._gossip_catchup_part(peer, ps, prs_h)
                time.sleep(self.gossip_sleep_s)
                continue
            if prs_h != rs.height:
                time.sleep(self.gossip_sleep_s)
                continue
            # same height: proposal + parts. Local refs throughout: the
            # consensus thread may null these fields while we work (the
            # RoundState snapshot is shallow)
            proposal = rs.proposal
            if proposal is not None and not has_proposal:
                ctx = self._wire_ctx(proposal.height)
                if ctx:
                    # the data routine can beat _broadcast_own_proposal
                    # to the wire (the state machine WAL-writes and adds
                    # its own parts first) — stamp every departure so
                    # the causal tx anchor is the EARLIEST send, not the
                    # own-broadcast hook
                    _trace.mark_height(proposal.height,
                                       "gossip.proposal_tx",
                                       peer=peer.node_id)
                peer.try_send(DATA_CHANNEL, cm.ConsensusMessagePB(
                    proposal=cm.ProposalPB(
                        proposal=proposal.to_proto()),
                    trace_ctx=ctx).encode())
                with ps.lock:
                    ps.proposal = True
            parts = rs.proposal_block_parts
            if parts is not None:
                ours = parts.bit_array()
                total = parts.total
                theirs = peer_parts if peer_parts is not None and \
                    peer_parts.size() == total else BitArray(total)
                missing = ours.sub(theirs)
                idx = missing.pick_random()
                if idx is not None:
                    part = parts.get_part(idx)
                    if part is not None and peer.try_send(
                            DATA_CHANNEL, cm.ConsensusMessagePB(
                                block_part=cm.BlockPartPB(
                                    height=rs.height, round=rs.round,
                                    part=part.to_proto()),
                                trace_ctx=self._wire_ctx(
                                    rs.height)).encode()):
                        ps.set_has_part(rs.height, idx, total)
                        continue  # keep pushing without sleeping
            time.sleep(self.gossip_sleep_s)

    def _gossip_catchup_part(self, peer: Peer, ps: PeerState,
                             height: int) -> None:
        meta = self.cs.block_store.load_block_meta(height)
        if meta is None:
            return
        total = meta.block_id.parts_total
        with ps.lock:
            theirs = ps.proposal_block_parts if \
                ps.proposal_block_parts is not None and \
                ps.proposal_block_parts.size() == total else BitArray(total)
        missing = theirs.not_()
        idx = missing.pick_random()
        if idx is None:
            return
        part = self.cs.block_store.load_block_part(height, idx)
        if part is None:
            return
        if peer.try_send(DATA_CHANNEL, cm.ConsensusMessagePB(
                block_part=cm.BlockPartPB(
                    height=height, round=0,
                    part=part.to_proto())).encode()):
            ps.set_has_part(height, idx, total)

    def _gossip_votes_routine(self, peer: Peer, ps: PeerState) -> None:
        while peer.is_running() and not self._stopped.is_set():
            rs = self.cs.round_state_nolock()
            with ps.lock:
                prs_h, prs_r = ps.height, ps.round
            sent = False
            if prs_h == rs.height and rs.votes is not None:
                # current-round prevotes then precommits
                for vote_type in (PREVOTE, PRECOMMIT):
                    vs = rs.votes.votes(prs_r, vote_type) if prs_r >= 0 \
                        else None
                    if vs is None:
                        continue
                    theirs = ps.vote_bits(prs_r, vote_type, vs.size())
                    missing = vs.bit_array().sub(theirs)
                    idx = missing.pick_random()
                    if idx is not None:
                        vote = vs.get_by_index(idx)
                        if vote is not None and self._send_vote(peer, ps,
                                                                vote):
                            sent = True
                            break
                # last commit for peers entering the height
                if not sent and rs.last_commit is not None and \
                        prs_h >= 1 and rs.votes is not None:
                    pass
            elif 0 < prs_h < rs.height and \
                    prs_h >= self.cs.block_store.base():
                # catchup votes: precommits from the stored seen commit
                commit = self.cs.block_store.load_seen_commit(prs_h) or \
                    self.cs.block_store.load_block_commit(prs_h)
                if commit is not None:
                    n = len(commit.signatures)
                    theirs = ps.ensure_catchup(prs_h, n)
                    for i, csig in enumerate(commit.signatures):
                        if csig.is_absent() or theirs.get_index(i):
                            continue
                        vote = Vote(
                            type=PRECOMMIT, height=commit.height,
                            round=commit.round,
                            block_id=csig.block_id(commit.block_id),
                            timestamp=csig.timestamp,
                            validator_address=csig.validator_address,
                            validator_index=i, signature=csig.signature)
                        if peer.try_send(VOTE_CHANNEL, cm.ConsensusMessagePB(
                                vote=cm.VotePB(
                                    vote=vote.to_proto())).encode()):
                            theirs.set_index(i, True)
                            sent = True
                        break
            if not sent:
                time.sleep(self.gossip_sleep_s)

    QUERY_MAJ23_SLEEP_S = 2.0  # reactor.go:849 queryMaj23Routine cadence

    def _query_maj23_routine(self, peer: Peer, ps: PeerState) -> None:
        """Periodically tell the peer about 2/3-majorities we've seen so it
        replies with its actual vote bits (VoteSetBits) — the reconciliation
        path that heals any divergence between a peer's real vote set and
        our optimistic PeerState bookkeeping."""
        while peer.is_running() and not self._stopped.is_set():
            time.sleep(self.QUERY_MAJ23_SLEEP_S)
            rs = self.cs.round_state_nolock()
            with ps.lock:
                prs_h, prs_r = ps.height, ps.round
            if prs_h != rs.height or rs.votes is None or prs_r < 0:
                continue
            for vote_type in (PREVOTE, PRECOMMIT):
                vs = rs.votes.votes(prs_r, vote_type)
                if vs is None:
                    continue
                block_id, has_maj = vs.two_thirds_majority()
                if not has_maj:
                    continue
                peer.try_send(STATE_CHANNEL, cm.ConsensusMessagePB(
                    vote_set_maj23=cm.VoteSetMaj23PB(
                        height=rs.height, round=prs_r, type=vote_type,
                        block_id=block_id.to_proto())).encode())

    def _send_vote(self, peer: Peer, ps: PeerState, vote: Vote) -> bool:
        ok = peer.try_send(VOTE_CHANNEL, cm.ConsensusMessagePB(
            vote=cm.VotePB(vote=vote.to_proto()),
            trace_ctx=self._wire_ctx(vote.height)).encode())
        if ok:
            ps.set_has_vote(vote.height, vote.round, vote.type,
                            vote.validator_index)
        return ok

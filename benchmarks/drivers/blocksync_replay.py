"""Driver ``blocksync_replay``: a node that joins a chain late and catches
up through blocksync v0, in the process that holds the chip.

It builds what ``node/node.py`` builds for such a node — ``BlocksyncReactor``
(v0), ``BlockExecutor``, ``BlockStore`` and ``StateStore`` on the shipped
``db_backend`` (SQLite files under a temporary home), the kvstore app behind
``proxy.AppConns``, mempool, evidence pool and event bus — starts the
reactor in fast-sync mode, so that its own ``_pool_routine`` thread runs the
loop, and stands in for the network with in-process peers: a peer's
``try_send`` answers ``StatusRequest`` and ``BlockRequest`` at once, with
wire bytes made before the window (reference/blocks.py), through
``reactor.receive``. The driver calls no verify entry and no step of the
loop itself; it listens on the event bus (a subscription's predicate runs on
the publishing thread, so a NewBlock is seen the instant it is applied).

The window (the rule of benchmarks/README.md): opens at the first block
applied by a new run after ``warm_blocks`` blocks, closes at the first such
boundary after ``--seconds``. ``verify_sigs_per_s`` = the for-block
signatures of the LastCommits that verified the blocks applied between (167
a block here, each once; the two cache-hit re-verifications a block are not
counted) over the time between. If the served tip is reached first the
window closes there and a check fails. ``setup_s`` runs to the window's
opening: fabrication, the node's start and the run shape's warm-up
included. With ``--trace 1`` the window is ``trace_seconds`` long and all
of it is profiled: the profiler starts and stops on the pool thread at the
two boundaries, so spans, device operations and counters cover the same
runs. No block applied for ``stall_seconds`` ends the run with exit code
3, never a hang.

``correct`` (exact counts, limit 0): every block applied in the window has
the reference's hash at its height and the store holds it with its
SeenCommit; nothing compiled; no forbidden fallback lane and every dispatch
on ``tpu/pallas``. Then, the window closed and the pool drained, a tail of
the chain is served with three faults, each in a run of its own and from a
peer of its own — a signature tampered in one commit, a commit whose
absences leave too little power, a block whose successor's LastCommit names
another block id: the heights applied, the height refused, the reason and
the peers punished have to equal the reference's, the good copy from the
first peer is applied after each, and the counters hold those runs' lanes
to the device. At the end the height, the app hash and ``readback`` keys
drawn from the seed (read through the app's ``query``) equal the
reference's.
"""

from __future__ import annotations

import gc
import os
import random
import shutil
import sys
import tempfile
import threading
import time
import traceback

from benchmarks.lib import devtrace, gates, readers, tracered
from benchmarks.lib.report import Checks
from benchmarks.lib.result import RunResult
from benchmarks.reference import blocks as rb
from benchmarks.reference import commits as rc

FAULT_BLOCKS = 4        # blocks a lying peer serves: 2 good, the refused, its successor
FAULTS = ("tampered", "starved", "wrong_id")
REASONS = (("wrong signature", rb.BAD_SIGNATURE),
           ("insufficient voting power", rb.LOW_POWER),
           ("wrong block ID", rb.WRONG_BLOCK_ID))


class Stalled(SystemExit):
    def __init__(self, what: str):
        print(f"blocksync_replay: {what}; giving up", file=sys.stderr,
              flush=True)
        super().__init__(3)


class Net:
    """Stands in for ``p2p.Switch`` as far as the reactor uses it: the
    peers by id, a broadcast, and the punishment of a peer."""

    def __init__(self, reactor, watch):
        self.peers = {}
        self.reactor = reactor
        self.watch = watch
        self.punished = []      # (peer id, reason, height applied by then)

    def add(self, peer) -> None:
        self.peers[peer.node_id] = peer
        self.reactor.add_peer(peer)

    def broadcast(self, channel_id: int, msg: bytes) -> None:
        for p in list(self.peers.values()):
            p.try_send(channel_id, msg)

    def stop_peer_for_error(self, peer, reason) -> None:
        if self.peers.pop(peer.node_id, None) is None:
            return
        self.punished.append((peer.node_id, str(reason), self.watch.height))
        self.reactor.remove_peer(peer, reason)


class ServingPeer:
    """One peer of the node: it holds blocks by height as wire bytes and
    answers a request on the requester's own thread, at once."""

    def __init__(self, node_id: str, reactor, channel: int, compiles=None):
        from tmtpu.blocksync.msgs import BlocksyncMessagePB

        self.decode = BlocksyncMessagePB.decode
        self.node_id = node_id
        self.reactor = reactor
        self.channel = channel
        self.serve = {}             # height -> BlockResponse bytes
        self.base, self.height = 1, 0
        self.max_served = 0
        self.compiles = compiles
        self.first_request = None   # (t, compilations so far)

    def announce(self, base: int, height: int) -> None:
        """A StatusResponse, as a peer sends when it connects or grows."""
        self.base, self.height = base, height
        self.reactor.receive(self.channel, self,
                             rb.status_response(base, height))

    def send(self, channel_id: int, msg: bytes) -> bool:
        return True                 # the node's own status: nothing to do

    def try_send(self, channel_id: int, msg: bytes) -> bool:
        if self.first_request is None and self.compiles is not None:
            self.first_request = (time.perf_counter(), self.compiles.n)
        m = self.decode(msg)
        if m.status_request is not None:
            self.announce(self.base, self.height)
        elif m.block_request is not None:
            # whatever it holds: a request handed out before it announced
            # a lower height must not be left to time out
            h = m.block_request.height
            if h in self.serve:
                self.max_served = max(self.max_served, h)
                self.reactor.receive(self.channel, self, self.serve[h])
        return True


class Watch:
    """The driver's eyes on the node: a predicate on the event bus, run by
    the pool thread as each NewBlock is published. It marks run boundaries
    (the run counter moved since the last block) and opens and closes the
    window there; everything it keeps is read by the main thread after."""

    def __init__(self, ctx, mix, prog_metrics, compiles, seconds):
        self.ctx, self.prog_metrics, self.compiles = ctx, prog_metrics, compiles
        self.seconds = seconds
        self.warm_blocks = int(mix["warm_blocks"])
        self.applied = []           # (height, block hash, t)
        self.height = 0
        self.last_t = time.perf_counter()
        self.runs_seen = 0
        self.armed = False
        self.open = self.close = None       # (t, height, registry, compiles)
        self.closed = threading.Event()
        self.tracer = None
        self.trace = None
        self.reducing = False       # the pool thread is reading the trace
        self.error = None           # the bus swallows what a predicate raises

    def _runs(self) -> int:
        series = self.prog_metrics.blocksync_run_blocks.summary_series()
        return sum(v["count"] for v in series.values())

    def _edge(self):
        return (time.perf_counter(), self.height,
                self.prog_metrics.summary(), self.compiles.n)

    def __call__(self, item) -> bool:
        if item.type != "NewBlock":
            return False
        try:
            self._on_block(item)
        except Exception:  # noqa: BLE001 — handed to the main thread
            self.error = self.error or traceback.format_exc()
        return False                # nothing is queued for this subscriber

    def _on_block(self, item) -> None:
        now = time.perf_counter()
        self.height = item.data["block"].header.height
        self.applied.append((self.height, item.data["block_id"].hash, now))
        self.last_t = now
        runs, first_of_run = self._runs(), False
        if runs != self.runs_seen:
            self.runs_seen, first_of_run = runs, True
        if not first_of_run or not self.armed or self.close is not None:
            return
        if self.open is None:
            if self.height > self.warm_blocks:
                if self.ctx.trace:
                    self.tracer = devtrace.Tracer(
                        emulated=not self.ctx.require_chip)
                    self.tracer.start()
                self.open = self._edge()
        elif now - self.open[0] >= self.seconds:
            self.shut()

    def shut(self) -> None:
        self.close = self._edge()
        if self.tracer is not None:
            self.reducing = True
            self.trace = self.tracer.stop()
            self.last_t, self.reducing = time.perf_counter(), False
        self.closed.set()


def build_node(cfg: dict, home: str, vals: rc.ValSet, p: rb.ChainParams):
    """What node/node.py builds for a fast-syncing node, from the same
    parts -> (reactor, parts by name)."""
    from tmtpu.abci.example.kvstore import KVStoreApplication
    from tmtpu.blocksync.reactor import BlocksyncReactor
    from tmtpu.consensus.replay import Handshaker
    from tmtpu.crypto import ed25519 as prog_ed
    from tmtpu.evidence.pool import EvidencePool
    from tmtpu.libs.db import MemDB, SQLiteDB
    from tmtpu.mempool.clist_mempool import CListMempool
    from tmtpu.proxy import AppConns, default_client_creator
    from tmtpu.state.execution import BlockExecutor
    from tmtpu.state.state import state_from_genesis
    from tmtpu.state.store import StateStore
    from tmtpu.store.block_store import BlockStore
    from tmtpu.types.event_bus import EventBus
    from tmtpu.types.genesis import GenesisDoc, GenesisValidator
    from tmtpu.types.params import ConsensusParams

    def db(name):
        if cfg["program"]["db_backend"] == "mem":
            return MemDB()
        os.makedirs(os.path.join(home, "data"), exist_ok=True)
        return SQLiteDB(os.path.join(home, "data", name + ".sqlite"))

    genesis = GenesisDoc(
        p.chain_id, genesis_time=p.genesis_time_ns,
        consensus_params=ConsensusParams(block_max_bytes=p.block_max_bytes,
                                         block_max_gas=p.block_max_gas),
        validators=[GenesisValidator(prog_ed.PubKeyEd25519(pub), power)
                    for pub, power in zip(vals.pubs, vals.powers)])
    genesis.validate_and_complete()
    block_store = BlockStore(db("blockstore"))
    state_store = StateStore(db("state"))
    state = state_from_genesis(genesis)
    if [v.address for v in state.validators.validators] != vals.addrs:
        raise SystemExit("the program orders the validator set otherwise "
                         "than the reference does")
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
    reactor = BlocksyncReactor(hs.state, block_exec, block_store, True)
    return reactor, {"proxy_app": proxy_app, "event_bus": event_bus,
                     "block_store": block_store, "state_store": state_store}


def fault_plan(kind: str, vals, p, chain, tips, held: int, seed: int,
               txs_per_block: int, tx_bytes: int):
    """What the lying peer serves after ``held`` (the block the pool holds
    unapplied): FAULT_BLOCKS blocks, the fault in the last pair.
    -> ({height: Block}, refused height)"""
    heights = list(range(held + 1, held + 1 + FAULT_BLOCKS))
    served = {h: chain[h - 1] for h in heights}
    refused, last = heights[-2], heights[-1]
    if kind == "tampered":
        served[last] = rb.tampered_successor(vals, chain[last - 1], seed)
    elif kind == "starved":
        served[last] = rb.starved_successor(vals, chain[last - 1], seed)
    else:
        served[refused] = rb.another_block(vals, p, tips[refused - 1], seed,
                                           txs_per_block, tx_bytes)
    return served, refused


def fresh_lanes(run) -> int:
    """For-block signatures a fused verify of ``run`` sends to the device
    when none is cached: those of every successor's LastCommit that names
    its block (the entry refuses another id before it collects a lane)."""
    return sum(sum(1 for s in nxt.last_commit.sigs if s[0] == rc.COMMIT)
               for blk, nxt in zip(run, run[1:])
               if (nxt.last_commit.block_hash, nxt.last_commit.parts_total,
                   nxt.last_commit.parts_hash) == blk.id)


def run(ctx) -> RunResult:
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    clock = time.perf_counter
    n_val = int(cfg["validators"])
    assumed = cfg["assumed"]
    n_absent = int(assumed["absent_per_commit"])
    # the configuration's own value, where it states one (a toy size)
    txs_per_block = int(assumed.get("txs_per_block", mix["txs_per_block"]))
    tx_bytes = int(cfg["tx_bytes"])
    n_chain = int(mix["chain_blocks"])
    stall_s = float(mix["stall_seconds"])
    seconds = min(ctx.seconds, float(mix["trace_seconds"])) if ctx.trace \
        else ctx.seconds
    if int(mix["serving_peers"]) != 1 or \
            int(mix["adversarial_runs"]) != len(FAULTS):
        raise SystemExit("blocksync_replay plays one serving peer and "
                         f"{len(FAULTS)} faults")

    # -- reach the chip -----------------------------------------------------
    t = clock()
    from tmtpu.abci import types as abci
    from tmtpu.blocksync.common import BLOCKCHAIN_CHANNEL, run_shape
    from tmtpu.blocksync.pool import REQUEST_WINDOW
    from tmtpu.config.config import CryptoConfig
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.libs import metrics as prog_metrics

    if int(mix["peer_window"]) != REQUEST_WINDOW:
        raise SystemExit(f"the mix states a peer window of "
                         f"{mix['peer_window']}, the pool's is "
                         f"{REQUEST_WINDOW}")
    crypto_batch.configure(CryptoConfig(**cfg["program"]["crypto"]))
    crypto_batch.set_default_backend(cfg["program"]["crypto_backend"])
    crypto_batch.start_backend(cfg["program"]["crypto_backend"],
                               "benchmarks/run.py")
    device = devtrace.device_facts()
    ctx.check_device(device)
    compiles = devtrace.CompileCount()
    chip_reach_s = clock() - t

    # -- the chain, from the seed -------------------------------------------
    t = clock()
    vals = rc.make_valset(ctx.seed, n_val, int(assumed["voting_power"]))
    p = rb.ChainParams(cfg["chain_id"], int(cfg["genesis_time_ns"]),
                       app_version=int(cfg["app_version"]))
    chain, tips = rb.make_chain(vals, p, ctx.seed, n_chain, txs_per_block,
                                tx_bytes, n_absent)
    wire = {b.height: rb.block_response(b) for b in chain}
    datagen_s = clock() - t

    work = tempfile.mkdtemp(prefix="bench-replay-")
    reactor = parts = None
    try:
        # -- the node ---------------------------------------------------------
        t = clock()
        reactor, parts = build_node(cfg, os.path.join(work, "home"), vals, p)
        watch = Watch(ctx, mix, prog_metrics, compiles, seconds)
        parts["event_bus"].subscribe("bench", watch)
        net = Net(reactor, watch)
        reactor.switch = net
        first = ServingPeer("peer-a", reactor, BLOCKCHAIN_CHANNEL, compiles)
        first.serve = wire
        # the tail is kept back for the faults
        first.base, first.height = 1, n_chain - len(FAULTS) * FAULT_BLOCKS - 2
        net.add(first)
        node_s = clock() - t
        gc.collect()
        gc.freeze()     # the chain's objects are not walked inside the window

        # -- start: the pool routine warms the run's shape, then asks -------
        t_start = clock()
        reactor.on_start()     # as the switch does when it starts

        def wait(done, what: str, limit: float = None):
            """Poll ``done()``; no block applied (or ``limit`` passed)
            for too long ends the run."""
            t0 = clock()
            while not done():
                if watch.error:
                    raise SystemExit("blocksync_replay: the watch failed:\n"
                                     + watch.error)
                time.sleep(0.05)
                now = clock()
                idle = 0.0 if watch.reducing else now - max(
                    watch.last_t, (first.first_request or (now,))[0])
                if idle > stall_s or (limit and now - t0 > limit):
                    raise Stalled(f"waiting for {what}: height "
                                  f"{watch.height}, no block for {idle:.0f}s")

        wait(lambda: first.first_request is not None,
             "the node's first request", 1500.0)
        warm_s = first.first_request[0] - t_start
        wait(lambda: watch.height >= watch.warm_blocks, "the warm blocks")
        watch.armed = True
        wait(lambda: watch.closed.is_set()
             or watch.height >= first.height - 1, "the window")
        cut_at_tip = 0
        if not watch.closed.is_set():
            # the served tip came first: close where the node stands
            cut_at_tip = 1
            if watch.open is None:
                raise Stalled("the tip was reached before the window opened")
            watch.shut()
        (t_open, h_open, reg0, comp0), (t_close, h_close, reg1, comp1) = \
            watch.open, watch.close
        setup_s = t_open - ctx.t_start
        window_s = t_close - t_open
        trace = watch.trace
        device["memory_peak_bytes"] = devtrace.memory_peak_bytes()
        n_blocks = h_close - h_open
        sigs = sum(chain[h].last_commit.present()
                   for h in range(h_open + 1, h_close + 1))
        print(f"blocksync_replay: window {window_s:.3f}s, blocks "
              f"{h_open + 1}..{h_close} ({n_blocks}), {sigs} signatures; "
              f"set-up: chip {chip_reach_s:.1f}s data {datagen_s:.1f}s node "
              f"{node_s:.1f}s warm {warm_s:.1f}s", file=sys.stderr, flush=True)
        shorter, due = [], 10
        for h, _hash, at in watch.applied:
            if h > h_open and at - t_open >= due and at <= t_close:
                shorter.append(f"{due}s={(h - h_open) / (at - t_open):.2f}")
                due += 10
        print(f"blocksync_replay: blocks/s by window length "
              f"{' '.join(shorter)} full={n_blocks / window_s:.2f}",
              file=sys.stderr, flush=True)
        # where a window's time went unevenly: the longest waits between
        # two blocks (a run's verify lies in one of every 35)
        ats = [at for h, _x, at in watch.applied if h_open <= h <= h_close]
        gaps = sorted((b - a, i) for i, (a, b) in enumerate(zip(ats, ats[1:])))
        print("blocksync_replay: gap between blocks p50 "
              f"{1000 * gaps[len(gaps) // 2][0]:.1f} ms, longest "
              + " ".join(f"{1000 * g:.0f}ms@{h_open + i + 1}"
                         for g, i in gaps[:-6:-1]),
              file=sys.stderr, flush=True)

        # -- drain: the first peer stops at what it has served ---------------
        t = clock()
        first.announce(1, first.max_served)
        wait(lambda: watch.height >= first.max_served - 1, "the drain")
        time.sleep(0.2)     # a request already handed out may still land
        wait(lambda: watch.height >= first.max_served - 1, "the drain")
        drained = first.max_served - 1
        drain_s = clock() - t

        # -- the reference's replay of what was served so far ----------------
        rng = random.Random(ctx.seed ^ 0xC0FFEE)
        tail_from = drained
        sample = set(rng.sample(range(2, drained),
                                min(int(mix["reference_sample"]), drained - 2)))
        replay = rb.Replay(vals, p, verify_at=sample | set(
            range(tail_from, n_chain + 1)))
        ref_out = replay.run(chain[:drained + 1])
        checks = Checks()
        checks.at_most("reference_refused_its_chain",
                       int(ref_out.refused is not None), 0)

        # -- the faults, each in a run of its own ----------------------------
        fault_rows = []
        regs = []
        comp_tail0 = compiles.n
        for k, kind in enumerate(FAULTS):
            held = watch.height + 1
            served, refused = fault_plan(kind, vals, p, chain, tips, held,
                                         ctx.seed, txs_per_block, tx_bytes)
            run_blocks = [chain[held - 1]] + [served[h]
                                              for h in sorted(served)]
            want = replay.run(run_blocks)
            liar = ServingPeer(f"peer-liar-{kind}", reactor,
                               BLOCKCHAIN_CHANNEL)
            liar.serve = {h: rb.block_response(b) for h, b in served.items()}
            applied_before, punished_before = len(watch.applied), \
                len(net.punished)
            reg_a = prog_metrics.summary()
            net.add(liar)
            liar.announce(min(served), max(served))
            wait(lambda: len(net.punished) > punished_before
                 or watch.height >= max(served) - 1,
                 f"the {kind} fault's verdict", 120.0)
            time.sleep(0.1)
            reg_b = prog_metrics.summary()
            got_applied = [h for h, _x, _t in watch.applied[applied_before:]]
            punished = net.punished[punished_before:]
            got_refused = None
            if punished:
                why = punished[0][1]
                got_refused = (punished[0][2] + 1, next(
                    (r for text, r in REASONS if text in why), why))
            # the servers of the refused height and of its successor
            want_punished = {liar.node_id} if want.refused else set()
            row = {"kind": kind, "applied": got_applied,
                   "refused": got_refused,
                   "punished": sorted({x[0] for x in punished}),
                   "want_applied": want.applied,
                   "want_refused": want.refused,
                   "want_punished": sorted(want_punished)}
            print(f"fault {kind}: program applied={got_applied} "
                  f"refused={got_refused} punished={row['punished']} | "
                  f"reference applied={want.applied} "
                  f"refused={want.refused}", flush=True)
            regs.append((kind, readers.registry_delta(reg_b, reg_a),
                         fresh_lanes(run_blocks)))
            # the good copy, from the first peer
            good = [chain[h - 1] for h in (refused, refused + 1)]
            want_good = replay.run(good)
            first.announce(1, refused + 1)
            wait(lambda: watch.height >= refused, f"the good copy of "
                 f"{refused}", 120.0)
            row["good_applied"] = watch.height == refused and \
                want_good.applied == [refused]
            fault_rows.append(row)
        reg_end = prog_metrics.summary()
        tail_compiles = compiles.n - comp_tail0

        # -- correct ------------------------------------------------------------
        t = clock()
        final_height = watch.height
        store, state = parts["block_store"], reactor.state
        wrong_hash = missing = 0
        by_height = {h: x for h, x, _t in watch.applied}
        for h in range(h_open + 1, h_close + 1):
            b = chain[h - 1]
            wrong_hash += by_height.get(h) != b.hash
            meta = store.load_block_meta(h)
            seen = store.load_seen_commit(h)
            ok = meta is not None and seen is not None and (
                meta.block_id.hash, meta.block_id.parts_total,
                meta.block_id.parts_hash) == b.id and \
                seen.to_proto().encode() == rb.encode_commit(
                    vals, tips[h].commit)
            missing += not ok
        checks.at_most("window_blocks_wrong_hash", wrong_hash, 0)
        checks.at_most("window_blocks_not_in_store", missing, 0)
        checks.at_least("window_blocks", n_blocks, 1)
        checks.at_most("window_cut_at_tip", cut_at_tip, 0)
        checks.at_most("commits_off_size", sum(
            1 for h in range(h_open + 1, h_close + 1)
            if chain[h].last_commit.present() != n_val - n_absent), 0)
        delta = readers.registry_delta(reg1, reg0)
        r = readers.Readings(
            clock={"chip_reach_s": chip_reach_s, "datagen_s": datagen_s,
                   "warm_s": warm_s},
            counters={"program_counter": delta}, trace=trace,
            window_s=window_s, device_kind=device["kind"])

        def counted(name, field="value", table=delta):
            return readers.term_value(
                {"source": "program_counter", "name": name, "field": field},
                "", readers.Readings(counters={"program_counter": table})) \
                or 0
        checks.at_most("window_bad_blocks",
                       counted("blocksync_bad_blocks_total"), 0)
        checks.at_most("window_applied_counter_off", abs(counted(
            "blocksync_blocks_applied_total") - n_blocks), 0)
        checks.at_most("compiles_in_window", comp1 - comp0, 0)
        gates.device_path(checks, r, ctx.require_chip,
                          int(counted("blocksync_run_blocks$", "count")))
        # whatever the run lengths were, first, short or last: the shape
        # the pool routine warmed before its first request is the only one
        checks.at_most("compiles_after_first_request",
                       compiles.n - first.first_request[1], 0)
        print("blocksync_replay: run shape (blocks, lanes) "
              f"{run_shape(state.validators)}", file=sys.stderr, flush=True)

        differ = peers_differ = good_missing = 0
        for row in fault_rows:
            differ += row["applied"] != row["want_applied"] or \
                row["refused"] != row["want_refused"]
            peers_differ += row["punished"] != row["want_punished"]
            good_missing += not row["good_applied"]
        checks.at_most("fault_outcomes_differ", differ, 0)
        checks.at_most("fault_peers_punished_differ", peers_differ, 0)
        checks.at_most("fault_good_copy_not_applied", good_missing, 0)
        checks.at_most("fault_bad_blocks_counter_off", abs(counted(
            "blocksync_bad_blocks_total",
            table=readers.registry_delta(reg_end, reg1)) - len(FAULTS)), 0)
        for kind, table, lanes_due in regs:
            gates.lanes_on_device(checks, f"fault_{kind}", readers.Readings(
                counters={"program_counter": table}), ctx.require_chip,
                lanes_due)
        checks.at_most("fault_compiles", tail_compiles, 0)

        checks.at_most("final_height_off",
                       abs(final_height - replay.tip.height)
                       + abs(state.last_block_height - replay.tip.height), 0)
        checks.at_most("final_app_hash_differs",
                       int(state.app_hash != replay.tip.app_hash), 0)
        keys = rng.sample(sorted(replay.state), min(int(mix["readback"]),
                                                    len(replay.state)))
        query = parts["proxy_app"].query
        checks.at_most("readback_wrong", sum(
            1 for k in keys if bytes(query.query_sync(
                abci.RequestQuery(data=k)).value) != replay.state[k]), 0)
        checks.at_least("readback_sampled", len(keys),
                        min(int(mix["readback"]), 1))
        print(f"blocksync_replay: drain {drain_s:.1f}s to height {drained}, "
              f"final height {final_height}; checks took {clock() - t:.1f}s; "
              f"none of it in setup_s", file=sys.stderr, flush=True)
    finally:
        if reactor is not None:
            reactor.on_stop()
        if parts is not None:
            parts["proxy_app"].stop()
        shutil.rmtree(work, ignore_errors=True)

    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    return RunResult(
        checks=checks, attempted=n_blocks,
        failed=int(counted("blocksync_bad_blocks_total")),
        end_to_end={"verify_sigs_per_s": sigs / window_s,
                    "setup_s": setup_s},
        device=device, readings=r,
        breakdown=tracered.breakdown(trace) if trace else None)

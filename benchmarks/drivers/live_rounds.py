"""Driver ``live_rounds``: one validator of a large chain in live consensus,
height after height, in the process that holds the chip.

It builds the node ``node/node.py`` builds for a validator, through the
program's own scripted network (``tmtpu/e2e/flood_round.py build_node`` and
``Network``: ``ConsensusState`` + ``ConsensusReactor``, consensus WAL on
disk, FilePV with the node's key, ``BlockExecutor``, stores on the shipped
``db_backend`` under a temporary home, the kvstore app behind
``proxy.AppConns``), compiles the set's vote-flush shapes as
``Node.on_start`` does (``warm_validator_set``) and stands in for every
other validator with one relay peer. The chain is fabricated before the
window by reference/rounds.py; the relay thread hands
``ConsensusReactor.receive`` its wire bytes: when the node has entered
height h, h's proposal and parts, then every co-signer's prevote, then
every precommit, back to back, then it waits for height h+1. The driver
calls no verify entry and no step of the state machine; it listens on the
event bus (a subscription's predicate runs on the publishing thread, so a
NewBlock is seen the instant it is applied).

The window (the rule of benchmarks/README.md): opens at the commit of the
last of ``warm_heights`` heights, closes at the first commit after
``--seconds``. ``verify_sigs_per_s`` = the co-signers' votes of the heights
committed between (2 x the co-signers a height: prevotes, precommits and
late precommits, each added to a vote set after its signature was
verified) over the time between. If the chain's clean heights run out first
the window closes there and a check fails. ``setup_s`` runs to the window's
opening: fabrication, the node's start, the shapes' warm-up and the warm
heights included. With ``--trace 1`` the window is ``trace_seconds`` long
and all of it is profiled: the profiler starts and stops on the consensus
thread, at the first complete proposal after each of the two commits (a
commit lies inside its finalize's span, which a profile cut there would
lose: the traced window holds the same whole heights, shifted by the part
of a height between the two events). No commit for ``stall_seconds`` ends the run
with exit code 3, never a hang.

``correct`` (exact counts, limit 0): every height committed in the window
has the reference's block hash and its stored SeenCommit names that block
with more than 2/3 of the power, each signature in it valid under the
reference's one-at-a-time verifier; over the whole run the votes the
counters say were added or dropped equal the votes sent, and in the window
none was refused; nothing compiled after the node's start, no CPU-fallback
lane, every dispatch on ``tpu/pallas``, and beyond the vote flushes no more
lanes dispatched than late precommits were dropped (a LastCommit's
signatures that the node had not verified as votes; every other LastCommit
check of validate_block hits the cache whole). Then three faults, each in a
height of its own followed by a clean height — a prevote whose signature is
tampered mid-flood, a co-signer that sends two precommits for different
block ids, precommits that stop at exactly 2/3 of the power until one more
arrives: what was added, what was refused, the evidence the pool holds and
the block committed equal the plain protocol's (reference/rounds.py
``Height``), which also replays ``reference_sample`` of the window's
heights vote by vote. At the end the app hash and ``readback`` keys read
through the app's ``query`` equal the reference's.
"""

from __future__ import annotations

import gc
import hashlib
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
from benchmarks.reference import rounds as rr

FAULTS = ("tampered_prevote", "double_precommit", "starved_precommits")
FAULT_HEIGHTS = 2 * len(FAULTS)     # each fault and the clean height after it


class Stalled(SystemExit):
    def __init__(self, what: str):
        print(f"live_rounds: {what}; giving up", file=sys.stderr, flush=True)
        super().__init__(3)


class Watch:
    """The driver's eyes on the node: a predicate on the event bus, run by
    the consensus thread as each NewBlock is published. It opens and closes
    the window at commits; everything it keeps is read by other threads
    after."""

    def __init__(self, ctx, warm_heights, prog_metrics, compiles, seconds):
        self.ctx, self.prog_metrics, self.compiles = ctx, prog_metrics, compiles
        self.seconds = seconds
        self.warm_heights = warm_heights
        self.commits = []           # (height, block hash, t)
        self.height = 0
        self.last_t = time.perf_counter()
        self.open = self.close = None       # (t, height, registry, compiles)
        self.closed = threading.Event()
        self.tracer = None
        self.trace = None
        self.reducing = False       # the consensus thread is reading the trace
        self.error = None           # the bus swallows what a predicate raises
        self._due = None            # what the next complete proposal sets off
        self.t_open_commit = 0.0

    def _edge(self):
        return (time.perf_counter(), self.height,
                self.prog_metrics.summary(), self.compiles.n)

    def __call__(self, item) -> bool:
        try:
            if item.type == "NewBlock":
                self._on_block(item)
            elif item.type == "CompleteProposal" and self._due is not None:
                due, self._due = self._due, None
                due()
        except Exception:  # noqa: BLE001 — handed to the main thread
            self.error = self.error or traceback.format_exc()
        return False                # nothing is queued for this subscriber

    def _on_block(self, item) -> None:
        now = time.perf_counter()
        self.height = item.data["block"].header.height
        self.commits.append((self.height, item.data["block_id"].hash, now))
        self.last_t = now
        if self.close is not None or self._due is not None:
            return
        if self.open is None:
            if self.height >= self.warm_heights:
                self._at_boundary(self._open)
        elif now - self.t_open_commit >= self.seconds:
            self._at_boundary(self.shut)

    def _at_boundary(self, act) -> None:
        """A window's boundary is a commit. A commit lies inside the span of
        its finalize, which a profile that started or stopped there would
        cut: a traced window is taken from the next complete proposal after
        the opening commit to the one after the closing commit — the same
        whole heights, no span open at either end."""
        if self.open is None:
            self.t_open_commit = time.perf_counter()
        if self.ctx.trace:
            self._due = act
        else:
            act()

    def _open(self) -> None:
        if self.ctx.trace:
            self.tracer = devtrace.Tracer(emulated=not self.ctx.require_chip)
            self.tracer.start()
        self.open = self._edge()

    def shut(self) -> None:
        self.close = self._edge()
        if self.tracer is not None:
            self.reducing = True
            self.trace = self.tracer.stop()
            self.last_t, self.reducing = time.perf_counter(), False
        self.closed.set()


def other_block_id(seed: int, height: int) -> rb.BlockID:
    """A complete block id that is no block's of the chain."""
    d = hashlib.sha256(b"live-other-%d-%d" % (seed, height)).digest()
    return (d, 1, hashlib.sha256(d).digest())


def run(ctx) -> RunResult:
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    clock = time.perf_counter
    assumed = cfg["assumed"]
    n_val = int(cfg["validators"])
    n_chain = int(mix["chain_heights"])
    warm_heights = int(mix["warm_heights"])
    stall_s = float(mix["stall_seconds"])
    seconds = min(ctx.seconds, float(mix["trace_seconds"])) if ctx.trace \
        else ctx.seconds
    if int(mix["relay_peers"]) != 1 or \
            int(mix["adversarial_heights"]) != len(FAULTS):
        raise SystemExit("live_rounds plays one relay peer and "
                         f"{len(FAULTS)} faults")

    from tmtpu.e2e import flood_round

    if not hasattr(flood_round, "Network"):
        # before a signature is made: a program without the network fails soon
        raise SystemExit("live_rounds: this program has no "
                         "tmtpu/e2e/flood_round.py Network to play a chain's "
                         "heights to a live validator with")

    # -- the chain, from the seed, signed in worker processes ---------------
    t = clock()
    spec = rr.RoundsSpec(ctx.seed, cfg["chain_id"],
                         int(cfg["genesis_time_ns"]), n_val,
                         int(assumed["voting_power"]),
                         int(assumed["txs_per_block"]), int(cfg["tx_bytes"]),
                         int(cfg["app_version"]))
    chain = rr.make_chain(spec, n_chain, min(int(mix["datagen_workers"]),
                                             os.cpu_count() or 1))
    vals, n_co = chain.vals, len(chain.co_signers)
    datagen_s = clock() - t

    # -- reach the chip -----------------------------------------------------
    t = clock()
    from tmtpu.abci import types as abci
    from tmtpu.config.config import ConsensusConfig, CryptoConfig
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.crypto import ed25519 as prog_ed
    from tmtpu.libs import metrics as prog_metrics
    from tmtpu.privval.file_pv import FilePV
    from tmtpu.types.genesis import GenesisDoc, GenesisValidator
    from tmtpu.types.params import ConsensusParams

    backend = cfg["program"]["crypto_backend"]
    crypto_batch.configure(CryptoConfig(**cfg["program"]["crypto"]))
    crypto_batch.set_default_backend(backend)
    crypto_batch.start_backend(backend, "benchmarks/run.py")
    device = devtrace.device_facts()
    ctx.check_device(device)
    compiles = devtrace.CompileCount()
    chip_reach_s = clock() - t

    work = tempfile.mkdtemp(prefix="bench-live-")
    net = None
    try:
        # -- the node: built, its shapes warmed, then started -----------------
        t = clock()
        p = spec.params()
        genesis = GenesisDoc(
            p.chain_id, genesis_time=p.genesis_time_ns,
            consensus_params=ConsensusParams(
                block_max_bytes=p.block_max_bytes,
                block_max_gas=p.block_max_gas),
            validators=[GenesisValidator(prog_ed.PubKeyEd25519(pub), power)
                        for pub, power in zip(vals.pubs, vals.powers)])
        genesis.validate_and_complete()
        home = os.path.join(work, "home")
        os.makedirs(os.path.join(home, "config"))
        pv = FilePV(prog_ed.PrivKeyEd25519(
            vals.privs[chain.node].private_bytes_raw()),
            os.path.join(home, "config", "priv_validator_key.json"),
            os.path.join(home, "data", "priv_validator_state.json"))
        os.makedirs(os.path.join(home, "data"), exist_ok=True)
        pv.save()
        if cfg["program"]["db_backend"] != "sqlite":
            raise SystemExit("the configuration states another store than "
                             "build_node's")
        node = flood_round.build_node(
            home, genesis, pv,
            consensus_config=ConsensusConfig(**cfg["program"]["consensus"]))
        cs, store = node["consensus"], node["block_store"]
        if [v.address for v in cs.state.validators.validators] != vals.addrs:
            raise SystemExit("the program orders the validator set otherwise "
                             "than the reference does")
        node_s = clock() - t
        t = clock()
        warmed = crypto_batch.warm_validator_set(cs.state.validators) \
            if crypto_batch._resolve_backend(backend) == "tpu" else []
        warm_s = clock() - t
        compiles_at_start = compiles.n

        class Script:
            def proposal(self, h):
                return chain.heights[h - 1].proposal, chain.heights[h - 1].parts

            def flood(self, h, _block_id):
                return chain.heights[h - 1].prevotes, \
                    chain.heights[h - 1].precommits

        net = flood_round.Network(node, Script())
        relay = net.relay
        watch = Watch(ctx, warm_heights, prog_metrics, compiles, seconds)
        node["event_bus"].subscribe("bench", watch)
        gc.collect()
        gc.freeze()     # the chain's objects are not walked inside the window
        net.start()

        def counted(name, field="value", labels=None, table=None):
            """``table``: a registry delta, or the registry as it stands."""
            term = {"source": "program_counter", "name": name, "field": field}
            if labels:
                term["labels"] = labels
            if table is None:
                table = {k: v["series"]
                         for k, v in prog_metrics.summary().items()}
            return readers.term_value(term, "", readers.Readings(
                counters={"program_counter": table})) or 0

        def own_votes() -> int:
            rs = cs.rs
            return 2 * (rs.height - 1) + (rs.step >= 4) + (rs.step >= 6)

        def accounted() -> int:
            """Peers' votes the counters hold, added or dropped: the node's
            own two a height it has voted in are taken off."""
            return int(counted("consensus_votes_(added|dropped)_total")
                       - votes_before - own_votes())

        votes_before = counted("consensus_votes_(added|dropped)_total")
        reg_start = prog_metrics.summary()

        # -- the relay's thread: clean heights until the window has closed ----
        relay_err = []
        last_clean = n_chain - FAULT_HEIGHTS
        played = {"height": 0, "cut_at_tip": 0}

        def relay_routine():
            try:
                for h in range(1, last_clean + 1):
                    net.play_height(h, stall_s)
                    played["height"] = h
                    net.wait_entered(h + 1, stall_s)
                    if watch.closed.is_set():
                        return
                played["cut_at_tip"] = 1
            except BaseException as e:  # noqa: BLE001 — reported below
                relay_err.append(e)

        def wait(done, what: str):
            """Poll ``done()``; no commit for too long ends the run."""
            while not done():
                if watch.error:
                    raise SystemExit("live_rounds: the watch failed:\n"
                                     + watch.error)
                if relay_err:
                    raise Stalled(f"the relay failed waiting for {what}: "
                                  f"{relay_err[0]!r}")
                time.sleep(0.02)
                idle = 0.0 if watch.reducing else clock() - watch.last_t
                if idle > stall_s:
                    raise Stalled(f"waiting for {what}: at "
                                  f"{cs.rs.height_round_step()}, no commit "
                                  f"for {idle:.0f}s")

        thread = threading.Thread(target=relay_routine, daemon=True,
                                  name="vote-relay")
        watch.last_t = clock()
        thread.start()
        wait(lambda: not thread.is_alive(), "the window")
        if not watch.closed.is_set():
            if watch.open is None:
                raise Stalled("the clean heights ran out before the window "
                              "opened")
            watch.shut()    # the clean heights ran out: close where it stands
        (t_open, h_open, reg0, comp0), (t_close, h_close, reg1, comp1) = \
            watch.open, watch.close
        setup_s = t_open - ctx.t_start
        window_s = t_close - t_open
        trace = watch.trace
        device["memory_peak_bytes"] = devtrace.memory_peak_bytes()
        n_heights = h_close - h_open
        # what the node added: a late precommit that arrived after round 0
        # began is dropped, as state.go drops it, and is no verified vote
        sigs = 2 * n_co * n_heights - int(counted(
            "consensus_votes_dropped_total", labels="reason=late",
            table=readers.registry_delta(reg1, reg0)))
        at = [x[2] for x in watch.commits if h_open <= x[0] <= h_close]
        intervals = [b - a for a, b in zip(at, at[1:])]
        print(f"live_rounds: window {window_s:.3f}s, heights {h_open + 1}.."
              f"{h_close} ({n_heights}), {sigs} votes; set-up: data "
              f"{datagen_s:.1f}s chip {chip_reach_s:.1f}s node {node_s:.1f}s "
              f"warm {warm_s:.1f}s over {len(warmed)} shapes "
              f"{[(w[0], w[1], w[2]) for w in warmed]}; a height p50 "
              f"{1000 * sorted(intervals)[len(intervals) // 2]:.0f} ms, "
              f"longest {1000 * max(intervals):.0f} ms" if intervals else
              "live_rounds: the window holds no height", file=sys.stderr,
              flush=True)

        # -- the faults, each in a height of its own, then a clean one --------
        t = clock()

        def settle(what: str):
            """Every vote sent so far is in the counters, added or dropped:
            the node sits in the commit wait of the height it entered."""
            wait(lambda: accounted() >= relay.sent_votes, what)

        def on_relay(what: str, fn, *args):
            """``fn`` on a thread of its own, as the window's relay: a node
            that has stopped taking messages ends the run, not hangs it."""
            th = threading.Thread(target=lambda: _guard(fn, *args),
                                  daemon=True, name="vote-relay")
            watch.last_t = clock()
            th.start()
            wait(lambda: not th.is_alive(), what)

        def _guard(fn, *args):
            try:
                fn(*args)
            except BaseException as e:  # noqa: BLE001 — reported by wait()
                relay_err.append(e)

        def entered(h: int, what: str):
            wait(lambda: cs.rs.height >= h, what)

        needed = vals.total_power * 2 // 3
        fault_rows = []
        comp_tail0 = compiles.n
        h = played["height"] + 1
        settle("the window's last late precommits")
        unaccounted_window = accounted() - relay.sent_votes
        for kind in FAULTS:
            hd = chain.heights[h - 1]
            bid = hd.block.id
            ref = rr.Height(vals, spec.chain_id, h, hd.block.time_ns)
            co = chain.co_signers
            reg_a = prog_metrics.summary()
            ev_before = len(node["evidence_pool"].pending_evidence(1 << 30))
            entered(h, f"height {h}")
            on_relay("the proposal", relay.proposal, hd.proposal, hd.parts)
            prevotes, precommits = list(hd.prevotes), list(hd.precommits)
            ref_prevotes = [chain.vote(rr.PREVOTE, h, i) for i in co]
            ref_precommits = [chain.vote(rr.PRECOMMIT, h, i) for i in co]
            held_without_commit = None
            if kind == "tampered_prevote":
                k = len(co) // 2                # mid-flood
                ref_prevotes[k] = rr.tampered(ref_prevotes[k])
                prevotes[k] = rr.vote_wire(vals, ref_prevotes[k])
            elif kind == "double_precommit":
                k = len(co) // 3                # before the 2/3 point
                second = chain.vote(rr.PRECOMMIT, h, co[k],
                                    other_block_id(ctx.seed, h))
                ref_precommits.insert(k + 1, second)
                precommits.insert(k + 1, rr.vote_wire(vals, second))
            on_relay("the prevotes", relay.votes, prevotes)
            for v in ref_prevotes:
                ref.deliver(v)
            ref.own(rr.PREVOTE, bid, chain.node)
            if ref.polka() == bid:
                ref.own(rr.PRECOMMIT, bid, chain.node)
            if kind == "starved_precommits":
                # with the node's own, exactly 2/3 of the power: one short
                k = needed // vals.powers[0] - 1
                on_relay("the starved precommits", relay.votes,
                         precommits[:k])
                for v in ref_precommits[:k]:
                    ref.deliver(v)
                settle("the starved precommits")
                time.sleep(float(mix["starved_hold_seconds"]))
                held_without_commit = cs.rs.height == h and \
                    store.height() == h - 1 and ref.committed is None
                on_relay("the rest of the precommits", relay.votes,
                         precommits[k:])
                for v in ref_precommits[k:]:
                    ref.deliver(v)
            else:
                on_relay("the precommits", relay.votes, precommits)
                for v in ref_precommits:
                    ref.deliver(v)
            entered(h + 1, f"the commit of height {h}")
            on_relay("the clean height", net.play_height, h + 1, stall_s)
            entered(h + 2, f"the commit of height {h + 1}")
            settle(f"the {kind} fault's votes")
            delta = readers.registry_delta(prog_metrics.summary(), reg_a)

            def d(name, labels=None):
                return int(counted(name, labels=labels, table=delta))
            got = {
                "prevotes": d("consensus_votes_added_total", "type=prevote$"),
                # added on time or late, or a late one dropped after round
                # 0 began, as state.go drops it: the plain protocol has no
                # clock and counts the three alike
                "precommits": d("consensus_votes_added_total",
                                "type=(late_)?precommit$")
                + d("consensus_votes_dropped_total", "reason=late"),
                "refused": d("consensus_votes_dropped_total",
                             "reason=refused"),
                "invalid": d("consensus_invalid_votes_total"),
                "dropped_otherwise": d("consensus_votes_dropped_total",
                                       "reason=height"),
                "committed": next((x[1] for x in watch.commits
                                   if x[0] == h), None),
            }
            # the fault height and the clean one after it; the node's own
            # four votes of the two heights are in the counters too
            want = {
                "prevotes": ref.added[rr.PREVOTE] + n_co + 2,
                "precommits": ref.added[rr.PRECOMMIT] + n_co + 2,
                "refused": len(ref.refused),
                "invalid": sum(1 for r in ref.refused
                               if r[2] == rr.BAD_SIGNATURE),
                "dropped_otherwise": 0,
                "committed": ref.committed[0] if ref.committed else None,
            }
            pending = node["evidence_pool"].pending_evidence(1 << 30)[
                ev_before:]
            got["evidence"] = sorted(
                (e.vote_a.height, e.vote_a.validator_index,
                 bytes(e.vote_a.block_id.hash), bytes(e.vote_a.signature),
                 bytes(e.vote_b.block_id.hash), bytes(e.vote_b.signature),
                 e.total_voting_power, e.validator_power, e.timestamp)
                for e in pending)
            want["evidence"] = sorted(
                (e.vote_a.height, e.vote_a.index, e.vote_a.block_id[0],
                 e.vote_a.signature, e.vote_b.block_id[0],
                 e.vote_b.signature, e.total_voting_power,
                 e.validator_power, e.timestamp_ns) for e in ref.evidence)
            row = {"kind": kind, "got": got, "want": want,
                   "held_without_commit": held_without_commit,
                   "clean_committed": next(
                       (x[1] for x in watch.commits if x[0] == h + 1), None)
                   == chain.heights[h].block.hash}
            shown = {k: (v if k != "evidence" else len(v))
                     for k, v in got.items() if k != "committed"}
            print(f"fault {kind} at height {h}: program {shown} committed="
                  f"{(got['committed'] or b'').hex()[:12]} | reference "
                  f"added={ref.added} refused={ref.refused} evidence="
                  f"{len(ref.evidence)} committed="
                  f"{(want['committed'] or b'').hex()[:12]} at precommit "
                  f"{ref.commit_at}; held without commit="
                  f"{held_without_commit}", flush=True)
            fault_rows.append(row)
            h += 2
        tail_compiles = compiles.n - comp_tail0
        final_height = h - 1
        faults_s = clock() - t

        # -- correct ------------------------------------------------------------
        t = clock()
        checks = Checks()
        rng = random.Random(ctx.seed ^ 0xC0FFEE)
        by_height = {x[0]: x[1] for x in watch.commits}
        window = range(h_open + 1, h_close + 1)
        sampled = set(rng.sample(list(window), min(
            int(mix["reference_sample"]), len(window))))
        wrong_hash = bad_commit = bad_sig = replay_differs = 0
        for hh in window:
            hd = chain.heights[hh - 1]
            wrong_hash += by_height.get(hh) != hd.block.hash
            meta, seen = store.load_block_meta(hh), store.load_seen_commit(hh)
            if meta is None or seen is None or (
                    bytes(meta.block_id.hash), meta.block_id.parts_total,
                    bytes(meta.block_id.parts_hash)) != hd.block.id or (
                    bytes(seen.block_id.hash), seen.block_id.parts_total,
                    bytes(seen.block_id.parts_hash)) != hd.block.id or \
                    seen.height != hh or len(seen.signatures) != n_val:
                bad_commit += 1
                continue
            # the stored SeenCommit under the reference's serial verifier
            power = 0
            for idx, s in enumerate(seen.signatures):
                if s.block_id_flag == rc.ABSENT:
                    continue
                v = rr.Vote(rr.PRECOMMIT, hh, seen.round,
                            hd.block.id if s.block_id_flag == rc.COMMIT
                            else rb.ZERO_ID, s.timestamp, idx,
                            bytes(s.signature))
                try:
                    vals.pub_objs[idx].verify(
                        v.signature, rr.vote_sign_bytes(spec.chain_id, v))
                except Exception:  # noqa: BLE001 — counted
                    bad_sig += 1
                    continue
                if s.block_id_flag == rc.COMMIT:
                    power += vals.powers[idx]
            bad_commit += power <= needed
            if hh in sampled:
                # the plain protocol on this height's votes, one at a time
                ref = rr.Height(vals, spec.chain_id, hh, hd.block.time_ns)
                for i in chain.co_signers:
                    ref.deliver(chain.vote(rr.PREVOTE, hh, i))
                ref.own(rr.PREVOTE, hd.block.id, chain.node)
                ref.own(rr.PRECOMMIT, hd.block.id, chain.node)
                for i in chain.co_signers:
                    ref.deliver(chain.vote(rr.PRECOMMIT, hh, i))
                replay_differs += ref.refused != [] or \
                    ref.committed != hd.block.id or \
                    ref.added != {rr.PREVOTE: n_co, rr.PRECOMMIT: n_co}
        checks.at_most("window_heights_wrong_hash", wrong_hash, 0)
        checks.at_most("window_seen_commits_off", bad_commit, 0)
        checks.at_most("window_seen_commit_bad_signatures", bad_sig, 0)
        checks.at_most("window_heights_differ_from_reference",
                       replay_differs, 0)
        checks.at_least("window_heights_replayed_serially", len(sampled),
                        min(int(mix["reference_sample"]), 1))
        checks.at_least("window_heights", n_heights, 1)
        checks.at_most("window_cut_at_tip", played["cut_at_tip"], 0)

        delta = readers.registry_delta(reg1, reg0)
        r = readers.Readings(
            clock={"chip_reach_s": chip_reach_s, "datagen_s": datagen_s,
                   "warm_s": warm_s, "height_interval_s": intervals},
            counters={"program_counter": delta}, trace=trace,
            window_s=window_s, device_kind=device["kind"])

        def in_window(name, field="value", labels=None):
            return counted(name, field, labels, delta)
        # the votes: each sent vote in the counters once, none refused in the
        # window, and the window's own share of them what its heights hold
        # (late precommits cross a commit: those of the opening height are
        # in, those of the closing height out, so the two ends may differ by
        # at most one drain's worth each way)
        checks.at_most("votes_unaccounted_at_window_end",
                       abs(unaccounted_window), 0)
        checks.at_most("votes_unaccounted_at_end",
                       abs(accounted() - relay.sent_votes), 0)
        checks.at_most("window_votes_refused", in_window(
            "consensus_votes_dropped_total", labels="reason=(refused|height)")
            + in_window("consensus_invalid_votes_total"), 0)
        added_in_window = in_window("consensus_votes_added_total") \
            - 2 * n_heights
        checks.at_most("window_votes_added_off", int(
            abs(added_in_window - sigs) > n_co), 0)
        checks.at_most("window_prevotes_added_off", abs(in_window(
            "consensus_votes_added_total", labels="type=prevote$")
            - (n_co + 1) * n_heights), 0)
        # the device path
        checks.at_most("compiles_in_window", comp1 - comp0, 0)
        checks.at_most("compiles_after_start",
                       compiles.n - compiles_at_start, 0)
        checks.at_most("fault_compiles", tail_compiles, 0)
        checks.at_most("vote_flush_shapes_warmed", len(warmed), 3)
        checks.at_most("cpu_fallback_lanes",
                       in_window("crypto_cpu_fallback_total"), 0)
        # every vote flush a dispatch (the sigcache holds none of a fresh
        # vote); beyond them only a LastCommit's signatures the node had
        # not verified as votes: late precommits it dropped
        flushes = in_window("consensus_vote_flush_lanes$", "count")
        gates.device_path(checks, r, ctx.require_chip, int(flushes))
        checks.at_least("lanes_dispatched_in_window", in_window(
            "crypto_batch_size$", "sum"), in_window(
                "consensus_vote_flush_lanes$", "sum"))
        whole = readers.registry_delta(prog_metrics.summary(), reg_start)
        checks.at_most("lanes_dispatched_beyond_the_votes", counted(
            "crypto_batch_size$", "sum", table=whole) - counted(
                "consensus_vote_flush_lanes$", "sum", table=whole), counted(
                    "consensus_votes_dropped_total", labels="reason=late",
                    table=whole))

        differ = held = clean_missing = 0
        for row in fault_rows:
            differ += row["got"] != row["want"] or \
                row["want"]["committed"] is None
            held += row["held_without_commit"] is False
            clean_missing += not row["clean_committed"]
        checks.at_most("fault_outcomes_differ", differ, 0)
        checks.at_most("fault_commit_not_held_at_two_thirds", held, 0)
        checks.at_most("fault_clean_height_not_committed", clean_missing, 0)
        checks.at_least("fault_evidence_held", sum(
            len(row["got"]["evidence"]) for row in fault_rows), 1)

        state = cs.state
        ref_state = rr.final_state(chain, final_height)
        checks.at_most("final_height_off", abs(
            state.last_block_height - final_height), 0)
        checks.at_most("final_app_hash_differs", int(
            bytes(state.app_hash) != chain.tips[final_height].app_hash), 0)
        keys = rng.sample(sorted(ref_state), min(int(mix["readback"]),
                                                 len(ref_state)))
        query = node["proxy_app"].query
        checks.at_most("readback_wrong", sum(
            1 for k in keys if bytes(query.query_sync(
                abci.RequestQuery(data=k)).value) != ref_state[k]), 0)
        checks.at_least("readback_sampled", len(keys),
                        min(int(mix["readback"]), 1))
        late = in_window("consensus_votes_added_total",
                         labels="type=late_precommit")
        print(f"live_rounds: {int(flushes)} vote flushes in the window, "
              f"{int(late)} late precommits; final height {final_height}; "
              f"the faults took {faults_s:.1f}s, the checks "
              f"{clock() - t:.1f}s; none of it in setup_s", file=sys.stderr,
              flush=True)
    finally:
        if net is not None:
            net.stop()
        shutil.rmtree(work, ignore_errors=True)

    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    return RunResult(
        checks=checks, attempted=n_heights,
        failed=int(wrong_hash + bad_commit),
        end_to_end={"verify_sigs_per_s": sigs / window_s if window_s else 0.0,
                    "setup_s": setup_s},
        device=device, readings=r,
        breakdown=tracered.breakdown(trace) if trace else None)

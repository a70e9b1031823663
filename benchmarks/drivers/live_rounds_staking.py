"""Driver ``live_rounds_staking``: one validator of a chain whose stake
moves every height, in live consensus, in the process that holds the chip.

The node, the relay and the window are ``live_rounds``'s (its docstring):
``tmtpu/e2e/flood_round.py build_node`` and ``Network``, the two vote-flush
shapes warmed as ``Node.on_start`` does, one relay peer handing
``ConsensusReactor.receive`` the wire bytes of a chain fabricated before
the window. The chain is ``reference/staking.py``'s: powers by a Zipf law,
power changes in every block and a leave and a join every ``join_every``-th
(the kvstore's ``val:`` txs, applied by the app's EndBlock to the set two
heights on), each height's proposer the one the priorities pick, each
block's time the power-weighted median of its LastCommit, and a height's
prevotes and precommits each in an order the seed draws for it (a gossiping
peer's PickRandom). So every 2/3 point falls by power at a place of its
own, and every vote flush rides the fused device tally with powers that
carry past the first 13-bit limb.

``correct`` (exact counts, limit 0): every check of ``live_rounds``, with
each height's own set where that says the set, and

- every window height's block hash equals the reference's (its header holds
  both set hashes, the proposer and the weighted median time);
- the state store's sets of every window height's H+1 and H+2 equal the
  reference's byte for byte (addresses, powers, priorities, proposer);
- for every window height the precommit power the node's vote set held for
  the block (its fused device tally) equals the reference's integer sum of
  the powers of the validators in that vote set's bit array, and is more
  than 2/3 of the height's total;
- lanes whose power carries past limb 0 were dispatched in the window;
- the starved fault stops at the longest prefix of the precommit order
  whose power, with the node's own, is at most 2/3 of the total;
- the updates the node applied (``state_validator_updates_total`` by kind)
  are the reference's, every join and leave took effect at H+2 in the
  stored sets, and the app's validator table read through ``query`` is the
  reference's.

A program that does not count the update path's changes or the tally's
lanes by their limbs cannot show the last two points: it exits at once,
before a signature is made.
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

from benchmarks.drivers import live_rounds as lr
from benchmarks.lib import devtrace, gates, readers, tracered
from benchmarks.lib.report import Checks
from benchmarks.lib.result import RunResult
from benchmarks.reference import blocks as rb
from benchmarks.reference import commits as rc
from benchmarks.reference import rounds as rr
from benchmarks.reference import staking as st

FAULTS = lr.FAULTS
FAULT_HEIGHTS = lr.FAULT_HEIGHTS


class Watch(lr.Watch):
    """``live_rounds``'s watch, and at each NewBlock what the vote set of
    the height below came to: its precommit power (the fused tally) and
    its bit array. By then round 0 of the new height has begun, and a
    late precommit of the height below no longer reaches that set."""

    def __init__(self, cs, *args):
        super().__init__(*args)
        self.cs = cs
        self.tallies = {}       # height -> (power, BitArray)

    def _on_block(self, item) -> None:
        lc = self.cs.rs.last_commit
        if lc is not None:
            self.tallies[lc.height] = (lc.sum_voting_power(),
                                       lc.bit_array())
        super()._on_block(item)


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
        raise SystemExit("live_rounds_staking plays one relay peer and "
                         f"{len(FAULTS)} faults")

    from tmtpu.e2e import flood_round
    from tmtpu.libs import metrics as prog_metrics

    if not hasattr(flood_round, "Network"):
        raise SystemExit("live_rounds_staking: this program has no "
                         "tmtpu/e2e/flood_round.py Network to play a chain's "
                         "heights to a live validator with")
    if not (hasattr(prog_metrics, "state_validator_updates")
            and hasattr(prog_metrics, "crypto_tally_power_lanes")):
        # before a signature is made: correct cannot be read without them
        raise SystemExit("live_rounds_staking: this program counts neither "
                         "the validator updates it applies "
                         "(state_validator_updates_total) nor its fused "
                         "tally's lanes by power limb "
                         "(crypto_tally_power_lanes_total)")

    # -- the chain, from the seed, signed in worker processes ---------------
    t = clock()
    spec = st.StakingSpec(
        ctx.seed, cfg["chain_id"], int(cfg["genesis_time_ns"]), n_val,
        total_power=int(assumed["total_power"]),
        changes_per_height=int(assumed["changes_per_height"]),
        change_span=float(assumed["change_span"]),
        join_every=int(assumed["join_every"]),
        txs_per_block=int(assumed["txs_per_block"]),
        tx_bytes=int(cfg["tx_bytes"]), app_version=int(cfg["app_version"]))
    chain = st.make_chain(spec, n_chain, min(int(mix["datagen_workers"]),
                                             os.cpu_count() or 1))
    plan, keys = chain.plan, chain.keys
    n_co = n_val - 1
    datagen_s = clock() - t

    # -- reach the chip -----------------------------------------------------
    t = clock()
    from tmtpu.abci import types as abci
    from tmtpu.config.config import ConsensusConfig, CryptoConfig
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.crypto import ed25519 as prog_ed
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

    work = tempfile.mkdtemp(prefix="bench-stake-")
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
            validators=[GenesisValidator(prog_ed.PubKeyEd25519(keys.pubs[k]),
                                         power)
                        for k, power in plan.genesis])
        genesis.validate_and_complete()
        home = os.path.join(work, "home")
        os.makedirs(os.path.join(home, "config"))
        pv = FilePV(prog_ed.PrivKeyEd25519(
            keys.privs[plan.node_key].private_bytes_raw()),
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
        state_store = node["state_store"]
        if [v.address for v in cs.state.validators.validators] != \
                chain.vals(1).addrs:
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
        watch = Watch(cs, ctx, warm_heights, prog_metrics, compiles, seconds)
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
                    raise SystemExit("live_rounds_staking: the watch "
                                     "failed:\n" + watch.error)
                if relay_err:
                    raise lr.Stalled(f"the relay failed waiting for {what}: "
                                     f"{relay_err[0]!r}")
                time.sleep(0.02)
                idle = 0.0 if watch.reducing else clock() - watch.last_t
                if idle > stall_s:
                    raise lr.Stalled(f"waiting for {what}: at "
                                     f"{cs.rs.height_round_step()}, no "
                                     f"commit for {idle:.0f}s")

        thread = threading.Thread(target=relay_routine, daemon=True,
                                  name="vote-relay")
        watch.last_t = clock()
        thread.start()
        wait(lambda: not thread.is_alive(), "the window")
        if not watch.closed.is_set():
            if watch.open is None:
                raise lr.Stalled("the clean heights ran out before the "
                                 "window opened")
            watch.shut()    # the clean heights ran out: close where it stands
        (t_open, h_open, reg0, comp0), (t_close, h_close, reg1, comp1) = \
            watch.open, watch.close
        setup_s = t_open - ctx.t_start
        window_s = t_close - t_open
        trace = watch.trace
        device["memory_peak_bytes"] = devtrace.memory_peak_bytes()
        n_heights = h_close - h_open
        sigs = 2 * n_co * n_heights - int(counted(
            "consensus_votes_dropped_total", labels="reason=late",
            table=readers.registry_delta(reg1, reg0)))
        at = [x[2] for x in watch.commits if h_open <= x[0] <= h_close]
        intervals = [b - a for a, b in zip(at, at[1:])]
        print(f"live_rounds_staking: window {window_s:.3f}s, heights "
              f"{h_open + 1}..{h_close} ({n_heights}), {sigs} votes; set-up: "
              f"data {datagen_s:.1f}s chip {chip_reach_s:.1f}s node "
              f"{node_s:.1f}s warm {warm_s:.1f}s over {len(warmed)} shapes "
              f"{[(w[0], w[1], w[2]) for w in warmed]}; a height p50 "
              f"{1000 * sorted(intervals)[len(intervals) // 2]:.0f} ms, "
              f"longest {1000 * max(intervals):.0f} ms" if intervals else
              "live_rounds_staking: the window holds no height",
              file=sys.stderr, flush=True)

        # -- the faults, each in a height of its own, then a clean one --------
        t = clock()

        def settle(what: str):
            wait(lambda: accounted() >= relay.sent_votes, what)

        def on_relay(what: str, fn, *args):
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

        fault_rows = []
        comp_tail0 = compiles.n
        h = played["height"] + 1
        settle("the window's last late precommits")
        unaccounted_window = accounted() - relay.sent_votes
        for kind in FAULTS:
            hd = chain.heights[h - 1]
            vals, bid = hd.vals, hd.block.id
            node_i = chain.node(h)
            ref = rr.Height(vals, spec.chain_id, h, hd.block.time_ns)
            reg_a = prog_metrics.summary()
            ev_before = len(node["evidence_pool"].pending_evidence(1 << 30))
            entered(h, f"height {h}")
            on_relay("the proposal", relay.proposal, hd.proposal, hd.parts)
            prevotes, precommits = list(hd.prevotes), list(hd.precommits)
            ref_prevotes = [chain.vote(rr.PREVOTE, h, i)
                            for i in hd.prevote_order]
            ref_precommits = [chain.vote(rr.PRECOMMIT, h, i)
                              for i in hd.precommit_order]
            held_without_commit = None
            if kind == "tampered_prevote":
                k = n_co // 2                   # mid-flood
                ref_prevotes[k] = rr.tampered(ref_prevotes[k])
                prevotes[k] = rr.vote_wire(vals, ref_prevotes[k])
            elif kind == "double_precommit":
                # where, with the node's own, a third of the power has
                # precommitted: before the 2/3 point whatever the order
                k, power = 0, vals.powers[node_i]
                while power + vals.powers[hd.precommit_order[k]] \
                        < vals.total_power // 3:
                    power += vals.powers[hd.precommit_order[k]]
                    k += 1
                second = chain.vote(rr.PRECOMMIT, h, hd.precommit_order[k],
                                    lr.other_block_id(ctx.seed, h))
                ref_precommits.insert(k + 1, second)
                precommits.insert(k + 1, rr.vote_wire(vals, second))
            on_relay("the prevotes", relay.votes, prevotes)
            for v in ref_prevotes:
                ref.deliver(v)
            ref.own(rr.PREVOTE, bid, node_i)
            if ref.polka() == bid:
                ref.own(rr.PRECOMMIT, bid, node_i)
            if kind == "starved_precommits":
                # with the node's own, at most 2/3 of the power: the next
                # precommit commits the block
                k = st.starved_prefix(vals, hd.precommit_order, node_i)
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
                held_without_commit = held_without_commit and \
                    ref.commit_at == k + 1
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
        tally_off = tally_low = sets_differ = 0
        for hh in window:
            hd = chain.heights[hh - 1]
            vals = hd.vals
            needed = vals.total_power * 2 // 3
            wrong_hash += by_height.get(hh) != hd.block.hash
            for k in (hh + 1, hh + 2):
                stored = state_store.load_validators(k)
                sets_differ += stored is None or \
                    stored.encode() != plan.sets[k].encode(keys)
            power, bits = watch.tallies.get(hh, (None, None))
            if power is None:
                tally_off += 1
            else:
                tally_off += power != st.vote_set_power(
                    vals, bits.true_indices())
                tally_low += power <= needed
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
                # the plain protocol on this height's votes, one at a time,
                # in the order the relay sent them
                node_i = chain.node(hh)
                ref = rr.Height(vals, spec.chain_id, hh, hd.block.time_ns)
                for i in hd.prevote_order:
                    ref.deliver(chain.vote(rr.PREVOTE, hh, i))
                ref.own(rr.PREVOTE, hd.block.id, node_i)
                ref.own(rr.PRECOMMIT, hd.block.id, node_i)
                for i in hd.precommit_order:
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
        checks.at_most("window_stored_sets_differ", sets_differ, 0)
        checks.at_most("window_vote_set_power_off", tally_off, 0)
        checks.at_most("window_vote_set_power_not_above_two_thirds",
                       tally_low, 0)

        delta = readers.registry_delta(reg1, reg0)
        r = readers.Readings(
            clock={"chip_reach_s": chip_reach_s, "datagen_s": datagen_s,
                   "warm_s": warm_s, "height_interval_s": intervals},
            counters={"program_counter": delta}, trace=trace,
            window_s=window_s, device_kind=device["kind"])

        def in_window(name, field="value", labels=None):
            return counted(name, field, labels, delta)
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
        # the fused tally carried real powers past its first limb
        checks.at_least("window_tally_lanes_past_limb_0", in_window(
            "crypto_tally_power_lanes_total", labels="limbs=more$"), 1)
        # the device path
        checks.at_most("compiles_in_window", comp1 - comp0, 0)
        checks.at_most("compiles_after_start",
                       compiles.n - compiles_at_start, 0)
        checks.at_most("fault_compiles", tail_compiles, 0)
        checks.at_most("vote_flush_shapes_warmed", len(warmed), 3)
        checks.at_most("cpu_fallback_lanes",
                       in_window("crypto_cpu_fallback_total"), 0)
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

        # the update path over the whole run: the kinds the node applied,
        # each join and leave two heights on, the app's table
        want_kinds = {"power": 0, "join": 0, "leave": 0}
        for hh in range(1, final_height + 1):
            members = {m.key for m in plan.sets[hh + 1].members}
            for k, power in plan.updates[hh]:
                want_kinds["leave" if power == 0 else
                           "power" if k in members else "join"] += 1
        updates_off = sum(abs(counted(
            "state_validator_updates_total$", labels=f"kind={kind}$",
            table=whole) - n) for kind, n in want_kinds.items())
        checks.at_most("validator_updates_off", updates_off, 0)
        checks.at_least("joins_applied", want_kinds["join"], 1)
        moved_late = 0
        stored = {}

        def stored_keys(k):
            if k not in stored:
                vs = state_store.load_validators(k)
                stored[k] = {bytes(v.address) for v in vs.validators} \
                    if vs is not None else None
            return stored[k]
        moves = [(key, hh, True) for key, hh in plan.joins.items()] + \
            [(key, hh, False) for key, hh in plan.leaves.items()]
        for key, hh, joined in moves:
            if hh > final_height:
                continue
            before, after = stored_keys(hh + 1), stored_keys(hh + 2)
            addr = keys.addrs[key]
            moved_late += before is None or after is None or \
                (addr in before) == joined or (addr in after) != joined
        checks.at_most("joins_and_leaves_not_at_h_plus_2", moved_late, 0)
        app = chain.app(final_height)
        query = node["proxy_app"].query
        table_off = 0
        for pub in keys.pubs:
            got = bytes(query.query_sync(abci.RequestQuery(
                path="/val", data=rb._msg(1, pub))).value)
            table_off += got != (st.validator_update(pub, app.validators[pub])
                                 if pub in app.validators else b"")
        checks.at_most("app_validator_table_differs", table_off, 0)

        state = cs.state
        checks.at_most("final_height_off", abs(
            state.last_block_height - final_height), 0)
        checks.at_most("final_app_hash_differs", int(
            bytes(state.app_hash) != chain.tips[final_height].app_hash), 0)
        keys_read = rng.sample(sorted(app.state), min(int(mix["readback"]),
                                                      len(app.state)))
        checks.at_most("readback_wrong", sum(
            1 for k in keys_read if bytes(query.query_sync(
                abci.RequestQuery(data=k)).value) != app.state[k]), 0)
        checks.at_least("readback_sampled", len(keys_read),
                        min(int(mix["readback"]), 1))
        late = in_window("consensus_votes_added_total",
                         labels="type=late_precommit")
        joins = in_window("state_validator_updates_total",
                          labels="kind=join$")
        print(f"live_rounds_staking: {int(flushes)} vote flushes in the "
              f"window, {int(late)} late precommits, {int(joins)} joins; "
              f"updates over the run {want_kinds}; "
              f"final height {final_height}; the faults took "
              f"{faults_s:.1f}s, the checks {clock() - t:.1f}s; none of it "
              f"in setup_s", file=sys.stderr, flush=True)
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

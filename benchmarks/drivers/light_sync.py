"""Driver ``light_sync``: a light client that proves every header of a
chain since its trust root, in the process that holds the chip.

It builds the client ``tmtpu light --sequential`` builds, through the same
function (``light/client.py open_client``: ``LightStore`` on SQLite under a
temporary home, sequential mode, the defaults for everything else), and
stands in for the network with in-process providers, a primary and one
witness: a provider holds each light block as the protobuf bytes made before
the window (reference/light.py) and decodes them at every fetch, as a real
one must. The driver calls ``verify_light_block_at_height(last trusted +
session_blocks)`` in a closed loop, one call a session, and nothing below it.

The window (the rule of benchmarks/README.md) opens at the end of the last
of ``warm_sessions`` sessions and closes at the end of the first session
that ends after ``--seconds``: whole sessions only, each its runs' fetches
and fused verifies, then the witness, then its stores. ``verify_sigs_per_s``
= the for-block signatures (167 a header here) of the headers trusted
between ÷ the time between. If the served head is reached first the window
closes there and a check fails. ``setup_s`` runs to the window's opening:
fabrication, the client's start (the trust root's check and the run shape's
warm-up) and the warm sessions included. With ``--trace 1`` the window is
``trace_seconds`` long and all of it is profiled.

``correct`` (exact counts, limit 0): the heights every session trusted and
the heights the store holds at the end equal the plain reference's
(reference/light.py ``Sync``, which re-verifies the signatures of
``reference_sample`` sessions one at a time), ``readback`` stored light
blocks are the reference's bytes; the program's counters equal the driver's
counts (headers, sessions, runs, provider calls); nothing compiled after the
first fetch that follows the client's start; no forbidden fallback lane,
every dispatch on ``tpu/pallas`` and at the one run shape. Then four faults,
each in a session of its own from a liar of its own — a signature tampered
after the 2/3 point mid-run, a commit starved to exactly 2/3, a header whose
``validators_hash`` breaks the link, a witness that proves another header at
the target (evidence must reach the primary): the refusal's height and kind,
the unchanged last trusted height and the evidence equal the reference's,
and the same session from honest providers is trusted after each.
"""

from __future__ import annotations

import gc
import os
import random
import shutil
import sys
import tempfile
import time

from benchmarks.lib import devtrace, readers, tracered
from benchmarks.lib.gates import FORBIDDEN_FALLBACKS
from benchmarks.lib.report import Checks
from benchmarks.lib.result import RunResult
from benchmarks.reference import light as rl

FAULTS = ("tampered", "starved", "broken_link", "witness_fork")
FORK_BLOCKS = 20        # headers of a session's end that the witness forks
REASONS = (("wrong signature", rl.BAD_SIGNATURE),
           ("insufficient voting power", rl.LOW_POWER),
           ("expected old header next validators", rl.BROKEN_LINK))


class Serving:
    """One provider of the client: it holds light blocks by height as
    protobuf bytes and decodes one at every fetch. ``lie`` overlays what a
    liar serves in some heights' place."""

    def __init__(self, name: str, wire: dict, compiles=None):
        from tmtpu.light.provider import ErrLightBlockNotFound
        from tmtpu.types import pb
        from tmtpu.types.light_block import LightBlock

        self._decode = lambda raw: LightBlock.from_proto(
            pb.LightBlock.decode(raw))
        self._not_found = ErrLightBlockNotFound
        self.name = name
        self.wire = wire
        self.lie = {}
        self.reported = []
        self.compiles = compiles
        self.first_fetch = None     # (t, compilations so far)

    def id(self) -> str:
        return self.name

    def light_block(self, height):
        if self.first_fetch is None and self.compiles is not None:
            self.first_fetch = (time.perf_counter(), self.compiles.n)
        raw = self.lie.get(height) or self.wire.get(
            max(self.wire) if height is None else height)
        if raw is None:
            raise self._not_found(f"height {height}")
        return self._decode(raw)

    def report_evidence(self, ev) -> None:
        self.reported.append(ev)


def refusal(err) -> tuple:
    """What a caller of the client can tell of a refused session ->
    (height refused, kind)."""
    from tmtpu.light.client import ErrLightClientAttack
    from tmtpu.light.verifier import ErrVerificationFailed

    if isinstance(err, ErrLightClientAttack):
        return (err.evidence[0].conflicting_block.height(),
                rl.CONFLICTING_WITNESS)
    if isinstance(err, ErrVerificationFailed):
        why = str(err.reason)
        return (err.to_height,
                next((k for text, k in REASONS if text in why), why))
    return (None, f"{type(err).__name__}: {err}")


def fault_plan(kind: str, spec: rl.ChainSpec, chain, base: int,
               session_blocks: int, run_blocks: int, seed: int):
    """What the liars of one fault serve in a session on top of ``base``
    -> ({height: LightBlock} of the primary, the same of the witness,
    the height at fault)."""
    target = base + session_blocks
    # mid-run, in the second run where the session has one
    at = base + min(session_blocks - 1, run_blocks + run_blocks // 2 + 1)
    if kind == "tampered":
        return {at: rl.tampered(chain[at - 1], seed)}, {}, at
    if kind == "starved":
        return {at: rl.starved(chain[at - 1], seed)}, {}, at
    if kind == "broken_link":
        return rl.fork(spec, chain, at, 1, valset_seed=seed ^ 0xBAD), {}, at
    n = min(FORK_BLOCKS, session_blocks - 1)
    return {}, rl.fork(spec, chain, target - n + 1, n), target


def run(ctx) -> RunResult:
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    clock = time.perf_counter
    assumed = cfg["assumed"]
    n_val, n_absent = int(cfg["validators"]), int(assumed["absent_per_commit"])
    n_chain = int(mix["chain_blocks"])
    # the configuration's own value, where it states one (a toy size)
    session_blocks = int(assumed.get("session_blocks", mix["session_blocks"]))
    warm_sessions = int(mix["warm_sessions"])
    seconds = min(ctx.seconds, float(mix["trace_seconds"])) if ctx.trace \
        else ctx.seconds
    if cfg["mode"] != "sequential" or int(cfg["witnesses"]) != 1 or \
            int(mix["adversarial_sessions"]) != len(FAULTS):
        raise SystemExit("light_sync plays a sequential client with one "
                         f"witness and {len(FAULTS)} faults")

    from tmtpu.light import client as light_client

    if not hasattr(light_client, "open_client"):
        # before a signature is made: a program without the entry fails soon
        raise SystemExit("light_sync: this program has no "
                         "light/client.py open_client to build the client "
                         "of `tmtpu light --sequential` with")

    # -- the chain, from the seed, signed in worker processes ---------------
    t = clock()
    spec = rl.ChainSpec(ctx.seed, cfg["chain_id"],
                        int(cfg["genesis_time_ns"]), n_val,
                        int(assumed["voting_power"]), n_absent,
                        int(assumed["block_interval_ns"]),
                        int(cfg["app_version"]))
    _vals, chain = rl.make_chain(
        spec, n_chain, min(int(mix["datagen_workers"]), os.cpu_count() or 1))
    wire = {lb.height: lb.wire for lb in chain}
    now_ns = chain[-1].header.time_ns + int(cfg["now_after_head_ns"])
    datagen_s = clock() - t

    # -- reach the chip -----------------------------------------------------
    t = clock()
    from tmtpu.blocksync.common import run_shape
    from tmtpu.config.config import CryptoConfig
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.libs import metrics as prog_metrics

    if int(cfg["pruning_size"]) != light_client.DEFAULT_PRUNING_SIZE or \
            tuple(cfg["trust_level"]) != light_client.DEFAULT_TRUST_LEVEL or \
            cfg["program"]["db_backend"] != "sqlite":
        raise SystemExit("the configuration states another pruning size, "
                         "trust level or store than open_client's")
    crypto_batch.configure(CryptoConfig(**cfg["program"]["crypto"]))
    crypto_batch.set_default_backend(cfg["program"]["crypto_backend"])
    crypto_batch.start_backend(cfg["program"]["crypto_backend"],
                               "benchmarks/run.py")
    device = devtrace.device_facts()
    ctx.check_device(device)
    compiles = devtrace.CompileCount()
    chip_reach_s = clock() - t

    work = tempfile.mkdtemp(prefix="bench-light-")
    try:
        # -- the client: the root's check, then the run shape's warm-up ------
        t = clock()
        primary = Serving("primary", wire, compiles)
        witness = Serving("witness", wire)
        client = light_client.open_client(
            os.path.join(work, "home"), cfg["chain_id"],
            light_client.TrustOptions(int(cfg["trusting_period_ns"]), 1,
                                      chain[0].header.hash),
            primary, [witness], sequential=True)
        run_blocks, run_lanes = run_shape(client.trusted_light_block(1)
                                          .validator_set)
        primary.first_fetch = None      # what follows the client's start
        warm_s = clock() - t
        gc.collect()
        gc.freeze()     # the chain's objects are not walked inside the window

        sessions = []   # (target, end, heights trusted, error)

        def session():
            base = client.last_trusted_height()
            target, err = base + session_blocks, None
            try:
                client.verify_light_block_at_height(target, now_ns)
            except Exception as e:  # noqa: BLE001 — compared, not hidden
                err = e
            sessions.append((target, clock(), list(range(
                base + 1, client.last_trusted_height() + 1)), err))
            return err

        def edge():
            return (clock(), client.last_trusted_height(),
                    prog_metrics.summary(), compiles.n)

        for _ in range(warm_sessions):
            if session() is not None:
                raise SystemExit(f"light_sync: a warm session was refused: "
                                 f"{sessions[-1][3]!r}")

        # -- the window: whole sessions ---------------------------------------
        # each fault and the good session after it play the same heights:
        # len(FAULTS) sessions' worth of chain beyond the window's last
        last_start = n_chain - (len(FAULTS) + 1) * session_blocks
        tracer = None
        if ctx.trace:
            tracer = devtrace.Tracer(emulated=not ctx.require_chip)
            tracer.start()
        t_open, h_open, reg0, comp0 = edge()
        first_window_session = len(sessions)
        cut_at_tip = 0
        while True:
            if client.last_trusted_height() > last_start:
                cut_at_tip = 1
                break
            if session() is not None or clock() - t_open >= seconds:
                break
        t_close, h_close, reg1, comp1 = edge()
        trace = tracer.stop() if tracer else None
        setup_s = t_open - ctx.t_start
        window_s = t_close - t_open
        device["memory_peak_bytes"] = devtrace.memory_peak_bytes()
        n_blocks = h_close - h_open
        n_sessions = len(sessions) - first_window_session
        sigs = sum(chain[h - 1].commit.present()
                   for h in range(h_open + 1, h_close + 1))
        print(f"light_sync: window {window_s:.3f}s, headers {h_open + 1}.."
              f"{h_close} ({n_blocks}) in {n_sessions} sessions, {sigs} "
              f"signatures; set-up: data {datagen_s:.1f}s chip "
              f"{chip_reach_s:.1f}s warm {warm_s:.1f}s", file=sys.stderr,
              flush=True)
        ends = [t_open] + [s[1] for s in sessions[first_window_session:]]
        took = sorted(b - a for a, b in zip(ends, ends[1:]))
        if took:
            print(f"light_sync: a session p50 {1000 * took[len(took) // 2]:.1f}"
                  f" ms, longest {1000 * took[-1]:.1f} ms, shortest "
                  f"{1000 * took[0]:.1f} ms", file=sys.stderr, flush=True)

        # -- the reference's sync of what was served so far ------------------
        t = clock()
        rng = random.Random(ctx.seed ^ 0xC0FFEE)
        sampled = rng.sample(range(len(sessions)),
                             min(int(mix["reference_sample"]), len(sessions)))
        verify_at = {h for i in sampled
                     for h in range(sessions[i][0] - session_blocks + 1,
                                    sessions[i][0] + 1)}
        verify_at |= set(range(h_close + 1, n_chain + 1))   # the faults'
        sync = rl.Sync(cfg["chain_id"], chain[0],
                       int(cfg["trusting_period_ns"]),
                       light_client.DEFAULT_MAX_CLOCK_DRIFT_NS,
                       int(cfg["pruning_size"]), verify_at=verify_at)
        honest = lambda h: chain[h - 1]  # noqa: E731
        checks = Checks()
        differ = 0
        for target, _end, trusted, err in sessions:
            want = sync.session(honest, target, now_ns, witness=honest)
            differ += err is not None or want.refused is not None or \
                want.trusted != trusted
        checks.at_most("sessions_differ_from_reference", differ, 0)
        checks.at_least("sessions_reverified_serially", len(sampled),
                        min(int(mix["reference_sample"]), 1))

        # -- the faults, each in a session of its own, a liar of its own -----
        fault_rows, regs = [], []
        comp_tail0 = compiles.n
        for kind in FAULTS:
            base = client.last_trusted_height()
            lie_p, lie_w, at = fault_plan(kind, spec, chain, base,
                                          session_blocks, run_blocks,
                                          ctx.seed)
            want = sync.session(
                lambda h: lie_p.get(h) or chain[h - 1], base + session_blocks,
                now_ns, witness=lambda h: lie_w.get(h) or chain[h - 1])
            liar_p = Serving(f"liar-primary-{kind}", wire)
            liar_w = Serving(f"liar-witness-{kind}", wire)
            liar_p.lie = {h: lb.wire for h, lb in lie_p.items()}
            liar_w.lie = {h: lb.wire for h, lb in lie_w.items()}
            client.primary, client.witnesses = liar_p, [liar_w]
            reg_a = prog_metrics.summary()
            err = session()
            reg_b = prog_metrics.summary()
            client.primary, client.witnesses = primary, [witness]
            got = refusal(err) if err is not None else None
            row = {"kind": kind, "refused": got, "want": want.refused,
                   "trusted_after": client.last_trusted_height(),
                   "want_trusted_after": sync.last.height,
                   "evidence": len(liar_p.reported),
                   "want_evidence": want.evidence_to_primary}
            print(f"fault {kind}: program refused={got} last trusted="
                  f"{row['trusted_after']} evidence to primary="
                  f"{row['evidence']} | reference refused={want.refused} "
                  f"last trusted={sync.last.height} evidence="
                  f"{want.evidence_to_primary}", flush=True)
            # the runs before the one at fault went to the device whole
            whole_runs = (at - base - 1) // run_blocks
            regs.append((kind, readers.registry_delta(reg_b, reg_a),
                         whole_runs * run_blocks * (n_val - n_absent)))
            # the same session from the honest providers
            good_err = session()
            want_good = sync.session(honest, base + session_blocks, now_ns,
                                     witness=honest)
            row["good"] = good_err is None and want_good.refused is None \
                and want_good.trusted == sessions[-1][2] \
                and client.last_trusted_height() == base + session_blocks
            fault_rows.append(row)
        tail_compiles = compiles.n - comp_tail0
        reference_s = clock() - t

        # -- correct ------------------------------------------------------------
        t = clock()
        checks.at_least("window_sessions", n_sessions, 1)
        checks.at_most("window_cut_at_tip", cut_at_tip, 0)
        checks.at_most("commits_off_size", sum(
            1 for h in range(h_open + 1, h_close + 1)
            if chain[h - 1].commit.present() != n_val - n_absent), 0)
        delta = readers.registry_delta(reg1, reg0)
        r = readers.Readings(
            clock={"chip_reach_s": chip_reach_s, "datagen_s": datagen_s,
                   "warm_s": warm_s},
            counters={"program_counter": delta}, trace=trace,
            window_s=window_s, device_kind=device["kind"])

        def counted(name, field="value", labels=None, table=delta):
            term = {"source": "program_counter", "name": name, "field": field}
            if labels:
                term["labels"] = labels
            return readers.term_value(term, "", readers.Readings(
                counters={"program_counter": table})) or 0

        n_runs = n_sessions * -(-session_blocks // run_blocks)
        checks.at_most("verified_counter_off", abs(counted(
            "light_blocks_verified_total") - n_blocks), 0)
        checks.at_most("sessions_counter_off", abs(counted(
            "light_sessions_total") - n_sessions), 0)
        checks.at_most("provider_calls_off", abs(counted(
            "light_provider_calls_total", labels="role=primary") - n_blocks)
            + abs(counted("light_provider_calls_total",
                          labels="role=witness") - n_sessions), 0)
        checks.at_most("runs_off_size", abs(counted(
            "light_run_blocks$", "count") - n_runs) + abs(counted(
                "light_run_blocks$", "sum") - n_blocks), 0)
        checks.at_most("compiles_in_window", comp1 - comp0, 0)
        checks.at_most("compiles_after_first_fetch",
                       compiles.n - primary.first_fetch[1], 0)
        # the device path: every run one dispatch, of its own signatures,
        # padded to the one shape the client warmed
        checks.at_most("forbidden_fallback_lanes", counted(
            "crypto_cpu_fallback_total", labels=FORBIDDEN_FALLBACKS), 0)
        checks.at_most("cpu_fallback_lanes",
                       counted("crypto_cpu_fallback_total"), 0)
        def off_kernel(table):
            return counted("crypto_verify_latency_seconds", "count",
                           table=table) - counted(
                "crypto_verify_latency_seconds", "count",
                "backend=tpu,impl=pallas$", table)

        if ctx.require_chip:
            checks.at_most("dispatches_off_kernel", off_kernel(delta), 0)
        checks.at_most("dispatches_off_count", abs(counted(
            "crypto_verify_latency_seconds", "count") - n_runs), 0)
        checks.at_most("lanes_dispatched_off", abs(counted(
            "crypto_batch_size$", "sum") - sigs), 0)
        from tmtpu.tpu import dispatch as prog_dispatch

        shape = prog_dispatch._pad_to_bucket(run_lanes)
        want_pad = n_sessions * sum(
            shape / (min(run_blocks, session_blocks - i) * (n_val - n_absent))
            for i in range(0, session_blocks, run_blocks))
        checks.at_most("dispatches_off_shape", int(
            counted("crypto_pad_ratio", "count") != n_runs
            or abs(counted("crypto_pad_ratio", "sum") - want_pad)
            > 1e-6 * want_pad), 0)
        print(f"light_sync: run shape (headers, lanes) "
              f"{(run_blocks, run_lanes)}", file=sys.stderr, flush=True)

        differ = trusted_differ = evidence_differ = good_missing = 0
        for row in fault_rows:
            differ += row["refused"] != row["want"] or row["want"] is None
            trusted_differ += row["trusted_after"] != \
                row["want_trusted_after"]
            evidence_differ += row["evidence"] != row["want_evidence"]
            good_missing += not row["good"]
        checks.at_most("fault_outcomes_differ", differ, 0)
        checks.at_most("fault_last_trusted_differ", trusted_differ, 0)
        checks.at_most("fault_evidence_differs", evidence_differ, 0)
        checks.at_most("fault_good_session_not_trusted", good_missing, 0)
        for kind, table, lanes_due in regs:
            checks.at_least(f"fault_{kind}_lanes_dispatched", counted(
                "crypto_batch_size$", "sum", "backend=tpu$"
                if ctx.require_chip else None, table), lanes_due)
            checks.at_most(f"fault_{kind}_forbidden_fallback_lanes", counted(
                "crypto_cpu_fallback_total", labels=FORBIDDEN_FALLBACKS,
                table=table), 0)
            if ctx.require_chip:
                checks.at_most(f"fault_{kind}_dispatches_off_kernel",
                               off_kernel(table), 0)
        checks.at_most("fault_compiles", tail_compiles, 0)

        # what the store holds at the end, byte for byte
        held = {int(k[3:]): v for k, v in client.store.db.iter_prefix(b"lb/")}
        checks.at_most("store_heights_differ", int(
            sorted(held) != sorted(sync.stored)), 0)
        heights = rng.sample(sorted(sync.stored),
                             min(int(mix["readback"]), len(sync.stored)))
        checks.at_most("readback_wrong", sum(
            1 for h in heights if held.get(h) != sync.stored[h]), 0)
        checks.at_least("readback_sampled", len(heights),
                        min(int(mix["readback"]), 1))
        checks.at_most("final_height_off", abs(
            client.last_trusted_height() - sync.last.height), 0)
        print(f"light_sync: final height {client.last_trusted_height()}, "
              f"store holds {len(held)}; the reference's sync took "
              f"{reference_s:.1f}s, the checks {clock() - t:.1f}s; none of it "
              f"in setup_s", file=sys.stderr, flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    return RunResult(
        checks=checks, attempted=n_sessions,
        failed=sum(1 for s in sessions[first_window_session:
                                       first_window_session + n_sessions]
                   if s[3] is not None),
        end_to_end={"verify_sigs_per_s": sigs / window_s if window_s else 0.0,
                    "setup_s": setup_s},
        device=device, readings=r,
        breakdown=tracered.breakdown(trace) if trace else None)

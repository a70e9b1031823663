"""Driver ``commit_verify``: ``types/commit_verify.verify_commit`` called
back to back on distinct commits over one validator set, by a single
caller, in the process that holds the chip.

The window opens at the start of a call and closes at the end of the call
in flight when ``--seconds`` have passed; the rate is the signatures of
the calls the entry accepted over (last end - first start). The seed
chooses which validators are absent, never how many.

``correct``: once the window has closed, a sample of the window's commits
(drawn from the seed) and three adversarial commits, each made from a
fresh commit of its own (a signature tampered before the 2/3 point, one
after it, and a commit whose nil votes leave too little power), go
through the same entry, and every outcome — accepted, refused at which
lane, refused with which tally — has to equal the plain serial
reference's (reference/commits.py). The program's sigcache keeps every
valid lane of a commit it refuses, so two adversarial commits cut from one
fresh commit would send the second down the serial small-batch path and
never to the device; the counters over the adversarial calls are held to
every lane of all three dispatched, with nothing compiled for them: the
window's one program.
"""

from __future__ import annotations

import contextlib
import gc
import random
import re
import sys
import time

from benchmarks.lib import devtrace, gates, readers, tracered
from benchmarks.lib.report import Checks
from benchmarks.lib.result import RunResult
from benchmarks.reference import commits as ref

def _program_commit(c: ref.CommitData, vals: ref.ValSet):
    from tmtpu.types.block import BlockID, Commit, CommitSig

    bid = BlockID(hash=c.block_hash, parts_total=c.parts_total,
                  parts_hash=c.parts_hash)
    sigs = [CommitSig(flag, vals.addrs[i] if flag != ref.ABSENT else b"",
                      ts, sig) for i, (flag, ts, sig) in enumerate(c.sigs)]
    return bid, c.height, Commit(c.height, c.round, bid, sigs)


def call_entry(pvals, chain_id, pc) -> tuple:
    """One call of the timed entry -> an outcome in the reference's
    terms (reference/commits.py ``Outcome``)."""
    from tmtpu.types import commit_verify as cv

    bid, height, commit = pc
    try:
        cv.verify_commit(pvals, chain_id, bid, height, commit)
    except cv.ErrNotEnoughVotingPowerSigned as e:
        return ("low_power", e.got, e.needed)
    except cv.VerificationError as e:
        m = re.search(r"#(\d+)", str(e))
        return ("bad_sig", int(m.group(1)) if m else None)
    return ("ok",)


def adversarial(vals: ref.ValSet, seed: int, k: int, chain_id: str,
                n_absent: int):
    """Three commits (numbers k, k+1, k+2 of the seed) that share no
    signature with a window commit or with each other, so that no lane of
    any is in the sigcache. -> [(label, CommitData)]"""
    rng = random.Random(seed ^ 0x5EED)
    n = len(vals.pubs)

    def tampered(kk: int, in_last_third: bool) -> ref.CommitData:
        fresh = ref.make_commit(vals, seed, kk, chain_id, n_absent)
        present = [i for i, s in enumerate(fresh.sigs) if s[0] != ref.ABSENT]
        third = len(present) // 3
        at = rng.randrange(len(present) - third, len(present)) \
            if in_last_third else rng.randrange(0, third)
        return ref.tamper_signature(fresh, present[at])

    # votes for the block stop 5% short of the 2/3 that is needed
    for_block = (n * 2 // 3) * 95 // 100
    n_nil = max(1, (n - n_absent) - for_block)
    return [("tampered_early", tampered(k, False)),
            ("tampered_late", tampered(k + 1, True)),
            ("nil_heavy", ref.make_commit(vals, seed, k + 2, chain_id,
                                          n_absent, n_nil))]


def run(ctx) -> RunResult:
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    clock = time.perf_counter
    n_val = int(cfg["validators"])
    n_absent = int(cfg["assumed"]["absent_per_commit"])
    chain_id = cfg["chain_id"]
    n_commits = int(mix["distinct_commits"])

    # -- reach the chip -----------------------------------------------------
    t = clock()
    from tmtpu.config.config import CryptoConfig
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.crypto import ed25519 as prog_ed
    from tmtpu.libs import metrics as prog_metrics
    from tmtpu.types.validator import Validator, ValidatorSet

    crypto_batch.configure(CryptoConfig(**cfg["program"]["crypto"]))
    crypto_batch.set_default_backend(cfg["program"]["crypto_backend"])
    crypto_batch.start_backend(cfg["program"]["crypto_backend"],
                               "benchmarks/run.py")
    import jax

    device = devtrace.device_facts()
    ctx.check_device(device)
    compiles = devtrace.CompileCount()
    chip_reach_s = clock() - t

    # -- data from the seed -------------------------------------------------
    # (made in a thread beside the step above it gained nothing: the two
    # contend for the interpreter, 42 s of set-up against 40; my chip
    # runs, PR 25)
    t = clock()
    vals = ref.make_valset(ctx.seed, n_val, int(cfg["assumed"]["voting_power"]))
    pvals = ValidatorSet([Validator(prog_ed.PubKeyEd25519(p), pw)
                          for p, pw in zip(vals.pubs, vals.powers)])
    if [v.address for v in pvals.validators] != vals.addrs:
        raise SystemExit("the program orders the validator set otherwise "
                         "than the reference does")
    window_commits = [ref.make_commit(vals, ctx.seed, k, chain_id, n_absent)
                      for k in range(n_commits)]
    warm_commits = [ref.make_commit(vals, ctx.seed, n_commits + k, chain_id,
                                    n_absent) for k in range(2)]
    pcs = [_program_commit(c, vals) for c in window_commits]
    datagen_s = clock() - t

    # -- warm the one shape this cell flushes, through the entry itself ----
    t = clock()
    for c in warm_commits:
        got = call_entry(pvals, chain_id, _program_commit(c, vals))
        if got != ("ok",):
            raise SystemExit(f"warm-up verify_commit gave {got}")
    warm_s = clock() - t

    gc.collect()
    gc.freeze()     # set-up's objects are not walked inside the window

    tracer = None
    if ctx.trace:
        tracer = devtrace.Tracer(emulated=not ctx.require_chip)
        tracer.start()
    span = jax.profiler.TraceAnnotation if ctx.trace else \
        (lambda _name: contextlib.nullcontext())
    seconds = min(ctx.seconds, float(mix["trace_seconds"])) if ctx.trace \
        else ctx.seconds

    # -- the window ---------------------------------------------------------
    reg0 = prog_metrics.summary()
    compiles0 = compiles.n
    calls = []          # (start, end, commit index, outcome)
    setup_s = clock() - ctx.t_start
    t_open = clock()
    i = 0
    while True:
        s = clock()
        with span("bench.verify_commit"):
            got = call_entry(pvals, chain_id, pcs[i % n_commits])
        e = clock()
        calls.append((s, e, i % n_commits, got))
        i += 1
        if e - t_open >= seconds:
            break
    t_close = calls[-1][1]
    compiles_in_window = compiles.n - compiles0
    reg1 = prog_metrics.summary()
    trace = tracer.stop() if tracer else None
    device["memory_peak_bytes"] = devtrace.memory_peak_bytes()

    window_s = t_close - t_open
    durs = sorted(e - s for s, e, _k, _o in calls)
    print(f"commit_verify: window {window_s:.3f}s, {len(calls)} calls, call "
          f"min/p50/max {durs[0]:.4f}/{durs[len(durs) // 2]:.4f}/"
          f"{durs[-1]:.4f}s; set-up: chip {chip_reach_s:.1f}s data "
          f"{datagen_s:.1f}s warm {warm_s:.1f}s", file=sys.stderr, flush=True)
    accepted = [c for c in calls if c[3] == ("ok",)]
    sigs_accepted = sum(window_commits[c[2]].present() for c in accepted)
    # what a shorter window of the same process would have read: the rate
    # up to the first call that ends 10, 20, ... seconds in
    shorter, sigs, due = [], 0, 10
    for _s, e, k, got in calls[:-1]:
        sigs += window_commits[k].present() * (got == ("ok",))
        if e - t_open >= due:
            shorter.append(f"{due}s={sigs / (e - t_open):.1f}")
            due += 10
    print(f"commit_verify: rate by window length {' '.join(shorter)} "
          f"full={sigs_accepted / window_s:.1f}", file=sys.stderr, flush=True)

    # -- correct ------------------------------------------------------------
    t = clock()
    checks = Checks()
    rng = random.Random(ctx.seed ^ 0xC0FFEE)
    called = sorted({c[2] for c in calls})
    sample = rng.sample(called, min(len(called), int(mix["reference_sample"])))
    want = {k: ref.verify_commit(vals, window_commits[k]) for k in sample}
    checks.at_most("window_outcomes_differ",
                   sum(1 for c in calls if c[2] in want and c[3] != want[c[2]]),
                   0)
    checks.at_most("window_calls_refused", len(calls) - len(accepted), 0)
    checks.at_most("commits_off_size", sum(
        1 for c in window_commits if c.present() != n_val - n_absent), 0)
    differ, tally_gap = 0, None
    hostile = adversarial(vals, ctx.seed, n_commits + 2, chain_id, n_absent)
    for label, c in hostile:
        got = call_entry(pvals, chain_id, _program_commit(c, vals))
        exp = ref.verify_commit(vals, c)
        print(f"adversarial {label}: program={got} reference={exp}",
              flush=True)
        differ += got != exp
        if exp[0] == "low_power":
            tally_gap = abs(got[1] - exp[1]) if got[0] == "low_power" \
                else exp[1]
    reg2 = prog_metrics.summary()
    print(f"commit_verify: reference check took {clock() - t:.1f}s, not in "
          f"setup_s", file=sys.stderr, flush=True)
    checks.at_most("adversarial_outcomes_differ", differ, 0)
    checks.at_most("tally_gap", tally_gap, 0)
    checks.at_most("compiles_in_window", compiles_in_window, 0)
    delta = readers.registry_delta(reg1, reg0)
    r = readers.Readings(
        clock={"chip_reach_s": chip_reach_s, "datagen_s": datagen_s,
               "warm_s": warm_s,
               "verify_call_s": [e - s for s, e, _k, _o in calls]},
        counters={"program_counter": delta}, trace=trace,
        window_s=window_s, device_kind=device["kind"])
    gates.device_path(checks, r, ctx.require_chip, len(calls))
    # the adversarial calls came after reg1: their lanes have to reach the
    # device too, all of them, or the comparison above judged the CPU
    gates.lanes_on_device(
        checks, "adversarial", readers.Readings(counters={
            "program_counter": readers.registry_delta(reg2, reg1)}),
        ctx.require_chip, sum(c.present() for _l, c in hostile))
    checks.at_most("adversarial_compiles",
                   compiles.n - compiles0 - compiles_in_window, 0)

    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    return RunResult(
        checks=checks, attempted=len(calls),
        failed=len(calls) - len(accepted),
        end_to_end={"verify_sigs_per_s": sigs_accepted / window_s,
                    "setup_s": setup_s},
        device=device, readings=r,
        breakdown=tracered.breakdown(trace) if trace else None)

"""Driver ``commit_verify_mixed``: ``types/commit_verify.verify_commit``
called back to back on distinct commits over one validator set whose keys
are of three types (ed25519, sr25519, secp256k1 in thirds, interleaved in
the set's own order), by a single caller, in the process that holds the
chip. Every call is one flush of three device batches, one a key type.

The window rule is ``commit_verify``'s: it opens at the start of a call and
closes at the end of the call in flight when ``--seconds`` have passed; the
rate is the signatures of the calls the entry accepted, of all key types,
over (last end - first start). The seed chooses which validators of each
key type are absent, never how many: every commit carries the same number
of signatures of each type, so each type's flush meets one padded shape.

The commits are signed by ``reference/mixed_commits.py`` in worker
processes started before JAX is imported; the two warm-up commits first,
the rest while this process reaches the chip and compiles the three
kernels through the entry itself (two calls). A warm-up that left a lane on
a safety rung (a compile that outlasted the batch deadline, an open
breaker) ends the run at once: nothing is measured on a degraded path.

``correct``: once the window has closed, a sample of the window's commits
(drawn from the seed) and five adversarial commits, each cut from a fresh
commit of its own so that the sigcache can answer no lane of it (an
ed25519 signature tampered before the 2/3 point, an sr25519 one after it,
a secp256k1 one anywhere, a secp256k1 signature replaced by its high-S
twin (r, n - s), and a commit whose nil votes leave too little power —
its tally the sum of ed25519's fused device tally and two host sums), go
through the same entry, and every outcome has to equal the plain serial
reference's. Held to their limits besides, each key type on its own:
every commit at its size, no lane on a forbidden fallback, every dispatch
on ``tpu/pallas``, as many dispatches as calls, every adversarial lane
dispatched; and no compile inside the window or for the adversarial
calls, no sr25519 challenge walked in pure Python.
"""

from __future__ import annotations

import contextlib
import gc
import os
import random
import sys
import time

from benchmarks.drivers.commit_verify import _program_commit, call_entry
from benchmarks.lib import devtrace, gates, readers, tracered
from benchmarks.lib.report import Checks
from benchmarks.lib.result import RunResult
from benchmarks.lib.spec import BENCH_DIR, load_json
from benchmarks.reference import mixed_commits as ref

# per key type: the Pallas kernel's name in a device trace, and its row
# of operations per signature in lib/opcounts_curves.json
KERNELS = {ref.SR25519: (r"^_sr_verify_pallas", "sr25519_verify"),
           ref.SECP256K1: (r"^_k1_verify_pallas", "secp256k1_verify")}


def adversarial(vals: ref.ValSet, fresh: list, seed: int):
    """Five commits cut from the five ``fresh`` ones. -> [(label,
    CommitData)]"""
    rng = random.Random(seed ^ 0x5EED)

    def slot(c: ref.CommitData, curve: str, part: str) -> int:
        present = ref.present_slots(c)
        third = len(present) // 3
        span = {"early": present[:third], "late": present[-third:],
                "any": present}[part]
        return rng.choice([i for i in span if vals.curves[i] == curve])

    out = [(f"tampered_{curve}_{part}",
            ref.tamper_signature(c, slot(c, curve, part)))
           for c, curve, part in zip(fresh, ref.CURVES,
                                     ("early", "late", "any"))]
    at = slot(fresh[3], ref.SECP256K1, "any")
    out.append(("high_s_twin", ref.replace_signature(
        fresh[3], at, ref.k1_high_s_twin(fresh[3].sigs[at][2]))))
    out.append(("nil_heavy", fresh[4]))
    return out


def nil_heavy_count(n: int, n_absent: int) -> int:
    """Votes for the block stop 5% short of the 2/3 that is needed."""
    return max(1, (n - n_absent) - (n * 2 // 3) * 95 // 100)


def program_valset(vals: ref.ValSet):
    from tmtpu.crypto import ed25519, secp256k1, sr25519
    from tmtpu.types.validator import Validator, ValidatorSet

    key = {ref.ED25519: ed25519.PubKeyEd25519,
           ref.SR25519: sr25519.PubKeySr25519,
           ref.SECP256K1: secp256k1.PubKeySecp256k1}
    pvals = ValidatorSet([Validator(key[c](p), pw) for c, p, pw in
                          zip(vals.curves, vals.pubs, vals.powers)])
    if [v.address for v in pvals.validators] != vals.addrs:
        raise SystemExit("the program orders the mixed validator set, or "
                         "derives an address, otherwise than the reference")
    return pvals


def curve_gates(checks: Checks, tag: str, r: readers.Readings, on_chip: bool,
                least_dispatches: int, least_lanes: dict) -> None:
    """``lib/gates.py``'s checks, one key type at a time: a type whose
    flush quietly went serial or to the XLA graph fails here."""
    for curve in ref.CURVES:
        on = f"curve={curve},"
        checks.at_most(f"{tag}_fallback_lanes.{curve}", gates._count(
            r, "crypto_cpu_fallback_total", "value", on), 0)
        checks.at_least(f"{tag}_lanes_dispatched.{curve}", gates._count(
            r, "crypto_batch_size$", "sum",
            on + ("backend=tpu$" if on_chip else "")), least_lanes[curve])
        total = gates._count(r, "crypto_verify_latency_seconds", "count", on)
        if on_chip:
            on_kernel = gates._count(
                r, "crypto_verify_latency_seconds", "count",
                on + "backend=tpu,impl=pallas$")
            checks.at_most(f"{tag}_dispatches_off_kernel.{curve}",
                           total - on_kernel, 0)
            total = on_kernel
        checks.at_least(f"{tag}_dispatches.{curve}", total, least_dispatches)


def kernel_shares(r: readers.Readings) -> dict:
    """Each new kernel's share of the chip's integer peak, in %: the
    operations lib/opcounts_curves.json derives a signature x the lanes
    dispatched, over lib/peaks.json's peak and the kernel's device
    seconds. Nothing where the seconds or the lanes are 0, or off a chip
    the table knows."""
    peaks = load_json(os.path.join(BENCH_DIR, "lib", "peaks.json"))
    if r.trace is None or r.device_kind not in peaks:
        return {}
    ops = load_json(os.path.join(BENCH_DIR, "lib", "opcounts_curves.json"))
    out = {}
    for curve, (pattern, row) in KERNELS.items():
        seconds = readers.term_value(
            {"source": "trace_device_op", "name": pattern,
             "field": "seconds"}, "", r)
        lanes = gates._count(r, "crypto_batch_size$", "sum", f"curve={curve},")
        if seconds and lanes:
            out[f"{curve}_kernel_roofline_pct"] = 100.0 * lanes \
                * ops[row]["int_ops"] \
                / peaks[r.device_kind]["int_ops_per_s"] / seconds
    return out


def run(ctx, trust: str = "") -> RunResult:
    """``trust`` is the control of the tests: the reference takes every
    signature of that key type for good."""
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    # the workers, before JAX is imported
    pool = ref.Pool(ctx.seed, int(cfg["validators"]),
                    int(cfg["assumed"]["voting_power"]),
                    min(int(mix["datagen_workers"]), os.cpu_count() or 1))
    try:
        return _run(ctx, pool, trust)
    finally:
        pool.close()


def _run(ctx, pool: ref.Pool, trust: str) -> RunResult:
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    clock = time.perf_counter
    n_val = int(cfg["validators"])
    assumed = cfg["assumed"]
    absent = {c: int(assumed["absent_per_commit"][c]) for c in ref.CURVES}
    n_absent = sum(absent.values())
    chain_id = cfg["chain_id"]
    n_commits = int(mix["distinct_commits"])

    # -- the set; the two warm-up commits first, the rest beside us ---------
    t = clock()
    vals = ref.make_valset(ctx.seed, n_val, int(assumed["voting_power"]))

    def plan(k: int, n_nil: int = 0) -> ref.CommitData:
        return ref.plan_commit(vals, ctx.seed, k, chain_id, absent, n_nil)

    warm_commits = [plan(n_commits + k) for k in range(2)]
    pool.sign_commits(warm_commits)()
    window_commits = [plan(k) for k in range(n_commits)]
    fresh = [plan(n_commits + 2 + k) for k in range(4)] + \
        [plan(n_commits + 6, nil_heavy_count(n_val, n_absent))]
    signed = pool.sign_commits(window_commits + fresh)
    datagen_first_s = clock() - t

    # -- reach the chip -----------------------------------------------------
    t = clock()
    from tmtpu.config.config import CryptoConfig
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.libs import metrics as prog_metrics

    crypto_batch.configure(CryptoConfig(**cfg["program"]["crypto"]))
    crypto_batch.set_default_backend(cfg["program"]["crypto_backend"])
    info = crypto_batch.start_backend(cfg["program"]["crypto_backend"],
                                      "benchmarks/run.py")
    import jax

    device = devtrace.device_facts()
    ctx.check_device(device)
    compiles = devtrace.CompileCount()
    pvals = program_valset(vals)
    chip_reach_s = clock() - t

    # -- warm the three shapes this cell flushes, through the entry itself --
    t = clock()
    reg_warm = prog_metrics.summary()
    for c in warm_commits:
        got = call_entry(pvals, chain_id, _program_commit(c, vals))
        if got != ("ok",):
            raise SystemExit(f"warm-up verify_commit gave {got}")
    warmed = readers.Readings(counters={"program_counter":
                                        readers.registry_delta(
                                            prog_metrics.summary(), reg_warm)})
    off = warmed.counters["program_counter"].get(
        "tendermint_crypto_cpu_fallback_total", {})
    if sum(off.values()):
        raise SystemExit(f"warm-up left lanes on the serial path ({off}): "
                         f"the device path is degraded, not measuring")
    warm_s = clock() - t

    t = clock()
    signed()
    pcs = [_program_commit(c, vals) for c in window_commits]
    datagen_s = datagen_first_s + clock() - t

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
    print(f"commit_verify_mixed: window {window_s:.3f}s, {len(calls)} calls, "
          f"call min/p50/max {durs[0]:.4f}/{durs[len(durs) // 2]:.4f}/"
          f"{durs[-1]:.4f}s; set-up: chip {chip_reach_s:.1f}s warm "
          f"{warm_s:.1f}s data {datagen_s:.1f}s (beside the first two: "
          f"{datagen_first_s:.1f}s before, {datagen_s - datagen_first_s:.1f}s "
          f"after); native host prep bound: {info.get('native')}",
          file=sys.stderr, flush=True)
    accepted = [c for c in calls if c[3] == ("ok",)]
    sigs_accepted = sum(window_commits[c[2]].present() for c in accepted)

    # -- correct ------------------------------------------------------------
    t = clock()
    checks = Checks()
    rng = random.Random(ctx.seed ^ 0xC0FFEE)
    called = sorted({c[2] for c in calls})
    sample = rng.sample(called, min(len(called), int(mix["reference_sample"])))
    hostile = adversarial(vals, fresh, ctx.seed)
    judged = pool.verify_commits(
        vals, [window_commits[k] for k in sample] + [c for _l, c in hostile],
        trust)
    want = dict(zip(sample, judged))
    checks.at_most("window_outcomes_differ",
                   sum(1 for _s, _e, k, got in calls
                       if k in want and got != want[k]), 0)
    checks.at_most("window_calls_refused", len(calls) - len(accepted), 0)
    size = {c: len(vals.slots_of(c)) - absent[c] for c in ref.CURVES}
    sizes = [ref.present_by_curve(vals, c)
             for c in window_commits + [c for _l, c in hostile]]
    for curve in ref.CURVES:
        checks.at_most(f"commits_off_size.{curve}", sum(
            1 for got in sizes if got[curve] != size[curve]), 0)
    differ, tally_gap = 0, None
    for (label, c), exp in zip(hostile, judged[len(sample):]):
        got = call_entry(pvals, chain_id, _program_commit(c, vals))
        print(f"adversarial {label}: program={got} reference={exp}",
              flush=True)
        differ += got != exp
        if exp[0] == "low_power":
            tally_gap = abs(got[1] - exp[1]) if got[0] == "low_power" \
                else exp[1]
    reg2 = prog_metrics.summary()
    print(f"commit_verify_mixed: reference check took {clock() - t:.1f}s, "
          f"not in setup_s", file=sys.stderr, flush=True)
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
    r.clock.update(kernel_shares(r))
    gates.device_path(checks, r, ctx.require_chip,
                      len(ref.CURVES) * len(calls))
    curve_gates(checks, "window", r, ctx.require_chip, len(calls),
                {c: size[c] * len(calls) for c in ref.CURVES})
    # the adversarial calls came after reg1: their lanes have to reach the
    # device too, all of them, or the comparison above judged the CPU
    after = readers.Readings(counters={
        "program_counter": readers.registry_delta(reg2, reg1)})
    gates.lanes_on_device(checks, "adversarial", after, ctx.require_chip,
                          sum(c.present() for _l, c in hostile))
    curve_gates(checks, "adversarial", after, ctx.require_chip, len(hostile),
                {c: size[c] * len(hostile) for c in ref.CURVES})
    checks.at_most("adversarial_compiles",
                   compiles.n - compiles0 - compiles_in_window, 0)
    checks.at_most("sr_python_transcript_lanes", sum(
        gates._count(x, "crypto_sr_python_transcript_lanes", "value")
        for x in (warmed, r, after)), 0)

    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        kernels = sum(v[0] for n, v in trace["device_ops"].items()
                      if "_verify_pallas" in n)
        print(f"commit_verify_mixed: the verify kernels' device seconds "
              f"{kernels:.4f} of busy {trace['busy_s']:.4f}",
              file=sys.stderr, flush=True)
    return RunResult(
        checks=checks, attempted=len(calls),
        failed=len(calls) - len(accepted),
        end_to_end={"verify_sigs_per_s": sigs_accepted / window_s,
                    "setup_s": setup_s},
        device=device, readings=r,
        breakdown=tracered.breakdown(trace) if trace else None)

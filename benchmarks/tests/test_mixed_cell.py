"""The cell ``mixed10k.commit-verify`` played end to end on XLA:CPU at a toy
size (tiny/mixed30-inproc.json: 30 validators, 10 a key type, 9 lanes a
flush at the one 64-lane shape): sound, traced, with each fault planted
underneath the timed path and with the control in the reference's place —
``correct`` true, true, false, false, false. Then every metric file the
cell brought, read on an emulated trace with the three kernels' names in
it. Nothing printed here is a device number."""
import functools
import json
import os
import re

import pytest

from benchmarks import run as bench_run
from benchmarks.drivers import commit_verify_mixed as drv
from benchmarks.lib import readers
from benchmarks.lib.spec import BENCH_DIR, ROOT, load_json
from benchmarks.reference import mixed_commits as ref

CELL = "mixed10k.commit-verify"
TINY = {"mixed10k-inproc": "benchmarks/tests/tiny/mixed30-inproc.json"}


def _run(capfd, seed, trace="0"):
    from tmtpu.libs import log

    log.configure()     # the program's logger keeps the stream it first saw
    rc = bench_run.main(["--workload", CELL, "--seed", seed, "--seconds", "2",
                         "--trace", trace], config_files=TINY,
                        require_chip=False)
    assert rc == 0
    line = json.loads(capfd.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    return line


def _failed(line):
    return sorted(k for k, v in line["checks"].items() if not v["ok"])


# -- the faults and the control -----------------------------------------------

def _sr_lanes_sent_serially(monkeypatch):
    """Once the warm-up is over, the batch layer's partition hands the
    sr25519 lanes to the serial path: every answer stays right, and one
    key type never reaches the device."""
    from tmtpu.crypto import batch as crypto_batch

    calls = {"n": 0}
    real_entry, real_split = drv.call_entry, \
        crypto_batch.TPUBatchVerifier._split

    def counted(*a):
        calls["n"] += 1
        return real_entry(*a)

    def split(items, curves):
        if calls["n"] > 2:
            curves = {k: v for k, v in curves.items() if k != ref.SR25519}
        return real_split(items, curves)
    monkeypatch.setattr(drv, "call_entry", counted)
    monkeypatch.setattr(crypto_batch.TPUBatchVerifier, "_split",
                        staticmethod(split))


def _tampered_sr_lane_let_through(monkeypatch):
    """The sr25519 device batch answers valid for every lane."""
    from tmtpu.tpu import dispatch

    real = dispatch.device_verify

    def lenient(curve, *a, **kw):
        mask, tallied = real(curve, *a, **kw)
        return (mask | True, tallied) if curve == ref.SR25519 \
            else (mask, tallied)
    monkeypatch.setattr(dispatch, "device_verify", lenient)


def _control(monkeypatch):
    """The reference replaced by one that takes every sr25519 signature
    for good: it is the program that is right, and ``correct`` has to say
    that the two differ."""
    monkeypatch.setattr(drv, "run", functools.partial(
        drv.run, trust=ref.SR25519))


SERIAL = ["adversarial_dispatches.sr25519", "adversarial_lanes_dispatched",
          "adversarial_lanes_dispatched.sr25519",
          "adversarial_lanes_off_device", "window_dispatches.sr25519",
          "window_lanes_dispatched.sr25519"]
CASES = [
    (None, "0", []),
    (None, "1", []),
    (_sr_lanes_sent_serially, "0", SERIAL),
    (_tampered_sr_lane_let_through, "0", ["adversarial_outcomes_differ"]),
    (_control, "0", ["adversarial_outcomes_differ"]),
]

# what a traced run on XLA:CPU finds to read of the metrics this cell
# brought (the kernels' names are a chip's: test_metric_files_* below)
ON_CPU = {"sr25519_hostprep_ms_per_10k", "secp256k1_hostprep_ms_per_10k",
          "collect_ms_per_10k.all", "sigcache_ms_per_10k.all",
          "lane_loops_ms_per_10k.all", "curve_dispatches_per_call"}


@pytest.mark.parametrize("fault,trace,expect", CASES, ids=[
    "sound", "traced", "sr_lanes_sent_serially",
    "tampered_sr_lane_let_through", "control_trusts_sr25519"])
def test_mixed_cell(fault, trace, expect, monkeypatch, capfd):
    if fault:
        fault(monkeypatch)
    # a seed of its own: the sigcache is the process's
    seed = str(3_000_000_000 + [c[:2] for c in CASES].index((fault, trace)))
    line = _run(capfd, seed, trace)
    assert _failed(line) == sorted(expect)
    assert line["correct"] is (not expect)
    assert line["failed"] == 0 and line["attempted"] >= 1
    checks = line["checks"]
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["sr_python_transcript_lanes"]["value"] == 0
    if trace == "1":
        got = line["metrics"]
        assert ON_CPU <= set(got), ON_CPU - set(got)
        assert got["curve_dispatches_per_call"]["value"] == 3.0
        assert all(got[m]["value"] > 0 for m in ON_CPU)
    else:
        assert set(line["metrics"]) == {"verify_sigs_per_s", "setup_s"}


# -- the metric files this cell brought, on an emulated trace -----------------

LANES = {ref.ED25519: 3167 * 4, ref.SR25519: 3166 * 4, ref.SECP256K1: 3167 * 4}
OPS = {"_verify_pallas_jit.1": [0.028, 4],
       "_sr_verify_pallas_jit.2": [0.14, 4],
       "_k1_verify_pallas_jit.3": [0.23, 4], "fusion.7": [0.001, 12]}
SPANS = {"commit_verify.collect": [0.16, 4], "batch.keys": [0.05, 4],
         "batch.lookup": [0.02, 4], "batch.insert": [0.04, 4],
         "batch.fold": [0.004, 4], "batch.split": [0.016, 4],
         "batch.apply": [0.006, 12], "ed25519.prepare": [0.016, 4],
         "ed25519.pad": [0.001, 4], "sr25519.prepare": [0.066, 4],
         "sr25519.pad": [0.0, 4], "secp256k1.prepare": [0.068, 4],
         "secp256k1.pad": [0.0, 4]}


def _metric(name):
    return load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))


def _readings(ops=OPS):
    r = readers.Readings(
        counters={"program_counter": {
            "tendermint_crypto_batch_size": {
                f"curve={c},backend=tpu": {"count": 4, "sum": n}
                for c, n in LANES.items()},
            "tendermint_crypto_flush_curves": {"": {"count": 4, "sum": 12}}}},
        trace={"window_s": 1.0, "busy_s": 0.4, "chips": 1,
               "device_ops": ops, "spans": SPANS, "idle_gaps": {}},
        window_s=1.0, device_kind="TPU v5 lite")
    r.clock.update(drv.kernel_shares(r))
    return r


def _per_10k(seconds, lanes):
    return seconds / lanes * 1e4 * 1e3


def _roofline(row, seconds, lanes, table="opcounts_curves.json"):
    ops = load_json(os.path.join(BENCH_DIR, "lib", table))[row]["int_ops"]
    peak = load_json(os.path.join(BENCH_DIR, "lib", "peaks.json"))[
        "TPU v5 lite"]["int_ops_per_s"]
    return 100.0 * lanes * ops / peak / seconds


ALL = sum(LANES.values())
WANT = {
    "sr25519_kernel_ms_per_10k": _per_10k(0.14, LANES[ref.SR25519]),
    "secp256k1_kernel_ms_per_10k": _per_10k(0.23, LANES[ref.SECP256K1]),
    "ed25519_kernel_ms_per_10k.anchored": _per_10k(0.028, LANES[ref.ED25519]),
    "ed25519_kernel_roofline.anchored": _roofline(
        "ed25519_verify", 0.028, LANES[ref.ED25519], "opcounts.json"),
    "sr25519_kernel_roofline": _roofline(
        "sr25519_verify", 0.14, LANES[ref.SR25519]),
    "secp256k1_kernel_roofline": _roofline(
        "secp256k1_verify", 0.23, LANES[ref.SECP256K1]),
    "sr25519_hostprep_ms_per_10k": _per_10k(0.066, LANES[ref.SR25519]),
    "secp256k1_hostprep_ms_per_10k": _per_10k(0.068, LANES[ref.SECP256K1]),
    "collect_ms_per_10k.all": _per_10k(0.16, ALL),
    "sigcache_ms_per_10k.all": _per_10k(0.11, ALL),
    "lane_loops_ms_per_10k.all": _per_10k(0.026, ALL),
    "curve_dispatches_per_call": 3.0,
}


def test_the_cell_lists_what_it_brought_and_not_what_misreads_it():
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert set(WANT) <= listed
    assert not listed & {
        "ed25519_kernel_ms_per_10k", "ed25519_kernel_roofline",
        "sigcache_ms_per_10k", "lane_loops_ms_per_10k", "collect_ms_per_10k",
        "collect_cpu_ms_per_10k"}
    for m in bench["per_layer"]:
        if m["name"] in WANT:
            assert m["workloads"] == [CELL] and \
                m["moves"] == "verify_sigs_per_s"


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_files_on_an_emulated_trace(name):
    got = readers.read_metric(_metric(name), _readings())
    assert got == pytest.approx(WANT[name]) and got > 0
    if "roofline" in name:
        assert got < 100


@pytest.mark.parametrize("name", sorted(n for n in WANT if "kernel" in n))
def test_kernel_metrics_read_nothing_without_their_kernel(name):
    """A program whose trace holds no such kernel (the XLA graph, a
    parent without it): nothing is returned, nothing is raised."""
    quiet = _readings({"fusion.7": [0.001, 12]})
    assert readers.read_metric(_metric(name), quiet) is None
    no_trace = readers.Readings(counters=quiet.counters, window_s=1.0,
                                device_kind="TPU v5 lite")
    assert drv.kernel_shares(no_trace) == {}
    assert readers.read_metric(_metric(name), no_trace) is None


def test_dispatches_per_call_reads_nothing_without_the_counter():
    r = _readings()
    del r.counters["program_counter"]["tendermint_crypto_flush_curves"]
    assert readers.read_metric(_metric("curve_dispatches_per_call"), r) is None


@pytest.mark.parametrize("name,own,others", [
    ("ed25519_kernel_ms_per_10k.anchored", "_verify_pallas_jit.1",
     ["_sr_verify_pallas_jit.2", "_k1_verify_pallas_jit.3"]),
    ("ed25519_kernel_roofline.anchored", "_verify_pallas_jit",
     ["_sr_verify_pallas_jit", "_k1_verify_pallas_jit.1"]),
])
def test_anchored_names_leave_the_other_kernels_out(name, own, others):
    read = _metric(name)["read"]
    pattern = (read.get("num") or read["seconds"])["name"]
    assert re.search(pattern, own)
    assert not any(re.search(pattern, o) for o in others)
    # the accepted pair's pattern takes all three: why this cell leaves it out
    old = _metric(name.replace(".anchored", ""))["read"]
    loose = (old.get("num") or old["seconds"])["name"]
    assert all(re.search(loose, o) for o in [own] + others)
    for curve, (pat, _row) in drv.KERNELS.items():
        assert not re.search(pat, own)
        assert sum(bool(re.search(pat, o)) for o in OPS) == 1, curve

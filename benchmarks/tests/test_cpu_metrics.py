"""The per-layer metrics PR 36 added, read off recorded registry tables as
``readers.read_metric`` reads them from a run: a span's CPU seconds beside
its wall seconds (``tendermint_trace_span_cpu_seconds{name}``), the peers'
queue, the collector's pauses and the process's CPU. All of them are data
files for the general reader; a program without the families (the parent)
leaves every one of them out of its line."""
import hashlib
import json
import os
import re

import pytest

from benchmarks.lib import readers
from benchmarks.lib.spec import BENCH_DIR, ROOT, load_json

# the 56 entries the benchmark had, as json.dumps(..., sort_keys=True)
HAD = 56
HAD_SHA256 = \
    "4f12a8e9dc903c9a135efb957999ad49f8dec04b957354dd63a5bb59e701d046"
LIVE = ["valset10k.live-rounds"]
SERVED = ["kvstore1.signed-sat", "kvstore1.plain-sat"]
INPROC = ["valset10k.commit-verify", "valset175.replay",
          "light175.sequential", "valset10k.live-rounds"]
# metric -> the cells its entry lists
NEW = {
    "live_receive_cpu_ms_per_10k": LIVE,
    "live_collect_cpu_ms_per_10k": LIVE,
    "live_apply_cpu_ms_per_10k": LIVE,
    "live_wal_cpu_ms_per_10k": LIVE,
    "live_block_cpu_ms_per_height": LIVE,
    "live_lock_wait_pct": LIVE,
    "live_queue_blocked_ms_per_height": LIVE,
    "live_queue_wait_ms": LIVE,
    "live_wal_appends_per_height": LIVE,
    "collect_cpu_ms_per_10k": ["valset10k.commit-verify"],
    "light_check_cpu_ms_per_block": ["light175.sequential"],
    "host_cpu_pct": INPROC,
    "host_cpu_pct.serve": SERVED,
    "gc_pause_pct": INPROC,
    "gc_pause_pct.serve": SERVED,
    "node_cpu_pct": SERVED,
    "node_gc_pause_pct": SERVED,
    "admit_host_cpu_ms_per_flush": SERVED,
}

WALL = "tendermint_trace_span_seconds"
CPU = "tendermint_trace_span_cpu_seconds"
# a window of 10 s, two heights of the live cell: what a registry delta
# holds (the numbers are made up, the shapes are the program's)
SPANS = {   # name: (count, wall seconds, CPU seconds)
    "consensus.receive": (40_034, 4.0, 2.6),
    "vote_set.collect": (50, 2.4, 1.3),
    "vote_set.apply": (50, 0.6, 0.45),
    "consensus.publish": (50, 0.2, 0.15),
    "consensus.wal": (52, 1.5, 0.4),
    "consensus.proposal": (36, 0.6, 0.5),
    "consensus.finalize_commit": (2, 2.2, 1.9),
    "consensus.idle": (52, 0.1, 0.001),
    "commit_verify.collect": (91, 3.6, 3.5),
    "light.check": (20, 1.4, 1.38),
    "light.store": (700, 4.6, 3.0),
    "mempool.screen": (40, 0.06, 0.05),
    "mempool.verify": (40, 0.4, 0.01),
    "mempool.check_tx": (40, 0.14, 0.07),
    "gc.collect": (3, 0.03, 0.03),
}
VOTES = 20_000.0 + 17_000.0


def _family(i):
    return {f"name={n}": {"count": v[0], "sum": v[i]}
            for n, v in SPANS.items()}


RECORDED = {
    WALL: _family(1),
    CPU: _family(2),
    "tendermint_consensus_votes_added_total": {
        "type=prevote": 20_000.0, "type=precommit": 17_000.0},
    "tendermint_consensus_peer_queue_blocked_seconds": {
        "": {"count": 38, "sum": 3.1}},
    "tendermint_consensus_peer_queue_wait_seconds": {
        "": {"count": 40_034, "sum": 9_000.0}},
    "tendermint_consensus_wal_appends_total": {"": 131.0},
    "tendermint_crypto_batch_size": {
        "curve=ed25519,backend=tpu": {"count": 91, "sum": 864_500.0},
        "curve=sr25519,backend=tpu": {"count": 1, "sum": 999.0}},
    "tendermint_runtime_process_cpu_seconds": {"": 10.4},
    "tendermint_runtime_gc_pause_seconds": {
        "generation=0": {"count": 2_000, "sum": 0.11},
        "generation=1": {"count": 2, "sum": 0.01},
        "generation=2": {"count": 1, "sum": 0.02}},
    "tendermint_mempool_batch_flushes": {"": 40},
}
WINDOW_S = 10.0


def _sum(i, *names):
    return sum(SPANS[n][i] for n in names)


NONBLOCKING = ("consensus.receive", "vote_set.collect", "vote_set.apply",
               "consensus.publish")
WANT = {
    "live_receive_cpu_ms_per_10k": 2.6 / VOTES * 1e4 * 1e3,
    "live_collect_cpu_ms_per_10k": 1.3 / VOTES * 1e4 * 1e3,
    "live_apply_cpu_ms_per_10k": (0.45 + 0.15) / VOTES * 1e4 * 1e3,
    "live_wal_cpu_ms_per_10k": 0.4 / VOTES * 1e4 * 1e3,
    "live_block_cpu_ms_per_height": (0.5 + 1.9) / 2 * 1e3,
    "live_lock_wait_pct": 100 * (1 - _sum(2, *NONBLOCKING)
                                 / _sum(1, *NONBLOCKING)),
    "live_queue_blocked_ms_per_height": 3.1 / 2 * 1e3,
    "live_queue_wait_ms": 9_000.0 / 40_034 * 1e3,
    "live_wal_appends_per_height": 131.0 / 2,
    "collect_cpu_ms_per_10k": 3.5 / 864_500.0 * 1e4 * 1e3,
    "light_check_cpu_ms_per_block": 1.38 / 700 * 1e3,
    "host_cpu_pct": 104.0,
    "host_cpu_pct.serve": 104.0,
    "gc_pause_pct": 1.4,
    "gc_pause_pct.serve": 1.4,
    "node_cpu_pct": 104.0,
    "node_gc_pause_pct": 1.4,
    "admit_host_cpu_ms_per_flush": (0.05 + 0.07) / 40 * 1e3,
}


def _metric(name):
    return load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))


def _readings(table):
    # the node's registry has the program's shapes: one table serves both
    return readers.Readings(
        counters={"program_counter": table, "node_metrics": table},
        window_s=WINDOW_S)


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_off_a_recorded_registry(name):
    got = readers.read_metric(_metric(name), _readings(RECORDED))
    assert got == pytest.approx(WANT[name]) and got > 0


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_family_leaves_the_metric_out(name):
    """The parent's registry: no CPU family, no queue summaries, no
    ``runtime_*``; the reader finds nothing and does not raise. (The WAL's
    append counter is PR 35's: the parent reports that one metric too.)"""
    parent = {k: v for k, v in RECORDED.items()
              if k != CPU and "peer_queue" not in k and "runtime_" not in k}
    got = readers.read_metric(_metric(name), _readings(parent))
    if name == "live_wal_appends_per_height":
        assert got == pytest.approx(WANT[name])
    else:
        assert got is None
    assert readers.read_metric(_metric(name), readers.Readings()) is None


def test_a_cpu_term_never_reads_the_wall_family_nor_the_other_way():
    for name in NEW:
        for term in _metric(name)["read"].values():
            hit = [fam for fam in (WALL, CPU)
                   if re.search(term["name"], fam)]
            assert len(hit) <= 1, (name, term)


def test_the_cpu_metrics_stay_at_or_below_their_wall_twins():
    """On the recorded table, as on any the program can write: a span's
    CPU reads lie inside its wall reads."""
    r = _readings(RECORDED)
    r.trace = {"window_s": WINDOW_S, "spans": {
        n: [v[1], v[0]] for n, v in SPANS.items()}, "device_ops": {}}
    for cpu_name, wall_name in (
            ("live_receive_cpu_ms_per_10k", "live_receive_ms_per_10k"),
            ("live_collect_cpu_ms_per_10k", "live_collect_ms_per_10k"),
            ("live_apply_cpu_ms_per_10k", "live_apply_ms_per_10k"),
            ("live_wal_cpu_ms_per_10k", "live_wal_ms_per_10k"),
            ("live_block_cpu_ms_per_height", "live_block_ms_per_height"),
            ("collect_cpu_ms_per_10k", "collect_ms_per_10k"),
            ("light_check_cpu_ms_per_block", "light_check_ms_per_block"),
            ("admit_host_cpu_ms_per_flush", "admit_host_ms_per_flush")):
        cpu = readers.read_metric(_metric(cpu_name), r)
        wall = readers.read_metric(_metric(wall_name), r)
        assert 0 < cpu <= wall, (cpu_name, cpu, wall)


def test_the_entries_are_appended_and_agree_with_their_files():
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    had, new = bench["per_layer"][:HAD], bench["per_layer"][HAD:]
    assert hashlib.sha256(json.dumps(had, sort_keys=True).encode()) \
        .hexdigest() == HAD_SHA256
    assert [e["name"] for e in new[:len(NEW)]] == list(NEW)
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    for entry in new[:len(NEW)]:
        mfile = _metric(entry["name"])
        assert entry == dict(
            {k: mfile[k] for k in ("name", "unit", "better", "layer",
                                   "moves", "workloads")},
            source="program_counter")
        assert mfile["source"] in ("program_counter", "node_metrics")
        assert entry["workloads"] == NEW[entry["name"]]
        # every listed cell reports the end-to-end metric it moves
        assert set(entry["workloads"]) <= set(e2e[entry["moves"]])

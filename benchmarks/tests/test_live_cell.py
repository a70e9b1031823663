"""The cell ``valset10k.live-rounds`` rehearsed end to end on XLA:CPU at a
toy size (16 validators, a 14-height chain, every vote flush pinned to the
one 64-lane shape): once sound, once traced, once for each of the three
faults let through underneath the node, and once with the control, a
reference that verifies no signature, in the reference's place. ``correct``
has to come out true for the sound runs and false for every other. Nothing
printed here is a device number."""
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import readers, spec
from benchmarks.lib.spec import BENCH_DIR, load_json
from benchmarks.reference import rounds as rr

CELL = "valset10k.live-rounds"
TOY = {"valset10k-live": "benchmarks/tests/tiny/valset16-live.json"}
NEW_METRICS = ["live_height_ms", "live_votes_per_flush",
               "live_late_precommit_pct", "live_receive_ms_per_10k",
               "live_wal_ms_per_10k", "live_collect_ms_per_10k",
               "live_apply_ms_per_10k", "live_block_ms_per_height",
               "live_wait_ms_per_height", "live_verify_ms_per_10k",
               "live_steps_ms_per_height"]
SHARED_METRICS = ["chip_reach_s", "warm_s", "cpu_fallback_lanes",
                  "sigcache_hit_pct", "dispatch_ms_per_10k", "pad_ratio",
                  "sigcache_ms_per_10k", "lane_loops_ms_per_10k",
                  "hostprep_ms_per_10k", "transfer_ms_per_10k"]


@pytest.fixture
def toy(monkeypatch):
    """The cell's own files with the toy configuration in the real one's
    place and the sizes a CPU can play in seconds: a short chain signed in
    this process."""
    real = spec.load_cell

    def load(name, config_files=None):
        cell = real(name, config_files)
        cell.traffic.update(chain_heights=14, datagen_workers=1,
                            stall_seconds=60, starved_hold_seconds=0.3)
        return cell
    monkeypatch.setattr(spec, "load_cell", load)


def _run(capfd, seed, trace="0", seconds="0.2"):
    from tmtpu.libs import log

    log.configure()     # the program's logger keeps the stream it first saw
    rc = bench_run.main(["--workload", CELL, "--seed", seed, "--seconds",
                         seconds, "--trace", trace], config_files=TOY,
                        require_chip=False)
    assert rc == 0
    line = json.loads(capfd.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    return line


def _failed(line):
    return sorted(k for k, v in line["checks"].items() if not v["ok"])


# -- what is planted -------------------------------------------------------------

def every_signature_passes(monkeypatch):
    """The device path under the vote sets answers "valid" for every lane:
    a tampered prevote is added."""
    from tmtpu.crypto import batch as crypto_batch

    def lenient(self, items, tally):
        return [True] * len(items), sum(it[3] for it in items)
    monkeypatch.setattr(crypto_batch.TPUBatchVerifier, "_verify_pending",
                        lenient)


def double_vote_not_reported(monkeypatch):
    from tmtpu.evidence.pool import EvidencePool

    monkeypatch.setattr(EvidencePool, "report_conflicting_votes",
                        lambda self, a, b: None)


def commits_at_two_thirds(monkeypatch):
    """The set says it holds a little less power than it does: exactly 2/3
    of the real power is then "more than 2/3"."""
    from tmtpu.types.validator import ValidatorSet

    real = ValidatorSet.total_voting_power
    monkeypatch.setattr(ValidatorSet, "total_voting_power",
                        lambda self: real(self) - 2)


def reference_skips(monkeypatch, check):
    """The CONTROL in the reference's place: the plain protocol with one
    check left out (reference/rounds.py ``Height(skip=...)``)."""
    real = rr.Height

    class Control(real):
        def __init__(self, *a, **kw):
            super().__init__(*a, skip=check, **kw)
    monkeypatch.setattr(rr, "Height", Control)


PLANTS = {
    "tampered_let_through": every_signature_passes,
    "double_vote_unreported": double_vote_not_reported,
    "commit_at_exactly_two_thirds": commits_at_two_thirds,
    "reference_skips_signatures": lambda m: reference_skips(m, "signatures"),
}
TELLS = {"commit_at_exactly_two_thirds": "fault_commit_not_held_at_two_thirds"}


# -- the runs --------------------------------------------------------------------

def test_sound_run_is_correct(toy, capfd):
    line = _run(capfd, "601")
    assert _failed(line) == [] and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["verify_sigs_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reports_the_cells_layers(toy, capfd):
    line = _run(capfd, "602", trace="1")
    assert _failed(line) == []
    for name in NEW_METRICS + SHARED_METRICS:
        assert name in line["metrics"], name
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # every flush of a 16-validator set is pinned to the set: one 64-lane
    # shape, the node's own one-vote flushes at it too
    assert 1 < m["live_votes_per_flush"] <= 15
    # the misses are the votes; the hits validate_block's verify_commit of
    # LastCommit, three times a height
    assert 40 < m["sigcache_hit_pct"] < 75 and m["cpu_fallback_lanes"] == 0
    assert 0 <= m["live_late_precommit_pct"] < 50
    assert line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("plant", sorted(PLANTS))
def test_planted_fault_fails_correct(toy, capfd, monkeypatch, plant):
    PLANTS[plant](monkeypatch)
    # a seed of its own: the sigcache is the process's
    line = _run(capfd, str(700 + sorted(PLANTS).index(plant)))
    assert line["correct"] is False
    assert TELLS.get(plant, "fault_outcomes_differ") in _failed(line)
    # the window itself committed the reference's blocks: the tail tells
    assert "window_heights_wrong_hash" not in _failed(line)


def test_window_holds_whole_heights_only(toy, capfd):
    """The rate's numerator is that of whole heights, two votes a co-signer
    each, whatever --seconds."""
    line = _run(capfd, "603", seconds="0.05")
    assert _failed(line) == []
    assert line["attempted"] >= 1
    assert line["checks"]["window_prevotes_added_off"]["value"] == 0
    assert line["checks"]["votes_unaccounted_at_end"]["value"] == 0


# -- the metric files on a recorded span set --------------------------------------

def test_metric_files_read_a_recorded_span_set():
    """Seconds and counts as tracered.reduce_trace gives them for a window
    of three heights; a set without the spans (the parent's) leaves every
    metric out and raises nothing."""
    spans = {"consensus.receive": [2.4, 60_051], "consensus.wal": [0.6, 70],
             "consensus.idle": [0.9, 40], "vote_set.collect": [1.8, 66],
             "vote_set.apply": [0.9, 66], "consensus.publish": [0.3, 66],
             "batch.resolve": [1.2, 75], "consensus.proposal": [0.15, 54],
             "consensus.finalize_commit": [1.35, 3],
             "consensus.enter_prevote": [0.3, 3],
             "consensus.enter_precommit": [0.3, 3]}
    r = readers.Readings(
        clock={"height_interval_s": [3.1, 2.9, 3.0]},
        counters={"program_counter": {
            "tendermint_consensus_votes_added_total": {
                "type=prevote": 30_000, "type=precommit": 21_000,
                "type=late_precommit": 9_000},
            "tendermint_consensus_vote_flush_lanes": {
                "": {"count": 66, "sum": 60_000}}}},
        trace={"spans": spans, "device_ops": {}, "window_s": 9.0},
        window_s=9.0, device_kind="TPU v5 lite")
    want = {"live_height_ms": 3000.0, "live_votes_per_flush": 60_000 / 66,
            "live_late_precommit_pct": 30.0, "live_receive_ms_per_10k": 400.0,
            "live_wal_ms_per_10k": 100.0, "live_collect_ms_per_10k": 300.0,
            "live_apply_ms_per_10k": 200.0, "live_block_ms_per_height": 500.0,
            "live_wait_ms_per_height": 300.0, "live_verify_ms_per_10k": 200.0,
            "live_steps_ms_per_height": 200.0}
    assert sorted(want) == sorted(NEW_METRICS)
    bare = readers.Readings(counters={"program_counter": {}},
                            trace={"spans": {"bench.window": [1, 1]},
                                   "device_ops": {}, "window_s": 9.0})
    for name, value in want.items():
        mfile = load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))
        assert readers.read_metric(mfile, r) == pytest.approx(value), name
        assert readers.read_metric(mfile, bare) is None, name

"""The cell ``light175.sequential`` rehearsed end to end on XLA:CPU at a toy
size (12 validators, a 400-header chain, 18-header sessions of three
5-header runs and a tail of 3 at a 64-lane shape): once sound, once traced,
once for each of the four faults let through underneath the client, and
once with the control, a reference that verifies no signature, in the
reference's place. ``correct`` has to come out true for the sound runs and
false for every other. Nothing printed here is a device number."""
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import readers, spec
from benchmarks.lib.spec import BENCH_DIR, load_json
from benchmarks.reference import light as rl

CELL = "light175.sequential"
TOY = {"light175-sequential": "benchmarks/tests/tiny/light12-sequential.json"}
NEW_METRICS = ["light_fetch_ms_per_block", "light_check_ms_per_block",
               "light_verify_ms_per_block", "light_collect_ms_per_block",
               "light_store_ms_per_block", "light_detect_ms_per_session",
               "light_blocks_per_run"]
SHARED_METRICS = ["chip_reach_s", "warm_s", "cpu_fallback_lanes",
                  "sigcache_hit_pct", "dispatch_ms_per_10k", "pad_ratio",
                  "sigcache_ms_per_10k", "lane_loops_ms_per_10k",
                  "hostprep_ms_per_10k", "transfer_ms_per_10k",
                  "replay_lanes_per_dispatch", "valset_memo_hit_pct"]


@pytest.fixture
def toy(monkeypatch):
    """The cell's own files with the toy configuration in the real one's
    place and the sizes a CPU can sync in seconds: a short chain signed in
    this process, a 64-lane run."""
    from tmtpu.blocksync import common

    monkeypatch.setattr(common, "RUN_LANES", 64)
    real = spec.load_cell

    def load(name, config_files=None):
        cell = real(name, config_files)
        cell.traffic.update(chain_blocks=400, datagen_workers=1)
        return cell
    monkeypatch.setattr(spec, "load_cell", load)


def _run(capfd, seed, trace="0", seconds="1"):
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

def lets_through(monkeypatch, text):
    """The fused entry under the client answers "verified" where its error
    says ``text``: a tampered signature or a starved commit is trusted."""
    from tmtpu.types import commit_verify

    real = commit_verify.verify_commits_light_batch

    def lenient(entries, **kw):
        return [None if r is not None and text in str(r) else r
                for r in real(entries, **kw)]
    monkeypatch.setattr(commit_verify, "verify_commits_light_batch", lenient)


class _Any(bytes):
    """Bytes that every other value equals."""

    def __eq__(self, other):
        return True

    def __ne__(self, other):
        return False

    __hash__ = bytes.__hash__


def link_not_checked(monkeypatch):
    """The hash link of a run, and of the precise hop after it, holds
    whatever set a header names: the header below promises any."""
    from tmtpu.light import verifier

    def promise_any(lb_or_sh):
        h = lb_or_sh.header
        h.next_validators_hash = _Any(h.next_validators_hash)

    real_run, real_hop = verifier.verify_adjacent_run, verifier.verify_adjacent

    def run(trusted, blocks, *a, **kw):
        for lb in [trusted] + blocks:
            promise_any(lb)
        return real_run(trusted, blocks, *a, **kw)

    def hop(trusted, *a, **kw):
        promise_any(trusted)
        return real_hop(trusted, *a, **kw)
    monkeypatch.setattr(verifier, "verify_adjacent_run", run)
    monkeypatch.setattr(verifier, "verify_adjacent", hop)


def witness_not_asked(monkeypatch):
    from tmtpu.light.client import Client

    monkeypatch.setattr(Client, "_detect_divergence",
                        lambda self, verified, now_ns: None)


def reference_skips(monkeypatch, check):
    """The CONTROL in the reference's place: the plain sync with one check
    left out (reference/light.py ``Sync(skip=...)``)."""
    real = rl.Sync

    class Control(real):
        def __init__(self, *a, **kw):
            super().__init__(*a, skip=check, **kw)
    monkeypatch.setattr(rl, "Sync", Control)


PLANTS = {
    "tampered_let_through": lambda m: lets_through(m, "wrong signature"),
    "starved_let_through": lambda m: lets_through(
        m, "insufficient voting power"),
    "broken_link_let_through": link_not_checked,
    "witness_fork_let_through": witness_not_asked,
    "reference_skips_signatures": lambda m: reference_skips(m, "signatures"),
}


# -- the runs --------------------------------------------------------------------

def test_sound_run_is_correct(toy, capfd):
    line = _run(capfd, "401")
    assert _failed(line) == [] and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["verify_sigs_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reports_the_cells_layers(toy, capfd):
    line = _run(capfd, "402", trace="1")
    assert _failed(line) == []
    for name in NEW_METRICS + SHARED_METRICS:
        assert name in line["metrics"], name
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # an 18-header session: runs of 5, 5, 5 and 3 at 11 signatures a header
    assert m["light_blocks_per_run"] == 18 / 4
    assert m["replay_lanes_per_dispatch"] == 11 * 18 / 4
    assert abs(m["pad_ratio"] - (3 * 64 / 55 + 64 / 33) / 4) < 1e-6
    assert m["sigcache_hit_pct"] == 0 and m["cpu_fallback_lanes"] == 0
    # a fetched set is a new object: its one hash is computed; the target's
    # is asked for twice (validate_basic, then the run's check)
    assert 0 < m["valset_memo_hit_pct"] < 10
    assert line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("plant", sorted(PLANTS))
def test_planted_fault_fails_correct(toy, capfd, monkeypatch, plant):
    PLANTS[plant](monkeypatch)
    # a seed of its own: the sigcache is the process's
    line = _run(capfd, str(500 + sorted(PLANTS).index(plant)))
    assert line["correct"] is False
    assert "fault_outcomes_differ" in _failed(line)
    # the window itself was sound: only the tail tells
    assert "sessions_differ_from_reference" not in _failed(line)
    assert not [k for k in _failed(line) if k.startswith("window_")]


def test_window_holds_whole_sessions_only(toy, capfd):
    """The rate's numerator and denominator are those of whole sessions:
    the headers trusted are a multiple of a session's, whatever --seconds."""
    line = _run(capfd, "403", seconds="0.3")
    assert _failed(line) == []
    sessions = line["attempted"]
    assert sessions >= 1
    assert line["checks"]["verified_counter_off"]["value"] == 0
    assert line["checks"]["runs_off_size"]["value"] == 0   # 4 runs a session


# -- the metric files on a recorded span set --------------------------------------

def test_metric_files_read_a_recorded_span_set():
    """Seconds and counts as tracered.reduce_trace gives them for a window
    of two 140-header sessions; a set without the spans (the parent's)
    leaves every metric out and raises nothing."""
    spans = {"light.session": [2.40, 2], "light.fetch": [1.12, 282],
             "light.check": [0.56, 8], "light.verify_run": [0.42, 8],
             "commit_verify.collect": [0.28, 8], "light.detect": [0.02, 2],
             "light.store": [0.84, 280]}
    r = readers.Readings(
        counters={"program_counter": {"tendermint_light_run_blocks": {
            "": {"count": 8, "sum": 280}}}},
        trace={"spans": spans, "device_ops": {}, "window_s": 2.4},
        window_s=2.4, device_kind="TPU v5 lite")
    want = {"light_fetch_ms_per_block": 4.0, "light_check_ms_per_block": 2.0,
            "light_verify_ms_per_block": 1.5,
            "light_collect_ms_per_block": 1.0,
            "light_store_ms_per_block": 3.0,
            "light_detect_ms_per_session": 10.0, "light_blocks_per_run": 35}
    assert sorted(want) == sorted(NEW_METRICS)
    bare = readers.Readings(counters={"program_counter": {}},
                            trace={"spans": {"bench.window": [1, 1]},
                                   "device_ops": {}, "window_s": 2.4})
    for name, value in want.items():
        mfile = load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))
        assert readers.read_metric(mfile, r) == pytest.approx(value), name
        assert readers.read_metric(mfile, bare) is None, name

"""The cell ``staking10k.live-rounds`` rehearsed end to end on XLA:CPU at a
toy size (30 validators whose Zipf powers fill every 13-bit limb, three
power changes a height and a leave/join every second, a 14-height chain,
every vote flush pinned to the one 64-lane shape): once sound, once traced,
once for each fault let through underneath the node, and once with the
control, a reference that verifies no signature, in the reference's place.
``correct`` has to come out true for the sound runs and false for every
other. Nothing printed here is a device number."""
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import readers, spec
from benchmarks.lib.spec import BENCH_DIR, load_json
from benchmarks.reference import rounds as rr

CELL = "staking10k.live-rounds"
TOY = {"staking10k-live": "benchmarks/tests/tiny/staking30-live.json"}
NEW_METRICS = ["live_valset_update_ms_per_height",
               "live_valset_update_cpu_ms_per_height",
               "live_valset_changes_per_height", "tally_multilimb_lane_pct"]


@pytest.fixture
def toy(monkeypatch):
    real = spec.load_cell

    def load(name, config_files=None):
        cell = real(name, config_files)
        cell.traffic.update(chain_heights=14, datagen_workers=1,
                            stall_seconds=60, starved_hold_seconds=0.3)
        return cell
    monkeypatch.setattr(spec, "load_cell", load)


def _run(capfd, seed, trace="0", seconds="0.2"):
    from tmtpu.libs import log

    log.configure()
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

def app_keeps_a_leaver(monkeypatch):
    """The app returns the leave from EndBlock but keeps the key in its
    table."""
    from tmtpu.abci.example.kvstore import KVStoreApplication

    real = KVStoreApplication._set_validator
    monkeypatch.setattr(KVStoreApplication, "_set_validator",
                        lambda self, vu: None if vu.power == 0
                        else real(self, vu))


def store_drops_priorities(monkeypatch):
    """The state store writes each set with its priorities zeroed."""
    from tmtpu.state.store import StateStore
    from tmtpu.types.validator import Validator, ValidatorSet

    real = StateStore._save_validators

    def zeroed(self, height, vals):
        real(self, height, ValidatorSet.restore(
            [Validator(v.pub_key, v.voting_power, 0)
             for v in vals.validators]))
    monkeypatch.setattr(StateStore, "_save_validators", zeroed)


def updates_uncounted(monkeypatch):
    from tmtpu.libs import metrics

    monkeypatch.setattr(metrics.state_validator_updates, "inc",
                        lambda *a, **k: None)


def commits_before_two_thirds(monkeypatch):
    """A vote set weighs each vote for a block twice when it looks for the
    2/3 point (the tally itself stays right): it commits at a third."""
    from tmtpu.types import vote_set

    real = vote_set._BlockVotes.add_verified_vote
    monkeypatch.setattr(vote_set._BlockVotes, "add_verified_vote",
                        lambda self, vote, power: real(self, vote, 2 * power))


def reference_skips(monkeypatch, check):
    real = rr.Height

    class Control(real):
        def __init__(self, *a, **kw):
            super().__init__(*a, skip=check, **kw)
    monkeypatch.setattr(rr, "Height", Control)


PLANTS = {
    "app_keeps_a_leaver": app_keeps_a_leaver,
    "store_drops_priorities": store_drops_priorities,
    "updates_uncounted": updates_uncounted,
    "commit_before_two_thirds": commits_before_two_thirds,
    "reference_skips_signatures": lambda m: reference_skips(m, "signatures"),
}
TELLS = {"app_keeps_a_leaver": "app_validator_table_differs",
         "store_drops_priorities": "window_stored_sets_differ",
         "updates_uncounted": "validator_updates_off",
         "commit_before_two_thirds": "fault_commit_not_held_at_two_thirds",
         "reference_skips_signatures": "fault_outcomes_differ"}


# -- the runs --------------------------------------------------------------------

def test_sound_run_is_correct(toy, capfd):
    line = _run(capfd, "2147483901")
    assert _failed(line) == [] and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["verify_sigs_per_s"]["value"] > 0


def test_traced_run_reports_the_new_layers(toy, capfd):
    line = _run(capfd, "902", trace="1")
    assert _failed(line) == []
    m = {k: v["value"] for k, v in line["metrics"].items()}
    for name in NEW_METRICS + ["valset_memo_hit_pct", "live_height_ms",
                               "live_block_ms_per_height"]:
        assert name in m, name
    # three power changes a height and a leave/join every second: 4 a
    # height over a window of whole heights, give or take the pair
    assert 3 <= m["live_valset_changes_per_height"] <= 5
    # a 2^56 total over 30 Zipf powers: every lane but the lightest few
    # carries past the first limb
    assert 50 < m["tally_multilimb_lane_pct"] <= 100
    assert m["live_valset_update_ms_per_height"] > 0
    assert 0 <= m["valset_memo_hit_pct"] < 100


@pytest.mark.parametrize("plant", sorted(PLANTS))
def test_planted_fault_fails_correct(toy, capfd, monkeypatch, plant):
    PLANTS[plant](monkeypatch)
    line = _run(capfd, str(910 + sorted(PLANTS).index(plant)))
    assert line["correct"] is False
    assert TELLS[plant] in _failed(line)


def test_a_program_without_the_counters_exits_at_once(toy, monkeypatch):
    from tmtpu.libs import metrics

    monkeypatch.delattr(metrics, "crypto_tally_power_lanes")
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", CELL, "--seed", "1", "--seconds",
                        "0.2"], config_files=TOY, require_chip=False)
    assert "crypto_tally_power_lanes_total" in str(e.value.code)


# -- the metric files on a recorded span set --------------------------------------

def test_metric_files_read_a_recorded_span_set():
    """Three heights' spans and counters; a set without them (the parent's)
    leaves every metric out and raises nothing."""
    r = readers.Readings(
        counters={"program_counter": {
            "tendermint_state_validator_updates_total": {
                "kind=power": 24, "kind=join": 1, "kind=leave": 1},
            "tendermint_crypto_tally_power_lanes_total": {
                "limbs=one": 690, "limbs=more": 310},
            "tendermint_trace_span_cpu_seconds": {
                "name=state.update_validators": {"count": 3, "sum": 0.09}},
            "tendermint_trace_span_seconds": {
                "name=consensus.finalize_commit": {"count": 3, "sum": 4.0}}}},
        trace={"spans": {"state.update_validators": [0.12, 3],
                         "consensus.finalize_commit": [4.0, 3]},
               "device_ops": {}, "window_s": 9.0},
        window_s=9.0, device_kind="TPU v5 lite")
    want = {"live_valset_update_ms_per_height": 40.0,
            "live_valset_update_cpu_ms_per_height": 30.0,
            "live_valset_changes_per_height": 26 / 3,
            "tally_multilimb_lane_pct": 31.0}
    assert sorted(want) == sorted(NEW_METRICS)
    bare = readers.Readings(counters={"program_counter": {}},
                            trace={"spans": {"bench.window": [1, 1]},
                                   "device_ops": {}, "window_s": 9.0})
    # a program with the span-CPU registry but not this path's counters
    older = readers.Readings(counters={"program_counter": {
        k: v for k, v in r.counters["program_counter"].items()
        if "trace_span" in k}}, trace=bare.trace)
    for name, value in want.items():
        mfile = load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))
        assert readers.read_metric(mfile, r) == pytest.approx(value), name
        assert readers.read_metric(mfile, bare) is None, name
        assert readers.read_metric(mfile, older) is None, name

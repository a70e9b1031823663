"""The cell ``valset175.replay`` rehearsed end to end on XLA:CPU at a toy
size (12 validators, a 400-block chain, 5-block runs at a 64-lane shape):
once sound, once traced, once for each of the three faults let through
underneath the reactor, and once with the control, a reference that leaves
a check out, in the reference's place. ``correct`` has to come out true
for the sound runs and false for every other. Nothing printed here is a
device number."""
import json

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import spec
from benchmarks.reference import blocks as rb

CELL = "valset175.replay"
TOY = {"valset175-blocksync": "benchmarks/tests/tiny/valset12-blocksync.json"}
NEW_METRICS = ["replay_verify_ms_per_block", "replay_collect_ms_per_block",
               "replay_validate_ms_per_block", "replay_store_ms_per_block",
               "replay_app_ms_per_block", "replay_receive_ms_per_block",
               "replay_blocks_per_run", "replay_lanes_per_dispatch"]


@pytest.fixture
def toy(monkeypatch):
    """The cell's own files with the toy configuration in the real one's
    place (``load_cell(..., config_files=...)``) and the sizes a CPU can
    replay in seconds: a short chain, a pool window of 40, a 64-lane run."""
    from tmtpu.blocksync import common, pool

    monkeypatch.setattr(common, "RUN_LANES", 64)
    monkeypatch.setattr(pool, "REQUEST_WINDOW", 40)
    real = spec.load_cell

    def load(name, config_files=None):
        cell = real(name, config_files)
        cell.traffic.update(chain_blocks=400, peer_window=40, warm_blocks=12,
                            stall_seconds=600)
        return cell
    monkeypatch.setattr(spec, "load_cell", load)


def _run(capfd, seed, trace="0", seconds="2"):
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
    """The fused entry under the reactor answers "verified" where its
    error says ``text``: one fault of the three is let through."""
    from tmtpu.types import commit_verify

    real = commit_verify.verify_commits_light_batch

    def lenient(entries, **kw):
        return [None if r is not None and text in str(r) else r
                for r in real(entries, **kw)]
    monkeypatch.setattr(commit_verify, "verify_commits_light_batch", lenient)


def reference_skips(monkeypatch, check):
    """The CONTROL in the reference's place: the plain replay with one
    check left out (reference/blocks.py ``Replay(skip=...)``)."""
    real = rb.Replay

    class Control(real):
        def __init__(self, vals, p, **kw):
            super().__init__(vals, p, skip=check, **kw)
    monkeypatch.setattr(rb, "Replay", Control)


PLANTS = {
    "tampered_let_through": lambda m: lets_through(m, "wrong signature"),
    "starved_let_through": lambda m: lets_through(
        m, "insufficient voting power"),
    "wrong_id_let_through": lambda m: lets_through(m, "wrong block ID"),
    "reference_skips_signatures": lambda m: reference_skips(m, "signatures"),
}


# -- the runs --------------------------------------------------------------------

def test_sound_run_is_correct(toy, capfd):
    line = _run(capfd, "201")
    assert _failed(line) == [] and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["verify_sigs_per_s"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reports_the_cells_layers(toy, capfd):
    line = _run(capfd, "202", trace="1")
    assert _failed(line) == []
    for name in NEW_METRICS + ["sigcache_hit_pct", "pad_ratio", "warm_s",
                               "sigcache_ms_per_10k", "hostprep_ms_per_10k"]:
        assert name in line["metrics"], name
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["replay_blocks_per_run"] == 5          # 64 lanes // 12
    assert m["replay_lanes_per_dispatch"] == 55     # 5 blocks x 11 present
    # two cache-hit verify_commit for every fused verification
    assert abs(m["sigcache_hit_pct"] - 200 / 3) < 0.5
    assert abs(m["pad_ratio"] - 64 / 55) < 1e-6
    assert line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("plant", sorted(PLANTS))
def test_planted_fault_fails_correct(toy, capfd, monkeypatch, plant):
    PLANTS[plant](monkeypatch)
    # a seed of its own: the sigcache is the process's
    line = _run(capfd, str(300 + sorted(PLANTS).index(plant)))
    assert line["correct"] is False
    assert "fault_outcomes_differ" in _failed(line)
    # the window itself was sound: only the tail tells
    assert not [k for k in _failed(line) if k.startswith("window_")]

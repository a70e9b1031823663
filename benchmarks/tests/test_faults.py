"""Drives the rest of a run — everything but the harness's look for a
chip — at a toy size, once sound and once for each fault the cell can
have, planted underneath the timed path, and for the control put in the
program's place. ``correct`` has to come out true for the sound run and
false for every other."""
import json
import os
import sys

import pytest

from benchmarks import run as bench_run
from benchmarks.drivers import commit_verify as cv_drv
from benchmarks.drivers import served_tx
from benchmarks.reference import commits as ref
from benchmarks.tests.conftest import TINY

HERE = os.path.dirname(os.path.abspath(__file__))


def _run(capfd, cell, seconds="2", seed="77"):
    from tmtpu.libs import log

    log.configure()     # the program's logger keeps the stream it first saw
    rc = bench_run.main(["--workload", cell, "--seed", seed, "--seconds",
                         seconds, "--trace", "0"], config_files=TINY,
                        require_chip=False)
    assert rc == 0
    out = capfd.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert list(line)[-1] == "checks"
    return line


def _failed(line):
    return sorted(k for k, v in line["checks"].items() if not v["ok"])


# -- valset.commit-verify ----------------------------------------------------

def _device_answer(monkeypatch, alter):
    """Plants ``alter(mask, power_sum, powers) -> (mask, power_sum)`` on
    what the fused device step hands back, below the batch API."""
    from tmtpu.tpu import sharding

    real = sharding.batch_verify_tally

    def altered(pks, msgs, sigs, powers):
        mask, total = real(pks, msgs, sigs, powers)
        return alter([bool(ok) for ok in mask], total, powers)
    monkeypatch.setattr(sharding, "batch_verify_tally", altered)


def _accept_all(monkeypatch):
    """An answer altered where it is produced: every lane valid."""
    _device_answer(monkeypatch, lambda mask, _t, powers:
                   ([True] * len(mask), sum(powers)))


def _half_left_out(monkeypatch):
    """Half of the batch left out: the lanes past the midpoint of every
    dispatch come back valid, with their power in the sum."""
    def first_half(mask, _t, powers):
        mask = [ok or j >= len(mask) // 2 for j, ok in enumerate(mask)]
        return mask, sum(p for p, ok in zip(powers, mask) if ok)
    _device_answer(monkeypatch, first_half)


def _tally_altered(monkeypatch):
    """The on-device tally altered where it is produced."""
    _device_answer(monkeypatch, lambda mask, total, _p: (mask, total + 3))


def _control(monkeypatch):
    """The reference put in the program's place, with the guarantee
    'one bad signature anywhere refuses the commit' broken."""
    by_height = {}
    real_pc = cv_drv._program_commit

    def remember(c, vals):
        by_height[c.height, c.block_hash] = (c, vals)
        return real_pc(c, vals)

    def control_entry(_pvals, _chain, pc):
        c, vals = by_height[pc[1], pc[0].hash]
        return ref.verify_commit(vals, c, stop_at_quorum=True)
    monkeypatch.setattr(cv_drv, "_program_commit", remember)
    monkeypatch.setattr(cv_drv, "call_entry", control_entry)


COMMIT_VERIFY = [
    (None, []),
    (_accept_all, ["adversarial_outcomes_differ"]),
    (_half_left_out, ["adversarial_outcomes_differ"]),
    (_tally_altered, ["adversarial_outcomes_differ", "tally_gap"]),
    # the control is not the program: it dispatches nothing either
    (_control, ["adversarial_lanes_dispatched", "adversarial_outcomes_differ"]),
]


@pytest.mark.parametrize("fault,expect", COMMIT_VERIFY,
                         ids=lambda f: getattr(f, "__name__", "case"))
def test_commit_verify(fault, expect, monkeypatch, capfd):
    if fault:
        fault(monkeypatch)
    # a seed of its own: the sigcache is the process's, and a signature
    # that an earlier case verified would never reach the planted fault
    seed = str(100 + [c[0] for c in COMMIT_VERIFY].index(fault))
    line = _run(capfd, "valset10k.commit-verify", seed=seed)
    assert _failed(line) == sorted(expect)
    assert line["correct"] is (not expect)
    assert line["metrics"]["verify_sigs_per_s"]["value"] > 0


# -- kvstore1.* ----------------------------------------------------------------

def _faulty_node(monkeypatch, fault):
    real = served_tx.node_argv

    def argv(home, cfg):
        full = real(home, cfg)
        return [sys.executable, os.path.join(HERE, "faulty_node.py"),
                fault] + full[full.index("start"):]
    monkeypatch.setattr(served_tx, "node_argv", argv)


def _control_env(monkeypatch):
    """The program's own lower path: admission verify switched off."""
    monkeypatch.setenv("TMTPU_MEMPOOL_VERIFY_SIGNATURES", "false")


SERVED = [
    ("kvstore1.signed-sat", None, []),
    ("kvstore1.signed-sat", "accept_all",
     ["tampered_accepted", "tampered_committed"]),
    ("kvstore1.signed-sat", "alter_value", ["readback_wrong"]),
    ("kvstore1.signed-sat", "drop_half", ["readback_wrong"]),
    ("kvstore1.signed-sat", "control",
     ["tampered_accepted", "tampered_committed"]),
    ("kvstore1.plain-sat", None, []),
    ("kvstore1.plain-sat", "control",
     ["tampered_accepted", "tampered_committed"]),
]


@pytest.mark.parametrize("cell,fault,expect", SERVED)
def test_served(cell, fault, expect, monkeypatch, capfd):
    if fault == "control":
        _control_env(monkeypatch)
    elif fault:
        _faulty_node(monkeypatch, fault)
    line = _run(capfd, cell, seconds="4")
    assert _failed(line) == sorted(expect)
    assert line["correct"] is (not expect)
    assert line["attempted"] > 0 and line["failed"] == 0

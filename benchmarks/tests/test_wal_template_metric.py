"""``live_wal_template_pct`` read off a recorded counter set: the share of
WAL records whose payload came from a drain's vote template
(``consensus_wal_records_total{path}``, consensus/state.py
``_wal_write_msgs``), as ``readers.read_metric`` reads it from a run."""
import os

from benchmarks.lib import readers
from benchmarks.lib.spec import BENCH_DIR, ROOT, load_json

NAME = "live_wal_template_pct"
CELL = "valset10k.live-rounds"
# a traced window of the cell, two heights: 2 x (9,999 prevotes + 9,999
# precommits + the node's own two) from templates; 2 x (a proposal, 17
# parts, 2 timeouts, an end-height marker) by the reflective encoder
RECORDED = {
    "tendermint_consensus_wal_records_total": {
        "path=template": 40_000.0, "path=reflective": 42.0},
    "tendermint_consensus_wal_appends_total": {"": 131.0},
    "tendermint_consensus_votes_added_total": {
        "type=prevote": 20_000.0, "type=precommit": 16_990.0},
}


def _metric():
    return load_json(os.path.join(BENCH_DIR, "metrics", NAME + ".json"))


def test_the_share_of_template_records():
    r = readers.Readings(counters={"program_counter": RECORDED})
    got = readers.read_metric(_metric(), r)
    assert abs(got - 100 * 40_000 / 40_042) < 1e-9 and got > 99.5


def test_a_program_without_the_counter_leaves_the_metric_out():
    """The parent's registry: the reader finds nothing and does not
    raise; nor where no record was written in the window."""
    parent = {k: v for k, v in RECORDED.items() if "wal_" not in k}
    assert readers.read_metric(_metric(), readers.Readings(
        counters={"program_counter": parent})) is None
    assert readers.read_metric(_metric(), readers.Readings()) is None
    idle = dict(parent, tendermint_consensus_wal_records_total={})
    assert readers.read_metric(_metric(), readers.Readings(
        counters={"program_counter": idle})) is None


def test_the_entry_is_appended_and_agrees_with_the_file():
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = bench["per_layer"][-1]
    mfile = _metric()
    assert entry == {k: mfile[k] for k in (
        "name", "unit", "better", "source", "layer", "moves", "workloads")}
    assert entry["name"] == NAME and entry["workloads"] == [CELL]
    layers = {m["layer"] for m in bench["per_layer"][:-1]}
    assert entry["layer"] in layers

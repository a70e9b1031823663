"""The per-layer metrics that read the program's own spans out of the
profiler's trace (PR 26), on two small traces recorded on a v5e:

  data/trace_v5e_verify_commit_256_spans.json   two verify_commit calls of
      a 256-validator set, 250 signatures each (record_trace.py)
  data/trace_v5e_daemon_40lane.json   1.5 s of a daemon answering 40-lane
      requests, taken through its own GET /debug/profile

Every ``trace_span`` metric file goes through ``readers.read_metric`` as a
run would send it."""
import json
import os

import pytest

from benchmarks.lib import readers, tracered
from benchmarks.lib.spec import BENCH_DIR, load_json

HERE = os.path.dirname(os.path.abspath(__file__))
LANES = 2 * 250

# metric -> the spans whose seconds it adds up
PER_10K = {
    "collect_ms_per_10k": ["commit_verify.collect"],
    "sigcache_ms_per_10k": ["batch.keys", "batch.lookup", "batch.insert"],
    "lane_loops_ms_per_10k": ["batch.fold", "batch.split", "batch.apply"],
    "hostprep_ms_per_10k": ["ed25519.prepare", "ed25519.pad"],
    "transfer_ms_per_10k": ["ed25519.device_put", "ed25519.readback"],
}


def _reduced(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return tracered.reduce_trace(json.load(f))


def _metric(name):
    return load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))


@pytest.fixture(scope="module")
def commit_run():
    red = _reduced("trace_v5e_verify_commit_256_spans.json")
    return readers.Readings(
        counters={"program_counter": {"tendermint_crypto_batch_size": {
            "curve=ed25519,backend=tpu": {"count": 2, "sum": LANES},
            "curve=sr25519,backend=tpu": {"count": 1, "sum": 999}}}},
        trace=red, window_s=red["window_s"], device_kind="TPU v5 lite")


@pytest.mark.parametrize("name", sorted(PER_10K))
def test_stage_seconds_per_10k_lanes(commit_run, name):
    spans = commit_run.trace["spans"]
    assert all(spans[s][1] == 2 for s in PER_10K[name])   # one a call
    want = sum(spans[s][0] for s in PER_10K[name]) / LANES * 1e4 * 1e3
    got = readers.read_metric(_metric(name), commit_run)
    assert got == pytest.approx(want) and got > 0


def test_stages_partition_the_call(commit_run):
    """The caller's leaves do not overlap and lie inside the call; the
    worker's stages lie inside the caller's wait for them."""
    spans = commit_run.trace["spans"]
    sec = lambda *names: sum(spans[n][0] for n in names)  # noqa: E731
    caller = sec("commit_verify.collect", "batch.keys", "batch.lookup",
                 "batch.fold", "batch.split", "batch.dispatch",
                 "batch.apply", "batch.insert")
    whole = sec("commit_verify.verify_commit")
    assert 0.95 * whole <= caller <= whole
    worker = sec("ed25519.prepare", "ed25519.pad", "ed25519.device_put",
                 "ed25519.execute", "ed25519.readback")
    assert worker <= sec("crypto.batch_verify_tally") <= sec("batch.dispatch")
    # so the idle gaps are put down to the program's stages, and the
    # harness's span round the call keeps next to nothing
    gaps = commit_run.trace["idle_gaps"]
    idle = sum(gaps.values())
    assert gaps["bench.verify_commit"] < 0.05 * idle
    assert max(gaps, key=gaps.get) == "commit_verify.collect"


def test_a_trace_without_the_spans_leaves_the_metrics_out(commit_run):
    """What the parent commit's traced run gives: no such span, no
    number, no error."""
    bare = readers.Readings(
        counters=commit_run.counters,
        trace=dict(commit_run.trace, spans={"bench.verify_commit": [1, 2]}),
        window_s=commit_run.window_s, device_kind="TPU v5 lite")
    for name in list(PER_10K) + ["sidecar_idle_pct"]:
        assert readers.read_metric(_metric(name), bare) is None
    # nor without a trace at all
    bare.trace = None
    assert readers.read_metric(_metric("collect_ms_per_10k"), bare) is None


def test_daemon_idle_share_of_the_traced_window():
    red = _reduced("trace_v5e_daemon_40lane.json")
    r = readers.Readings(trace=red, window_s=20.0,
                         device_kind="TPU v5 lite")
    idle = red["spans"]["sidecar.coalescer.idle"][0]
    got = readers.read_metric(_metric("sidecar_idle_pct"), r)
    # of the traced window, not of the run's
    assert got == pytest.approx(100 * idle / red["window_s"])
    assert 85 < got < 100
    # the dispatcher thread is in idle, linger or dispatch, never in two
    staged = idle + red["spans"]["sidecar.coalescer.dispatch"][0] + \
        red["spans"].get("sidecar.coalescer.linger", [0.0])[0]
    # (this window runs from the first device event to the last, and a
    # span that straddles an edge counts whole: at most one idle wait,
    # 50 ms, at each end)
    assert 0.95 * red["window_s"] <= staged <= red["window_s"] + 0.1
    gaps = red["idle_gaps"]
    assert max(gaps, key=gaps.get) == "sidecar.coalescer.idle"
    assert gaps[tracered.NO_SPAN] < 0.05 * sum(gaps.values())


def test_counter_metrics_read_a_registry_delta():
    """The metrics that read the daemon's and the node's registries, the
    span totals family among them."""
    r = readers.Readings(counters={
        "program_counter": {
            "tendermint_sidecar_server_queue_wait_seconds": {
                "curve=ed25519": {"count": 40, "sum": 0.1}}},
        "node_metrics": {
            "tendermint_sidecar_client_request_latency_seconds": {
                "curve=ed25519": {"count": 40, "sum": 0.4}},
            "tendermint_trace_span_seconds": {
                "name=mempool.screen": {"count": 40, "sum": 0.06},
                "name=mempool.verify": {"count": 40, "sum": 0.4},
                "name=mempool.check_tx": {"count": 40, "sum": 0.14}},
            "tendermint_mempool_batch_flushes": {"": 40},
            "tendermint_consensus_step_duration_seconds": {
                "step=NewHeight": {"count": 5, "sum": 5.0},
                "step=Propose": {"count": 5, "sum": 0.3},
                "step=Commit": {"count": 5, "sum": 0.6}},
            "tendermint_tx_latency_stage_seconds": {
                "stage=submit_to_admit_enq": {"count": 100, "sum": 0.2},
                "stage=admit_enq_to_flush": {"count": 100, "sum": 0.5},
                "stage=flush_to_admit": {"count": 100, "sum": 0.3},
                "stage=admit_to_proposal": {"count": 90, "sum": 50.0}}}})
    want = {"sidecar_queue_wait_ms": 2.5, "admission_rtt_ms": 10.0,
            "admit_host_ms_per_flush": 5.0,
            "consensus_work_ms_per_block": 180.0,
            "tx_admit_ms": 10.0, "tx_admit_ms.plain": 10.0}
    for name, value in want.items():
        assert readers.read_metric(_metric(name), r) == \
            pytest.approx(value), name

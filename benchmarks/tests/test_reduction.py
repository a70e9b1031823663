"""The yardstick's arithmetic: the trace reduction on a small trace
recorded on a v5e (data/trace_v5e_verify_commit_256.json: two verify_commit
calls of a 256-validator set, made by record_trace.py), the peaks table,
the operation count and the general metric reader."""
import json
import os

import pytest

from benchmarks.lib import readers, tracered
from benchmarks.lib.spec import BENCH_DIR, load_json

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def red():
    with open(os.path.join(HERE, "data",
                           "trace_v5e_verify_commit_256.json")) as f:
        return tracered.reduce_trace(json.load(f))


def test_busy_window_and_ops(red):
    assert red["chips"] == 1
    # the bench.window span of the recording, and the kernel's two events
    assert red["window_s"] == pytest.approx(0.04233257, rel=1e-6)
    kernel = [v for n, v in red["device_ops"].items()
              if n.startswith("_verify_pallas")]
    assert len(kernel) == 1 and kernel[0][1] == 2
    assert kernel[0][0] == pytest.approx(0.001047378, rel=1e-6)
    # busy is the union of the op intervals: at least the kernel, at most
    # the sum of every op (ops on one chip do not overlap)
    total = sum(v[0] for v in red["device_ops"].values())
    assert kernel[0][0] <= red["busy_s"] <= total + 1e-12
    assert 0 < red["busy_s"] < red["window_s"]


def test_idle_gaps_add_up_and_name_the_span(red):
    gaps = red["idle_gaps"]
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=5e-5)
    # most of the idle time lies inside the harness's own span round the
    # call, i.e. in the program's Python
    assert max(gaps, key=gaps.get) == "bench.verify_commit"
    assert red["spans"]["bench.verify_commit"][1] == 2
    bd = tracered.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0].startswith("_verify_pallas")


def test_union_and_no_device_plane():
    assert tracered._union([(0, 5), (3, 8), (10, 12), (11, 11)]) == \
        [(0, 8), (10, 12)]
    assert tracered.reduce_trace({"planes": [
        {"name": "/host:CPU", "lines": []}]}) is None


def test_synthetic_two_chips_average():
    ev = lambda a, b: ["op", a, b - a]  # noqa: E731
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [ev(0, 400), ev(600, 800)]},
            {"name": "XLA Modules", "events": [ev(0, 1000)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [ev(0, 200)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.window", 0, 1000], ["outer", 300, 600],
            ["inner", 450, 100]]}]}]}
    red = tracered.reduce_trace(trace)
    assert red["chips"] == 2
    assert red["busy_s"] == pytest.approx((600 + 200) / 2 / 1e9)
    assert red["window_s"] == pytest.approx(1e-6)
    # modules are not counted on top of their ops
    assert red["device_ops"]["op"] == [pytest.approx(800e-9), 3]


def _readings(**kw):
    return readers.Readings(
        clock={"call_s": [0.5, 0.7, 0.6]},
        counters={"program_counter": {
            "tendermint_crypto_verify_latency_seconds": {
                "curve=ed25519,backend=tpu,impl=pallas":
                    {"count": 3, "sum": 0.18}},
            "tendermint_crypto_batch_size": {
                "curve=ed25519,backend=tpu": {"count": 3, "sum": 28500}},
            "tendermint_crypto_cpu_fallback_total": {}}},
        trace={"window_s": 2.0, "busy_s": 0.06, "spans": {},
               "device_ops": {"_verify_pallas_jit.1": [0.06, 3]}},
        window_s=1.8, device_kind="TPU v5 lite", **kw)


def test_reader_reductions():
    r = _readings()
    m = lambda name: load_json(os.path.join(  # noqa: E731
        BENCH_DIR, "metrics", name + ".json"))
    call = dict(m("verify_call_p50_ms"))
    call["read"] = {"of": {"name": "call_s"}}
    assert readers.read_metric(call, r) == pytest.approx(600.0)
    host = dict(m("flush_host_pct"))
    host["read"] = dict(host["read"], den={"source": "runner_clock",
                                           "name": "call_s"})
    assert readers.read_metric(host, r) == pytest.approx(100 * (1 - .18 / 1.8))
    assert readers.read_metric(m("dispatch_ms_per_10k"), r) == \
        pytest.approx(0.18 / 28500 * 1e4 * 1e3)
    assert readers.read_metric(m("ed25519_kernel_ms_per_10k"), r) == \
        pytest.approx(0.06 / 28500 * 1e4 * 1e3)
    # a registered counter with no series reads 0; an unknown one, nothing
    assert readers.read_metric(m("cpu_fallback_lanes"), r) == 0
    assert readers.read_metric(m("pad_ratio"), r) is None
    assert readers.read_metric(m("sigcache_hit_pct"), r) is None


def test_roofline_is_work_over_peak_over_kernel_time():
    r = _readings()
    mfile = load_json(os.path.join(BENCH_DIR, "metrics",
                                   "ed25519_kernel_roofline.json"))
    ops = load_json(os.path.join(BENCH_DIR, "lib", "opcounts.json"))[
        "ed25519_verify"]["int_ops"]
    assert ops == 3000 * 20 * 20 * 2
    want = 100 * (28500 * ops / 393e12) / 0.06
    got = readers.read_metric(mfile, r)
    assert got == pytest.approx(want) and 0 < got < 1
    # no kernel event in the trace: nothing to read, never 0
    r.trace["device_ops"] = {}
    assert readers.read_metric(mfile, r) is None
    # a device that is not in the table is an error, not a default
    r = _readings()
    r.device_kind = "TPU v9"
    with pytest.raises(SystemExit):
        readers.read_metric(mfile, r)


def test_registry_delta_and_flatten():
    before = {"c": {"kind": "counter", "series": {"a=1": 5}},
              "h": {"kind": "histogram",
                    "series": {"": {"count": 2, "sum": 1.0}}},
              "g": {"kind": "gauge", "series": {"": 7}}}
    after = {"c": {"kind": "counter", "series": {"a=1": 9, "a=2": 1}},
             "h": {"kind": "histogram",
                   "series": {"": {"count": 5, "sum": 2.5}}},
             "g": {"kind": "gauge", "series": {"": 3}}}
    d = readers.registry_delta(after, before)
    assert d["c"] == {"a=1": 4, "a=2": 1}
    assert d["h"][""] == {"count": 3, "sum": 1.5}
    assert d["g"][""] == 3
    assert readers.flatten({"a": {"b": 1, "c": "x", "d": True},
                            "e": 2.5}) == {"a.b": 1, "e": 2.5}

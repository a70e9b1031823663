"""The one span system, seen three ways (libs/trace.py): in the ring, in
the ``jax.profiler`` trace while a session runs (the stage spans of
crypto/batch.py, types/commit_verify.py, sidecar/coalescer.py as
``TraceAnnotation``s of the same names), and as cumulative per-name totals
in the metric registry (``tendermint_trace_span_seconds{name}``)."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from tmtpu.crypto import ed25519 as ed
from tmtpu.libs import breaker as bk
from tmtpu.libs import metrics, trace
from tmtpu.sidecar.coalescer import Coalescer
from tmtpu.sidecar.server import SidecarServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what one verify_commit leaves on the calling thread, stage by stage
CALLER_LEAVES = ("commit_verify.collect", "batch.keys", "batch.lookup",
                 "batch.fold", "batch.split", "batch.dispatch",
                 "batch.apply", "batch.insert")


def _toy_commit(n=16):
    from tests.test_types import CHAIN_ID, mk_valset, mk_vote
    from tmtpu.types.block import BlockID
    from tmtpu.types.vote import PRECOMMIT
    from tmtpu.types.vote_set import VoteSet

    vals, pvs = mk_valset(n, power=3)
    bid = BlockID(b"\x01" * 32, 1, b"\x02" * 32)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals, verify_backend="cpu")
    vs.add_votes([mk_vote(pvs[i], vals, i, block_id=bid) for i in range(n)])
    return CHAIN_ID, vals, bid, vs.make_commit()


# -- (a) the profiler's trace holds the program's stages ---------------------


def test_profiler_trace_holds_the_stage_spans_of_a_verify_commit():
    """A ``jax.profiler`` session over a toy ``verify_commit`` on the
    device path (XLA:CPU here): the trace, reduced as the benchmark
    reduces it, names every stage, and the caller's leaves cover the
    call."""
    from benchmarks.lib import devtrace
    from tmtpu.crypto import sigcache

    chain_id, vals, bid, commit = _toy_commit()
    # building the commit verified its votes: forget them, or the call
    # would be all sigcache hits and dispatch (and compile) nothing
    sigcache.DEFAULT.invalidate_all()
    vals.verify_commit(chain_id, bid, 1, commit, backend="tpu")   # compiles
    sigcache.DEFAULT.invalidate_all()
    tracer = devtrace.Tracer(emulated=True)
    tracer.start()
    try:
        vals.verify_commit(chain_id, bid, 1, commit, backend="tpu")
    finally:
        red = tracer.stop()
    assert red is not None
    spans = red["spans"]
    for name in CALLER_LEAVES + ("batch.resolve", "ed25519.prepare",
                                 "crypto.batch_verify_tally"):
        assert name in spans and spans[name][1] == 1, (name, sorted(spans))
    whole = spans["commit_verify.verify_commit"][0]
    leaves = sum(spans[n][0] for n in CALLER_LEAVES)
    assert 0.9 * whole <= leaves <= whole
    # the worker's spans lie inside the caller's wait for them
    assert spans["crypto.batch_verify_tally"][0] <= \
        spans["batch.dispatch"][0]
    # and the ring recorded the same call under the same names
    ring = {sp.name for sp in trace.snapshot()}
    assert set(CALLER_LEAVES) <= ring


def test_a_wide_resolve_opens_each_sigcache_stage_once():
    """A 512-lane resolve (hits, misses and a duplicate among them) opens
    ``batch.keys``, ``batch.lookup``, ``batch.fold`` and ``batch.insert``
    exactly once each: a span a stage, never a span a lane."""
    from tmtpu.crypto import batch as crypto_batch

    lanes = [(ed.PubKeyEd25519(pk), msg, sig, power)
             for pk, msg, sig, power in _request_lanes(511, b"wide")]
    warm = crypto_batch.CPUBatchVerifier()
    for lane in lanes[:100]:
        warm.add(*lane)
    assert warm.verify()[0]
    bv = crypto_batch.CPUBatchVerifier()
    for lane in lanes + lanes[-1:]:
        bv.add(*lane)
    trace.drain()
    all_ok, mask, tallied = bv.verify_tally()
    assert all_ok and mask == [True] * 512 and tallied == 512
    assert bv.cache_stats == {"lanes": 512, "hits": 100, "dedup": 1,
                              "dispatched": 411}
    opened = [sp.name for sp in trace.snapshot()
              if sp.name.startswith("batch.")]
    assert sorted(opened) == ["batch.fold", "batch.insert", "batch.keys",
                              "batch.lookup", "batch.resolve"]


# -- (b) no session, no annotation; no jax, no import ------------------------


class _CountingAnnotation:
    made = 0
    enabled = False

    def __init__(self, name):
        type(self).made += 1
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_no_annotation_is_constructed_without_a_profiler_session(
        monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, "made", 0)
    tr = trace.Tracer()
    monkeypatch.setattr(_CountingAnnotation, "enabled", False)
    with tr.span("quiet"):
        with tr.span("quiet.inner"):
            pass
    assert _CountingAnnotation.made == 0
    monkeypatch.setattr(_CountingAnnotation, "enabled", True)
    with tr.span("loud"):
        pass
    assert _CountingAnnotation.made == 1
    assert [sp.name for sp in tr.snapshot()] == ["quiet.inner", "quiet",
                                                 "loud"]


def test_a_collection_in_a_profiler_session_is_an_annotation(monkeypatch):
    """The collector's hook gives a collection of generation 1 or 2 the
    annotation any span has, entered when it starts and left when it
    stops; a young one, and any without a session, gets none."""
    import gc

    import jax

    class Annotation(_CountingAnnotation):
        open_now = 0
        names = []

        def __enter__(self):
            type(self).open_now += 1
            type(self).names.append(self.name)
            return self

        def __exit__(self, *exc):
            type(self).open_now -= 1
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    was = gc.isenabled()
    gc.disable()
    try:
        Annotation.enabled = False
        gc.collect(2)
        assert Annotation.names == []
        Annotation.enabled = True
        with trace.span("gc.parent"):
            gc.collect(0)
            gc.collect(2)
    finally:
        Annotation.enabled = False
        if was:
            gc.enable()
    assert Annotation.names == ["gc.parent", "gc.collect"]
    assert Annotation.open_now == 0


def test_profiler_trace_holds_a_collection_under_its_name():
    """In a real session (XLA:CPU here) the reduced trace has the
    ``gc.collect`` span, inside the span it interrupted."""
    import gc

    import jax.numpy as jnp

    from benchmarks.lib import devtrace

    jnp.arange(8).sum().block_until_ready()
    tracer = devtrace.Tracer(emulated=True)
    tracer.start()
    try:
        with trace.span("gc.parent"):
            junk = [[i] for i in range(50_000)]
            gc.collect(2)
            del junk
        jnp.arange(8).sum().block_until_ready()
    finally:
        red = tracer.stop()
    assert red is not None
    spans = red["spans"]
    assert spans["gc.collect"][1] >= 1
    assert 0 < spans["gc.collect"][0] <= spans["gc.parent"][0]


def test_span_never_imports_jax(tmp_path):
    """The benchmark's node runs with a poisoned ``jax`` on its path (a
    sidecar node must never open the chip): spans there must not try."""
    poison = tmp_path / "jax"
    poison.mkdir()
    (poison / "__init__.py").write_text(
        'raise ImportError("this process must not import jax")\n')
    code = (
        "import sys\n"
        "from tmtpu.libs import trace, metrics\n"
        "from tmtpu.libs.breaker import call_with_deadline\n"
        "with trace.span('a'):\n"
        "    call_with_deadline(lambda: trace.span('b').__enter__(), 5.0)\n"
        "assert 'jax' not in sys.modules, sorted(sys.modules)\n"
        "assert metrics.summary()['tendermint_trace_span_seconds']"
        "['series']['name=a']['count'] == 1\n"
        "print('ok')\n")
    env = dict(os.environ, PYTHONPATH=str(tmp_path) + os.pathsep + ROOT)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# -- (c) cumulative totals outlive the ring ----------------------------------


def test_span_totals_survive_eviction_and_drain():
    tr = trace.Tracer(capacity=4)
    for _ in range(10):
        with tr.span("stage.x"):
            pass
    with pytest.raises(ValueError):
        with tr.span("stage.err"):
            raise ValueError("recorded all the same")
    assert tr.dropped == 7 and len(tr.drain()) == 4
    totals = tr.span_totals()
    assert totals["stage.x"][0] == 10 and totals["stage.x"][1] > 0
    assert totals["stage.err"][0] == 1
    tr.mark("instant")                   # a mark is not a span
    assert "instant" not in tr.span_totals()


def test_span_totals_are_a_registry_family():
    fam = "tendermint_trace_span_seconds"
    before = metrics.summary()[fam]["series"].get(
        "name=test.family", {"count": 0, "sum": 0.0})
    with trace.span("test.family"):
        time.sleep(0.01)
    trace.drain()
    got = metrics.summary()[fam]
    assert got["kind"] == "summary"
    after = got["series"]["name=test.family"]
    assert after["count"] == before["count"] + 1
    assert after["sum"] >= before["sum"] + 0.01
    text = metrics.render_prometheus()
    assert f"# TYPE {fam} summary" in text
    assert f'{fam}_count{{name="test.family"}} {after["count"]}' in text
    # the shape the benchmark's readers difference over a window
    from benchmarks.lib import readers

    delta = readers.registry_delta({fam: got}, {fam: {
        "kind": "summary", "series": {"name=test.family": before}}})
    assert delta[fam]["name=test.family"]["count"] == 1


# -- (d) the causing span survives the thread hop ----------------------------


def test_call_with_deadline_keeps_the_callers_span_as_parent():
    tr = trace.DEFAULT
    ctx = trace.TraceContext("ab" * 8, 7, "node-x")
    seen = {}

    def work():
        with trace.span("hop.child") as sp:
            seen["thread"] = threading.current_thread().name
            return sp

    with trace.activate(ctx):
        with trace.span("hop.parent") as parent:
            child = bk.call_with_deadline(work, 5.0)
    assert seen["thread"] == "deadline-call"
    assert child.parent_id == parent.span_id
    assert child.trace_id == ctx.trace_id and child.ctx_parent == 7
    # nothing of the caller is left on the worker, nor of the worker here
    assert tr.handoff() == (None, None)
    # no deadline, no hop: the ordinary nesting
    with trace.span("inline.parent") as parent:
        child = bk.call_with_deadline(work, 0)
    assert child.parent_id == parent.span_id


# -- (e), (f) the coalescer ---------------------------------------------------


def _request_lanes(n, tag):
    out = []
    for i in range(n):
        priv = ed.gen_priv_key_from_secret(b"%s-%d" % (tag, i))
        msg = b"%s msg %d" % (tag, i)
        out.append((priv.pub_key().bytes(), msg, priv.sign(msg), 1))
    return out


def test_queue_wait_observed_once_a_request_expired_ones_too():
    def engine(curve, items, tally):
        return [True] * len(items), 0

    co = Coalescer(engine)
    co.scheduler.gather_wait_s = lambda pending: 0.05
    count0, sum0 = metrics.sidecar_server_queue_wait.totals(curve="ed25519")
    co.start()
    try:
        # the first holds the batch open 50 ms; the second has expired by
        # the cut and is answered without a lane dispatched (lanes signed
        # beforehand: on a loaded host signing can outlast the 50 ms)
        lanes_a, lanes_b = _request_lanes(3, b"qa"), _request_lanes(2, b"qb")
        a = co.submit("c1", "ed25519", lanes_a, False, deadline_s=5.0)
        b = co.submit("c2", "ed25519", lanes_b, False, deadline_s=0.001)
        assert a.wait(10) and b.wait(10)
        assert a.mask == [True] * 3 and b.failure == "expired"
        c = co.submit("c1", "ed25519", _request_lanes(1, b"qc"), False)
        assert c.wait(10)
    finally:
        co.stop()
    count1, sum1 = metrics.sidecar_server_queue_wait.totals(curve="ed25519")
    assert count1 - count0 == 3
    assert 0.0 < sum1 - sum0 < 5.0


def test_dispatcher_thread_is_always_in_exactly_one_stage():
    def engine(curve, items, tally):
        time.sleep(0.01)
        return [True] * len(items), 0

    trace.drain()
    co = Coalescer(engine)
    co.scheduler.gather_wait_s = lambda pending: 0.01
    co.start()
    t0 = time.perf_counter()
    try:
        for k in range(5):
            req = co.submit("c", "ed25519", _request_lanes(2, b"s%d" % k),
                            False)
            assert req.wait(10)
            time.sleep(0.005)
    finally:
        t1 = time.perf_counter()
        co.stop()
    stages = sorted(
        (sp.start_s, sp.end_s, sp.name) for sp in trace.snapshot()
        if sp.thread_name == "sidecar-coalescer"
        and sp.name in ("sidecar.coalescer.idle", "sidecar.coalescer.linger",
                        "sidecar.coalescer.dispatch"))
    names = {s[2] for s in stages}
    assert names == {"sidecar.coalescer.idle", "sidecar.coalescer.linger",
                     "sidecar.coalescer.dispatch"}
    assert sum(1 for s in stages
               if s[2] == "sidecar.coalescer.dispatch") == 5
    for (_a0, a1, an), (b0, _b1, bn) in zip(stages, stages[1:]):
        assert a1 <= b0, f"{an} overlaps {bn}"
    # and the three account for the thread's time
    inside = sum(min(e, t1) - max(s, t0) for s, e, _n in stages
                 if e > t0 and s < t1)
    assert inside >= 0.95 * (t1 - t0)
    replies = [sp for sp in trace.snapshot()
               if sp.name == "sidecar.coalescer.reply"]
    dispatch_ids = {sp.span_id for sp in trace.snapshot()
                    if sp.name == "sidecar.coalescer.dispatch"}
    assert len(replies) == 5
    assert all(sp.parent_id in dispatch_ids for sp in replies)


# -- the operator's way to the same trace ------------------------------------


def _get(url):
    try:
        r = urllib.request.urlopen(url, timeout=60)
        return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_debug_profile_endpoint_on_the_daemons_health_listener(tmp_path):
    from benchmarks.lib import tracered

    port = _free_port()
    out_dir = tmp_path / "data" / "profile"
    srv = SidecarServer(f"unix://{tmp_path}/p.sock", backend="tpu",
                        health_laddr=f"127.0.0.1:{port}",
                        profile_dir=str(out_dir))
    srv.start()
    try:
        status, doc = _get(f"http://127.0.0.1:{port}/debug/profile"
                           f"?seconds=1.3")
        assert status == 200 and doc["dir"] == str(out_dir)
        path = tracered.find_xplane(str(out_dir))
        assert path is not None
        # the dispatcher's wait is in the profiler's trace by name
        plain = tracered.load_xplane(path)
        names = {e[0] for p in plain["planes"] for ln in p["lines"]
                 for e in ln["events"]}
        assert "sidecar.coalescer.idle" in names
        status, doc = _get(f"http://127.0.0.1:{port}/debug/profile"
                           f"?seconds=x")
        assert status == 409 and "error" in doc
    finally:
        srv.stop()


def test_debug_profile_refused_on_the_serial_engine(tmp_path):
    port = _free_port()
    srv = SidecarServer(f"unix://{tmp_path}/q.sock", backend="cpu",
                        health_laddr=f"127.0.0.1:{port}",
                        profile_dir=str(tmp_path / "profile"))
    srv.start()
    try:
        status, doc = _get(f"http://127.0.0.1:{port}/debug/profile"
                           f"?seconds=0.1")
        assert status == 409 and "serial CPU" in doc["error"]
        assert not (tmp_path / "profile").exists()
    finally:
        srv.stop()

"""Unit tests for the span tracer (tmtpu/libs/trace.py) and its wiring
into the batch-verify hot path — the observability PR's acceptance test
lives here: batch_verify under tracing must produce the phase spans with
sane nesting and non-negative durations."""

import json
import threading

import numpy as np
import pytest

from tmtpu.crypto import ed25519_ref as ref
from tmtpu.libs import trace

RNG = np.random.default_rng(11)


def _mk(n, msg_len=64):
    seeds = [bytes(RNG.integers(0, 256, 32, dtype=np.uint8))
             for _ in range(n)]
    msgs = [bytes(RNG.integers(0, 256, msg_len, dtype=np.uint8))
            for _ in range(n)]
    pks = [ref.public_key(s) for s in seeds]
    sigs = [ref.sign(s, m) for s, m in zip(seeds, msgs)]
    return pks, msgs, sigs


# --- Tracer core -----------------------------------------------------------


def test_span_records_and_nests():
    tr = trace.Tracer(capacity=64)
    with tr.span("outer", a=1) as outer:
        with tr.span("inner") as inner:
            pass
        assert inner.parent_id == outer.span_id
    spans = tr.snapshot()
    # completion order: inner closes first
    assert [s.name for s in spans] == ["inner", "outer"]
    assert spans[0].parent_id == spans[1].span_id
    assert spans[1].parent_id is None
    assert spans[1].attrs == {"a": 1}
    for s in spans:
        assert s.duration_s >= 0.0


def test_span_set_attrs_mid_region():
    tr = trace.Tracer()
    with tr.span("x") as sp:
        sp.set(lanes=42, impl="xla")
    assert tr.snapshot()[0].attrs == {"lanes": 42, "impl": "xla"}


def test_annotate_reaches_the_innermost_open_span_only():
    """A callee's way to say something about the stage its caller times:
    the innermost span open on this thread takes the attrs; with none
    open, or tracing off, nothing happens."""
    tr = trace.Tracer()
    tr.annotate(lost=1)  # no span open
    with tr.span("outer"):
        with tr.span("inner"):
            tr.annotate(impl="native", sha="libcrypto")
        tr.annotate(after=True)
    by_name = {s.name: s.attrs for s in tr.snapshot()}
    assert by_name == {"inner": {"impl": "native", "sha": "libcrypto"},
                       "outer": {"after": True}}
    tr.set_enabled(False)
    with tr.span("ghost"):
        tr.annotate(a=1)
    assert len(tr.snapshot()) == 2


def test_span_error_flag_propagates():
    tr = trace.Tracer()
    try:
        with tr.span("boom"):
            raise ValueError("x")
    except ValueError:
        pass
    sp = tr.snapshot()[0]
    assert sp.attrs.get("error") is True
    assert sp.end_s is not None


def test_ring_eviction_counts_dropped():
    tr = trace.Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.snapshot()) == 4
    assert tr.dropped == 6
    assert [s.name for s in tr.snapshot()] == ["s6", "s7", "s8", "s9"]


def test_drain_clears_and_resets():
    tr = trace.Tracer(capacity=2)
    for _ in range(3):
        with tr.span("s"):
            pass
    got = tr.drain()
    assert len(got) == 2
    assert tr.snapshot() == []
    assert tr.dropped == 0


def test_disabled_tracer_records_nothing():
    tr = trace.Tracer()
    tr.set_enabled(False)
    with tr.span("ghost") as sp:
        sp.set(a=1)  # null span absorbs attrs
    assert tr.snapshot() == []
    tr.set_enabled(True)
    with tr.span("real"):
        pass
    assert [s.name for s in tr.snapshot()] == ["real"]


def test_traced_decorator():
    tr = trace.Tracer()

    @tr.traced("my.fn")
    def add(a, b):
        return a + b

    assert add(2, 3) == 5
    assert [s.name for s in tr.snapshot()] == ["my.fn"]


def test_threads_nest_independently():
    tr = trace.Tracer()
    errs = []

    def work(i):
        try:
            with tr.span(f"outer{i}") as o:
                with tr.span(f"inner{i}") as sp:
                    assert sp.parent_id == o.span_id
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    spans = tr.snapshot()
    assert len(spans) == 16
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.parent_id is not None:
            # parent is the same thread's outer span
            assert by_id[s.parent_id].thread_id == s.thread_id


def test_summary_aggregates_per_name():
    tr = trace.Tracer()
    for _ in range(3):
        with tr.span("a"):
            pass
    with tr.span("b"):
        pass
    s = tr.summary()
    assert s["spans"]["a"]["count"] == 3
    assert s["spans"]["b"]["count"] == 1
    assert s["buffered"] == 4
    assert s["enabled"] is True
    assert s["spans"]["a"]["total_s"] >= s["spans"]["a"]["max_s"] >= 0


# --- export formats --------------------------------------------------------


def test_chrome_trace_export():
    tr = trace.Tracer()
    with tr.span("outer"):
        with tr.span("inner", lanes=8):
            pass
    doc = trace.to_chrome_trace(tr.snapshot())
    json.dumps(doc)  # must be serializable
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    assert all(e["dur"] >= 0 for e in xs)
    inner = next(e for e in xs if e["name"] == "inner")
    outer = next(e for e in xs if e["name"] == "outer")
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert inner["args"]["lanes"] == 8
    # one thread_name metadata row for the single thread
    assert len(ms) == 1 and ms[0]["args"]["name"]


def test_jsonl_export_round_trips():
    tr = trace.Tracer()
    with tr.span("a", k="v"):
        pass
    text = trace.to_jsonl(tr.snapshot())
    assert text.endswith("\n")
    rows = [json.loads(ln) for ln in text.splitlines()]
    assert rows[0]["name"] == "a"
    assert rows[0]["attrs"] == {"k": "v"}
    assert rows[0]["dur_s"] >= 0
    assert trace.to_jsonl([]) == ""


# --- acceptance: the batch-verify pipeline emits phase spans ---------------


def test_batch_verify_emits_phase_spans():
    """ISSUE acceptance: run the device flush under tracing and
    assert the pipeline phases landed as nested spans — at least four
    distinct names, every duration non-negative, children inside the
    crypto.batch_verify root."""
    from tmtpu.tpu import dispatch

    pks, msgs, sigs = _mk(8)
    trace.drain()  # isolate from earlier tests' spans
    assert dispatch.device_verify("ed25519", pks, msgs, sigs)[0].all()
    spans = trace.drain()
    names = {s.name for s in spans}
    assert len(names) >= 4, names
    assert "crypto.batch_verify" in names
    for want in ("ed25519.prepare", "ed25519.execute"):
        assert want in names, names
    by_id = {s.span_id: s for s in spans}
    root = next(s for s in spans if s.name == "crypto.batch_verify")
    assert root.attrs["lanes"] == 8
    # the prepare says which host prep and which SHA-512 fed the device
    from tmtpu import native

    prep = next(s for s in spans if s.name == "ed25519.prepare")
    how = {"impl": "native", "sha": native.sha_impls()[0]} \
        if native.load() is not None else \
        {"impl": "python", "sha": "hashlib"}
    assert prep.attrs == {"lanes": 8, **how}
    for s in spans:
        assert s.duration_s >= 0.0
        if s.parent_id is not None and s.parent_id in by_id:
            parent = by_id[s.parent_id]
            # child lies within its parent's window
            assert s.start_s >= parent.start_s - 1e-9
            assert s.end_s <= parent.end_s + 1e-9


def test_vote_set_add_votes_span():
    """The consensus-side entry (VoteSet.add_votes) wraps its batch
    dispatch in a span carrying the vote count."""
    pytest.importorskip("cryptography")  # key types need libcrypto
    from tests.test_types import CHAIN_ID, mk_valset, mk_vote
    from tmtpu.types.vote import PRECOMMIT
    from tmtpu.types.vote_set import VoteSet

    trace.drain()
    vals, pvs = mk_valset(4)
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT, vals)
    votes = [mk_vote(pvs[i], vals, i, height=1, round=0)
             for i in range(4)]
    vs.add_votes(votes)
    spans = trace.drain()
    sp = next(s for s in spans if s.name == "vote_set.add_votes")
    assert sp.attrs["votes"] == 4
    assert sp.duration_s >= 0.0

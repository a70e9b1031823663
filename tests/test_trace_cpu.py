"""A span's CPU seconds beside its wall seconds, the collector's pauses,
the process's CPU and the peers' queue (libs/trace.py, libs/metrics.py,
consensus/state.py): what tells a layer's own work from another thread's
turns at the interpreter.

The timing cases take the best of a few tries: six xdist workers on a
shared host can take a spinning thread off its core for a whole try."""

import gc
import queue
import threading
import time

import pytest

from tmtpu.config.config import ConsensusConfig
from tmtpu.consensus import state as cstate
from tmtpu.libs import metrics, trace
from tmtpu.state.state import state_from_genesis
from tmtpu.types.block import BlockID
from tmtpu.types.genesis import GenesisDoc, GenesisValidator
from tmtpu.types.priv_validator import MockPV
from tmtpu.types.vote import PREVOTE, Vote

WALL = "tendermint_trace_span_seconds"
CPU = "tendermint_trace_span_cpu_seconds"
GC = "tendermint_runtime_gc_pause_seconds"


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _some_try(attempt, tries: int = 6):
    """``attempt()`` -> None when it held, else what it read."""
    read = None
    for _ in range(tries):
        read = attempt()
        if read is None:
            return
    pytest.fail(f"never in {tries} tries; the last read {read}")


def _series(family: str, key: str) -> dict:
    return metrics.summary()[family]["series"].get(
        key, {"count": 0, "sum": 0.0})


# -- (a) a span's CPU seconds -------------------------------------------------


def test_a_sleeping_span_burns_no_cpu():
    tr = trace.Tracer()
    with tr.span("cpu.sleep") as sp:
        time.sleep(0.05)
    assert sp.duration_s >= 0.05
    assert 0.0 <= sp.cpu_s < 0.01
    assert sp.cpu_s <= sp.duration_s


def test_a_spinning_span_burns_its_wall_time():
    tr = trace.Tracer()

    def attempt():
        with tr.span("cpu.spin") as sp:
            _spin(0.05)
        if 0.6 * sp.duration_s <= sp.cpu_s <= 1.05 * sp.duration_s:
            return None
        return sp.cpu_s, sp.duration_s

    _some_try(attempt)


def test_two_spinning_threads_share_one_interpreter():
    tr = trace.Tracer()

    def attempt():
        go = threading.Barrier(2)
        spans = []

        def work():
            go.wait(timeout=10)
            with tr.span("cpu.contended") as sp:
                _spin(0.2)
            spans.append(sp)

        threads = [threading.Thread(target=work) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads) and len(spans) == 2
        longer = max(sp.duration_s for sp in spans)
        if sum(sp.cpu_s for sp in spans) <= 1.2 * longer and \
                all(sp.cpu_s < 0.8 * sp.duration_s for sp in spans):
            return None
        return [(sp.cpu_s, sp.duration_s) for sp in spans]

    _some_try(attempt)


def test_a_resumed_workers_span_holds_the_workers_cpu():
    tr = trace.Tracer()

    def attempt():
        seen = {}

        def work(token):
            with tr.resume(token):
                with tr.span("cpu.worker") as sp:
                    _spin(0.05)
                seen["worker"] = sp

        with tr.span("cpu.caller") as caller:
            t = threading.Thread(target=work, args=(tr.handoff(),))
            t.start()
            t.join(timeout=30)
        assert not t.is_alive()
        worker = seen["worker"]
        assert worker.parent_id == caller.span_id
        assert worker.thread_id != caller.thread_id
        # the caller only waited; the worker's clock is its own thread's
        if worker.cpu_s >= 0.6 * worker.duration_s and \
                caller.cpu_s < 0.5 * caller.duration_s:
            return None
        return (worker.cpu_s, worker.duration_s,
                caller.cpu_s, caller.duration_s)

    _some_try(attempt)


def test_the_cpu_clock_is_read_at_most_once_an_interval(monkeypatch):
    """A read of a thread's CPU clock is a system call: a thread makes one
    an interval, however many spans it opens, and a span an interval long
    is still read as it ends."""
    reads = []
    real = time.thread_time

    def counted():
        reads.append(threading.get_ident())
        return real()

    assert 0 < trace._CPU_CLOCK_INTERVAL_S <= 0.005
    monkeypatch.setattr(trace, "_CPU_CLOCK_INTERVAL_S", 0.05)
    monkeypatch.setattr(trace.time, "thread_time", counted)
    tr = trace.Tracer()
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < 0.3:
        with tr.span("cpu.short"):
            _spin(0.0005)
            n += 1
    assert n > 100 and 2 <= len(reads) <= 8
    # what the thread burned between two reads goes to the span open at
    # the second: here nearly always a cpu.short
    assert tr.span_cpu_totals()["cpu.short"][1] >= 0.1
    reads.clear()
    with tr.span("cpu.long") as sp:
        _spin(0.06)
    assert 1 <= len(reads) <= 2 and sp.cpu_s > 0.02
    # a thread has a clock of its own
    t = threading.Thread(target=lambda: tr.span("cpu.other").__enter__())
    t.start()
    t.join(timeout=10)
    assert reads[-1] == t.ident


def test_cpu_rides_every_export_and_a_mark_has_none():
    tr = trace.Tracer()
    with tr.span("cpu.export"):
        _spin(0.002)
    mark = tr.mark("cpu.instant")
    sp = tr.snapshot()[0]
    assert sp.cpu_s > 0 and mark.cpu_s == 0.0
    assert sp.to_dict()["cpu_s"] == round(sp.cpu_s, 9)
    ev = trace.to_chrome_trace([sp])["traceEvents"][0]
    assert ev["args"]["cpu_us"] == pytest.approx(sp.cpu_s * 1e6)
    agg = tr.summary()["spans"]["cpu.export"]
    assert agg["cpu_s"] == round(sp.cpu_s, 6) <= 1.05 * agg["total_s"] + 1e-4
    assert tr.span_totals()["cpu.export"] == (1, sp.end_s - sp.start_s)
    assert tr.span_cpu_totals() == {"cpu.export": (1, sp.cpu_s)}


def test_the_cpu_family_counts_the_spans_the_wall_family_counts():
    with trace.span("cpu.family"):
        _spin(0.002)

    def attempt():
        # spans of another test's leftover threads may end between reads
        wall0 = metrics.summary()[WALL]["series"]
        cpu = metrics.summary()[CPU]
        wall1 = metrics.summary()[WALL]["series"]
        if wall0 != wall1:
            return "the totals moved between the reads"
        assert cpu["kind"] == "summary"
        assert set(cpu["series"]) == set(wall1)
        for key, w in wall1.items():
            assert cpu["series"][key]["count"] == w["count"], key
        assert 0 < cpu["series"]["name=cpu.family"]["sum"] \
            <= 1.05 * wall1["name=cpu.family"]["sum"] + 1e-4
        return None

    _some_try(attempt)
    text = metrics.render_prometheus()
    assert f"# TYPE {CPU} summary" in text
    assert f'{CPU}_count{{name="cpu.family"}} ' in text


# -- (b) the collector's pauses -----------------------------------------------


@pytest.fixture
def quiet_collector():
    """No automatic collection while the test counts its own."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


def test_a_full_collection_is_a_counted_span_under_the_open_one(
        quiet_collector):
    assert trace.DEFAULT._on_gc in gc.callbacks
    trace.drain()
    before = _series(GC, "generation=2")
    spans0 = trace.span_totals()["gc.collect"][0]
    with trace.span("cpu.gc_parent") as parent:
        gc.collect(2)
    after = _series(GC, "generation=2")
    assert after["count"] == before["count"] + 1
    assert after["sum"] > before["sum"]
    mine = [sp for sp in trace.snapshot() if sp.name == "gc.collect"
            and sp.thread_id == parent.thread_id]
    assert len(mine) == 1
    sp = mine[0]
    assert sp.attrs["generation"] == 2 and sp.attrs["collected"] >= 0
    assert sp.parent_id == parent.span_id
    assert parent.start_s <= sp.start_s <= sp.end_s <= parent.end_s
    # the thread's clock is read at most once an interval: a span may
    # hold what its thread burned that long before it began (and the two
    # clocks are not one clock: a per cent between them is no fault)
    assert 0.0 <= sp.cpu_s <= 1.05 * sp.duration_s \
        + 2 * trace._CPU_CLOCK_INTERVAL_S
    assert trace.span_totals()["gc.collect"][0] == spans0 + 1
    text = metrics.render_prometheus()
    assert f"# TYPE {GC} summary" in text
    assert f'{GC}_count{{generation="2"}} {after["count"]}' in text


def test_a_young_collection_moves_the_counter_and_records_no_span(
        quiet_collector):
    trace.drain()
    before = {g: _series(GC, f"generation={g}") for g in (0, 1, 2)}
    spans0 = trace.span_totals()["gc.collect"][0]
    gc.collect(0)
    after = {g: _series(GC, f"generation={g}") for g in (0, 1, 2)}
    assert after[0]["count"] == before[0]["count"] + 1
    assert after[0]["sum"] >= before[0]["sum"]
    assert after[1] == before[1] and after[2] == before[2]
    me = threading.get_ident()
    assert not [sp for sp in trace.snapshot()
                if sp.name == "gc.collect" and sp.thread_id == me]
    assert trace.span_totals()["gc.collect"][0] == spans0


def test_a_disabled_tracer_still_counts_collections(quiet_collector):
    tr = trace.Tracer()
    tr.hook_gc()
    try:
        tr.set_enabled(False)
        gc.collect(1)
        assert tr.gc_pause_totals()["1"][0] == 1
        assert tr.snapshot() == []
        tr.set_enabled(True)
        gc.collect(1)
        assert tr.gc_pause_totals()["1"][0] == 2
        assert [sp.attrs["generation"] for sp in tr.snapshot()] == [1]
        tr.hook_gc()                     # hooked once however often asked
        assert gc.callbacks.count(tr._on_gc) == 1
    finally:
        gc.callbacks.remove(tr._on_gc)


def test_a_collection_started_under_the_tracers_lock_does_not_deadlock(
        quiet_collector):
    """The hook records through the lock a span takes as it ends; a
    collection that starts at an allocation made under that lock runs the
    hook on the thread that holds it."""
    done = threading.Event()

    def work():
        with trace.DEFAULT._lock:
            gc.collect(1)
        done.set()

    t = threading.Thread(target=work, daemon=True)
    t.start()
    assert done.wait(timeout=20)


# -- (c) the process's CPU ----------------------------------------------------


def test_process_cpu_seconds_rise_across_a_spin():
    fam = "tendermint_runtime_process_cpu_seconds"
    got = metrics.summary()[fam]
    assert got["kind"] == "counter"
    before = got["series"][""]
    _spin(0.05)
    after = metrics.summary()[fam]["series"][""]
    assert after > before
    text = metrics.render_prometheus()
    assert f"# TYPE {fam} counter" in text and f"\n{fam} " in text
    # the shape the benchmark's readers difference over a window
    from benchmarks.lib import readers

    delta = readers.registry_delta(
        {fam: {"kind": "counter", "series": {"": after}}},
        {fam: {"kind": "counter", "series": {"": before}}})
    assert delta[fam][""] == pytest.approx(after - before)


# -- (d) the peers' queue -----------------------------------------------------


@pytest.fixture
def cs():
    """A consensus state that never starts: its queues and its drain."""
    gen = GenesisDoc(chain_id="queue-test", genesis_time=time.time_ns(),
                     validators=[GenesisValidator(MockPV().get_pub_key(),
                                                  10)])
    return cstate.ConsensusState(ConsensusConfig.test_config(),
                                 state_from_genesis(gen), None, None)


def _vote(i: int = 0) -> Vote:
    return Vote(PREVOTE, 1, 0, BlockID(b"\x01" * 32, 1, b"\x02" * 32),
                1_700_000_000 * 10**9, b"\x03" * 20, i)


def test_a_put_on_a_full_peer_queue_is_timed_and_a_free_one_is_not(cs):
    blocked, waited = (metrics.consensus_peer_queue_blocked,
                       metrics.consensus_peer_queue_wait)
    cs.peer_msg_queue = queue.Queue(maxsize=2)
    c0, s0 = blocked.totals()
    cs.add_vote_msg(_vote(0), "p")
    cs.add_proposal(None, "p")
    assert blocked.totals() == (c0, s0)          # room: nothing moves
    assert cs.peer_msg_queue.full()

    threading.Timer(0.05, cs.peer_msg_queue.get).start()
    cs.add_block_part(1, 0, None, "p")           # blocks until the get
    c1, s1 = blocked.totals()
    assert c1 == c0 + 1 and s1 - s0 >= 0.04
    assert cs.peer_msg_queue.qsize() == 2
    assert waited.totals()[0] >= 0               # the drain's, not the put's
    key = "tendermint_consensus_peer_queue_blocked_seconds"
    got = metrics.summary()[key]
    assert got["kind"] == "summary" and got["series"][""]["count"] == c1
    text = metrics.render_prometheus()
    assert f"# TYPE {key} summary" in text and f"{key}_count {c1}" in text


def test_a_drain_counts_each_peer_message_and_how_long_it_lay(cs):
    waited = metrics.consensus_peer_queue_wait
    k = 7
    for i in range(k):
        cs.add_vote_msg(_vote(i), "p")
    # an internal message is not the peers' queue
    cs.internal_msg_queue.put(cstate.MsgInfo(cstate.VoteMessage(_vote(k))))
    time.sleep(0.02)
    c0, s0 = waited.totals()
    msgs, timeouts = cs._drain_messages()
    c1, s1 = waited.totals()
    assert len(msgs) == k + 1 and not timeouts
    assert c1 - c0 == k
    assert k * 0.02 <= s1 - s0 < k * 60.0
    # nothing queued by the peers: nothing moves
    cs.internal_msg_queue.put(cstate.MsgInfo(cstate.VoteMessage(_vote(k))))
    cs._drain_messages()
    assert waited.totals() == (c1, s1)

"""Tier-1 wiring for the unified lint engine (tmtpu/analysis).

One test runs EVERY rule against the real tree off one shared index and
holds the result to the checked-in baseline — this replaces the seven
old test_check_*.py clean-tree tests (seven separate tree walks) with a
single pass. The rest are per-rule detection fixtures: tiny synthetic
trees under tmp_path proving each rule actually flags its failure mode
(a lint that cannot detect its own violation is decoration), with extra
attention on the three deep analyzers: lock-order, blocking-lock,
determinism.

Rule ids covered here (the meta rule asserts this list stays complete):
blocking-lock, determinism, exception-safety, failpoints, jax-hygiene,
lightserve, lock-order, meta, metrics, obs-docs, recv-sync, scenarios,
sidecar, sigcache, timeline, wire-taint.
"""

from __future__ import annotations

import json

import pytest

from tmtpu.analysis import baseline as baseline_mod
from tmtpu.analysis import registry
from tmtpu.analysis.index import RepoIndex, default_index

ALL_RULES = [
    "blocking-lock", "determinism", "exception-safety", "failpoints",
    "jax-hygiene", "lightserve", "lock-order", "meta", "metrics",
    "obs-docs", "recv-sync", "scenarios", "sidecar", "sigcache",
    "timeline", "wire-taint",
]


def _tree(tmp_path, files: dict) -> RepoIndex:
    """Materialize {relpath: source} under tmp_path and index it."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return RepoIndex(str(tmp_path))


def _run(index: RepoIndex, rule_id: str):
    return registry.run(index, [rule_id])[rule_id]


def _keys(findings):
    return {f.key for f in findings}


# ---------------------------------------------------------------- real tree


def test_registry_is_complete():
    assert registry.all_rule_ids() == ALL_RULES


def test_real_tree_matches_baseline():
    """The whole rule set, one index, one process: no new findings, no
    stale suppressions. Grandfathered findings (each with a written
    justification in tools/lint_baseline.json) are allowed."""
    idx = default_index()
    results = registry.run(idx)
    assert set(results) == set(ALL_RULES)  # import rules ran too
    bl = baseline_mod.load(baseline_mod.default_path(idx.root))
    new, _suppressed, stale = baseline_mod.apply(bl, results)
    problems = [str(f) for fs in new.values() for f in fs]
    assert not problems, "NEW lint findings:\n" + "\n".join(problems)
    assert not stale, f"stale baseline suppressions: {stale}"


def test_legacy_shims_are_clean():
    """The seven old CLIs survive as shims over their rules and agree
    with the baseline-filtered result."""
    from tools import check_recv_sync, check_timeline

    assert check_timeline.check() == []
    assert check_recv_sync.check() == []  # statesync sites suppressed


def test_cli_smoke(capsys):
    from tools import lint

    assert lint.main([]) == 0
    assert lint.main(["--rule", "no-such-rule"]) == 2
    capsys.readouterr()  # drain the text-mode output
    assert lint.main(["--json", "--rule", "timeline"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["rules_run"] == ["timeline"]
    assert report["new"] == {}


def test_changed_trigger_routing():
    # a docs-only change triggers only the rules that read docs: meta
    # (rule catalog) and obs-docs (the OBSERVABILITY.md contract)
    assert registry.affected_rules(["docs/ANALYSIS.md"]) \
        == ["meta", "obs-docs"]
    assert "sidecar" in registry.affected_rules(
        ["tmtpu/sidecar/protocol.py"])
    assert "sidecar" not in registry.affected_rules(
        ["tmtpu/consensus/state.py"])


# ------------------------------------------------------------- lock-order


def test_lock_order_flags_ab_ba_inversion(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/s.py": """
import threading

class S:
    def __init__(self):
        self.a = threading.Lock()
        self.b = threading.Lock()

    def x(self):
        with self.a:
            with self.b:
                pass

    def y(self):
        with self.b:
            self.z()

    def z(self):
        with self.a:
            pass
"""})
    keys = _keys(_run(idx, "lock-order"))
    assert "lock-order::cycle::S.a<->S.b" in keys


def test_lock_order_flags_plain_lock_self_nesting(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/t.py": """
import threading

class T:
    def __init__(self):
        self.m = threading.Lock()
        self.r = threading.RLock()

    def outer(self):
        with self.m:
            self.inner()

    def inner(self):
        with self.m:
            pass

    def router(self):
        with self.r:
            self.rinner()

    def rinner(self):
        with self.r:
            pass
"""})
    keys = _keys(_run(idx, "lock-order"))
    assert "lock-order::self::T.m" in keys     # Lock: deadlock
    assert "lock-order::self::T.r" not in keys  # RLock: re-entry is fine


def test_lock_order_resolves_condition_aliasing(tmp_path):
    # Condition(self.m) IS self.m: waiting-with-the-lock-held patterns
    # must not spawn a phantom second lock, and nesting the condition
    # under its own mutex is a real self-deadlock for a plain Lock
    idx = _tree(tmp_path, {"tmtpu/c.py": """
import threading

class C:
    def __init__(self):
        self.m = threading.Lock()
        self.cv = threading.Condition(self.m)

    def f(self):
        with self.m:
            with self.cv:
                pass
"""})
    keys = _keys(_run(idx, "lock-order"))
    assert "lock-order::self::C.m" in keys
    assert not any("C.cv" in k for k in keys)


# ----------------------------------------------------------- blocking-lock


def test_blocking_lock_flags_sleep_under_hot_lock(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/s.py": """
import threading
import time

class FooState:
    def __init__(self):
        self._mtx = threading.RLock()

    def handle(self):
        with self._mtx:
            self._work()

    def _work(self):
        time.sleep(0.1)
"""})
    keys = _keys(_run(idx, "blocking-lock"))
    assert ("blocking-lock::FooState._mtx::sleep:time.sleep"
            "::tmtpu/s.py::FooState._work") in keys


def test_blocking_lock_flags_abci_on_recv_thread(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/r.py": """
class MyReactor(Reactor):
    def receive(self, chid, peer, payload):
        self._serve()

    def _serve(self):
        return self.proxy.query_sync(payload)
"""})
    keys = _keys(_run(idx, "blocking-lock"))
    assert ("blocking-lock::recv::MyReactor::abci-sync:query_sync"
            "::tmtpu/r.py::MyReactor._serve") in keys


def test_blocking_lock_ignores_cold_locks(tmp_path):
    # same sleep, but the lock is not in the hot set and no reactor is
    # involved — must stay quiet
    idx = _tree(tmp_path, {"tmtpu/s.py": """
import threading
import time

class Store:
    def __init__(self):
        self._disk_lock = threading.Lock()

    def flush(self):
        with self._disk_lock:
            time.sleep(0.1)
"""})
    assert _run(idx, "blocking-lock") == []


# ------------------------------------------------------------ determinism


def test_determinism_flags_wall_clock_on_replay_path(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/cs.py": """
import time

class ConsensusState:
    def _handle_msgs(self, msgs):
        for m in msgs:
            self._apply(m)

    def _apply(self, m):
        stamp = time.time()
        tick = time.monotonic()
        return stamp, tick
"""})
    keys = _keys(_run(idx, "determinism"))
    assert ("determinism::wallclock:time.time::tmtpu/cs.py"
            "::ConsensusState._apply") in keys
    # monotonic is observability-only: exempt
    assert not any("monotonic" in k for k in keys)


def test_determinism_flags_unseeded_random_and_set_iteration(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/ex.py": """
import random

class BlockExecutor:
    def apply_block(self, state, block):
        nonce = random.random()
        total = 0
        for tx in set(block.txs):
            total += len(tx)
        return nonce, total
"""})
    keys = _keys(_run(idx, "determinism"))
    assert ("determinism::random:random.random::tmtpu/ex.py"
            "::BlockExecutor.apply_block") in keys
    assert ("determinism::set-iter::tmtpu/ex.py"
            "::BlockExecutor.apply_block") in keys


def test_determinism_ignores_unreachable_nondeterminism(tmp_path):
    # wall clock in a method the seeds never call: not a finding
    idx = _tree(tmp_path, {"tmtpu/cs.py": """
import time

class ConsensusState:
    def _handle_msgs(self, msgs):
        return len(msgs)

    def metrics_tick(self):
        return time.time()
"""})
    assert _run(idx, "determinism") == []


# ------------------------------------------------------------- failpoints


def test_failpoints_flags_duplicates_and_untested_sites(tmp_path):
    idx = _tree(tmp_path, {
        "tmtpu/a.py": 'faultinject.register("wal.crash")\n',
        "tmtpu/b.py": 'faultinject.register("wal.crash")\n'
                      'faultinject.register("exec.stall")\n',
        "tests/test_x.py": 'TMTPU_FAULTS = "exec.stall=crash"\n',
    })
    keys = _keys(_run(idx, "failpoints"))
    assert "failpoints::dup::wal.crash" in keys
    assert "failpoints::untested::wal.crash" in keys
    assert "failpoints::untested::exec.stall" not in keys


# ---------------------------------------------------------------- metrics


def test_metrics_flags_dead_unknown_and_unrendered(tmp_path):
    idx = _tree(tmp_path, {
        "tmtpu/libs/metrics.py":
            'dead = DEFAULT.counter("consensus", "dead")\n'
            'live = DEFAULT.gauge("consensus", "live")\n',
        "tmtpu/code.py":
            "live.set(1)\n"
            # split so the metrics rule's write-site scan of the real
            # tree does not match this fixture literal in THIS file
            "consensus_gh" "ost.inc()\n"
            'rogue = Counter("x", "y")\n',
    })
    keys = _keys(_run(idx, "metrics"))
    assert "metrics::dead::dead" in keys
    assert "metrics::dead::live" not in keys
    assert "metrics::unknown::consensus_ghost" in keys
    assert "metrics::ctor::tmtpu/code.py::Counter" in keys


def test_metrics_counts_a_metric_handed_to_its_writer(tmp_path):
    """A metric passed as a value to the code that writes it (the
    framed-daemon core takes each daemon's metrics so) is not dead; one
    only read through an attribute still is."""
    idx = _tree(tmp_path, {
        "tmtpu/libs/metrics.py":
            'handed = DEFAULT.gauge("p2p", "handed")\n'
            'peeked = DEFAULT.gauge("p2p", "peeked")\n'
            'passed = DEFAULT.counter("p2p", "passed")\n',
        "tmtpu/code.py":
            "class Daemon:\n"
            "    gauge = _m.handed\n"
            "queue = Queue(seen=_m.peeked.summary_series(),\n"
            "              overloads=_m.passed)\n",
    })
    keys = _keys(_run(idx, "metrics"))
    assert "metrics::dead::handed" not in keys
    assert "metrics::dead::passed" not in keys
    assert "metrics::dead::peeked" in keys


# --------------------------------------------------------------- obs-docs


def test_obs_docs_flags_undocumented_surface(tmp_path):
    """A tree exporting tx-lifecycle names without OBSERVABILITY.md rows
    is flagged per missing name; documenting them clears the findings;
    a tree with no tx-lifecycle surface passes vacuously."""
    files = {
        "tmtpu/libs/metrics.py":
            'tx_latency_x = DEFAULT.counter("tx", "latency_x_total")\n',
        "tmtpu/libs/txlat.py":
            'TX_STAGES = ("submit", "commit")\n',
    }
    idx = _tree(tmp_path, files)
    keys = _keys(_run(idx, "obs-docs"))
    assert "obs-docs::no-doc" in keys

    (tmp_path / "docs").mkdir()
    (tmp_path / "docs/OBSERVABILITY.md").write_text(
        "| `tendermint_tx_latency_x_total` | ... |\n"
        "| `submit` | ... |\n")
    keys = _keys(_run(RepoIndex(str(tmp_path)), "obs-docs"))
    assert "obs-docs::stage::commit" in keys
    assert "obs-docs::event::tx_latency" in keys
    assert "obs-docs::metric::tendermint_tx_latency_x_total" not in keys

    bare = _tree(tmp_path / "bare", {"tmtpu/empty.py": "x = 1\n"})
    assert _run(bare, "obs-docs") == []


# -------------------------------------------------------------- recv-sync


def test_recv_sync_walks_helpers_transitively(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/r.py": """
class SlowReactor(Reactor):
    def receive(self, chid, peer, payload):
        self._level1()

    def _level1(self):
        self._level2()

    def _level2(self):
        self.app.commit_sync()

class CleanReactor(Reactor):
    def receive(self, chid, peer, payload):
        self.queue.append(payload)
"""})
    keys = _keys(_run(idx, "recv-sync"))
    assert ("tmtpu/r.py::SlowReactor._level2::commit_sync") in keys
    assert not any("CleanReactor" in k for k in keys)


# --------------------------------------------------------------- sigcache


def test_sigcache_flags_serial_verify_and_unbatched_commit(tmp_path):
    idx = _tree(tmp_path, {
        "tmtpu/consensus/hot.py":
            "def f(pk, msg, sig):\n"
            "    return pk.verify_signature(msg, sig)\n",
        "tmtpu/crypto/impl.py":
            "def g(pk, msg, sig):\n"
            "    return pk.verify_signature(msg, sig)\n",
        "tmtpu/types/commit_verify.py":
            "def verify_commit(c):\n"
            "    return all(v.verify_signature() for v in c)\n"
            "def verify_commit_light(c):\n"
            "    bv = new_batch_verifier()\n"
            "    return bv\n"
            "def verify_commit_light_trusting(c):\n"
            "    return _verify_lanes(c)\n"
            "def _verify_lanes(c):\n"
            "    return True\n"
            "def verify_commits_light_batch(cs):\n"
            "    return [verify_commit_light(c) for c in cs]\n",
    })
    keys = _keys(_run(idx, "sigcache"))
    assert "sigcache::serial::tmtpu/consensus/hot.py" in keys
    # crypto/ is the oracle layer: allowed
    assert "sigcache::serial::tmtpu/crypto/impl.py" not in keys
    # verify_commit loops serial verifies (the dump also contains the
    # verify_signature text, so it passes the coarse body check — the
    # serial rule still catches its call site); commit_verify.py itself
    # is flagged for the raw verify_signature call
    assert "sigcache::serial::tmtpu/types/commit_verify.py" in keys
    assert "sigcache::missing::verify_commit" not in keys


# --------------------------------------------------------------- timeline


def test_timeline_flags_span_and_declaration_drift(tmp_path):
    idx = _tree(tmp_path, {
        "tmtpu/libs/timeline.py":
            'CONSENSUS_STEP_EVENTS = ("consensus.propose",)\n',
        "tmtpu/consensus/state.py":
            'timeline.record(h, "consensus.commit_exec")\n'
            'trace.span("consensus.commit_exec")\n',
    })
    keys = _keys(_run(idx, "timeline"))
    # declared step with no span literal anywhere
    assert "timeline::step-span::consensus.propose" in keys
    # recorded + span-matched but missing from the declared tuple
    assert "timeline::undeclared::consensus.commit_exec" in keys
    assert "timeline::recorded-span::consensus.commit_exec" not in keys


# ------------------------------------------- scenarios / sidecar / meta


def test_import_rules_skip_synthetic_trees(tmp_path):
    """scenarios, sidecar, lightserve, and meta import runtime
    registries (or read repo-level docs), so they must skip cleanly on
    fixture trees instead of crashing or reporting nonsense."""
    idx = _tree(tmp_path, {"tmtpu/empty.py": "x = 1\n"})
    results = registry.run(
        idx, ["scenarios", "sidecar", "lightserve", "meta"])
    assert results == {}


def test_unknown_rule_is_an_error():
    with pytest.raises(KeyError):
        registry.run(default_index(), ["no-such-rule"])


# ---------------------------------------------------------------- baseline


def test_baseline_apply_and_update_semantics(tmp_path):
    from tmtpu.analysis.findings import Finding

    f1 = Finding("r", "a.py", "m1", key="r::k1")
    f2 = Finding("r", "a.py", "m2", key="r::k2")
    bl = {"rules": {"r": {"status": "suppressions", "suppressions": [
        {"key": "r::k1", "reason": "grandfathered"},
        {"key": "r::gone", "reason": "stale"},
    ]}}}
    new, suppressed, stale = baseline_mod.apply(bl, {"r": [f1, f2]})
    assert _keys(new["r"]) == {"r::k2"}
    assert _keys(suppressed["r"]) == {"r::k1"}
    assert stale == {"r": ["r::gone"]}

    updated = baseline_mod.update(bl, {"r": [f1, f2]})
    sups = {s["key"]: s["reason"]
            for s in updated["rules"]["r"]["suppressions"]}
    assert sups["r::k1"] == "grandfathered"     # old reason survives
    assert sups["r::k2"] == baseline_mod.TODO_REASON
    assert "r::gone" not in sups                # vanished key dropped

    updated = baseline_mod.update(bl, {"r": []})
    assert updated["rules"]["r"] == {"status": "clean"}


# -------------------------------------------------------------- wire-taint


def test_wire_taint_follows_queue_handoff(tmp_path):
    """receive() enqueues raw wire bytes; a state-thread handler drains
    the queue and tallies them with no verification in between — the
    channel fixpoint must carry the taint across the thread handoff."""
    idx = _tree(tmp_path, {"tmtpu/consensus/r.py": """
class VoteReactor(Reactor):
    def __init__(self):
        self._q = queue.Queue()
        self.votes = VoteSet()

    def receive(self, chid, peer, msg_bytes):
        self._q.put(msg_bytes)

    def _handle(self):
        msg = self._q.get()
        self.votes.add_verified_vote(msg)
"""})
    keys = _keys(_run(idx, "wire-taint"))
    assert any("tally" in k and "wire" in k for k in keys), keys


def test_wire_taint_sanitizer_launders_the_frame(tmp_path):
    """The same flow with a verify_one() gate between the drain and the
    sink is the sanctioned shape — no finding."""
    idx = _tree(tmp_path, {"tmtpu/consensus/r.py": """
class VoteReactor(Reactor):
    def __init__(self):
        self._q = queue.Queue()
        self.votes = VoteSet()

    def receive(self, chid, peer, msg_bytes):
        self._q.put(msg_bytes)

    def _handle(self):
        msg = self._q.get()
        if not verify_one(msg.pk, msg.data, msg.sig):
            return
        self.votes.add_verified_vote(msg)
"""})
    assert _run(idx, "wire-taint") == []


def test_wire_taint_direct_sink_and_rpc_params(tmp_path):
    idx = _tree(tmp_path, {
        "tmtpu/consensus/w.py": """
class WalReactor(Reactor):
    def receive(self, chid, peer, msg_bytes):
        self.wal.write(msg_bytes)
""",
        "tmtpu/rpc/core.py": """
def build_routes(env):
    def broadcast_tx_sync(tx):
        env.signer.sign_vote(tx)
    return {"broadcast_tx_sync": broadcast_tx_sync}
""",
    })
    keys = _keys(_run(idx, "wire-taint"))
    assert any("wal-write" in k for k in keys), keys
    assert any("privval-sign" in k and "rpc" in k for k in keys), keys


# -------------------------------------------------------- exception-safety


def test_exception_safety_lock_across_raise(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/consensus/l.py": """
class S:
    def bad(self):
        self._mtx.acquire()
        self.apply(self.block)
        self._mtx.release()

    def good(self):
        with self._mtx:
            self.apply(self.block)
            raise ValueError("scoped release is exception-safe")

    def also_good(self):
        self._mtx.acquire()
        try:
            self.apply(self.block)
        finally:
            self._mtx.release()
"""})
    keys = _keys(_run(idx, "exception-safety"))
    assert "exception-safety::lock-across-raise::tmtpu/consensus/l.py" \
           "::S.bad::self._mtx" in keys
    assert not any("good" in k for k in keys), keys


def test_exception_safety_unjoined_thread(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/p2p/t.py": """
import threading

class Leaky:
    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def stop(self):
        self._stopped.set()

class Clean:
    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def stop(self):
        self._stopped.set()
        t = self._t
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2.0)
"""})
    keys = _keys(_run(idx, "exception-safety"))
    assert "exception-safety::unjoined-thread::tmtpu/p2p/t.py" \
           "::Leaky._t" in keys
    assert not any("Clean" in k for k in keys), keys


def test_exception_safety_unclosed_resource_and_with_alias(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/state/f.py": """
def leak(path):
    f = open(path, "rb")
    data = f.read(4)
    return data

def closed_by_with_alias(path):
    f = open(path, "rb")
    with f:
        return f.read()
"""})
    keys = _keys(_run(idx, "exception-safety"))
    assert "exception-safety::unclosed-resource::tmtpu/state/f.py" \
           "::leak::f" in keys
    assert not any("closed_by_with_alias" in k for k in keys), keys


def test_exception_safety_breaker_leak_and_delegated_failure(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/tpu/b.py": """
def leaky(pbr, dev):
    if pbr.allow():
        out = run_kernel(dev)
        pbr.record_success()
    return out

def delegated(pbr, dev):
    if pbr.allow():
        try:
            out = run_kernel(dev)
            pbr.record_success()
        except Exception as e:
            note_pallas_failure(pbr, e)
            out = run_fallback(dev)
    return out
"""})
    keys = _keys(_run(idx, "exception-safety"))
    assert "exception-safety::breaker-leak::tmtpu/tpu/b.py::leaky" in keys
    assert not any("delegated" in k for k in keys), keys


# ------------------------------------------------------------- jax-hygiene


def test_jax_hygiene_host_sync_on_hot_flush_path(tmp_path):
    """A .item() readback reached through a helper from _verify_pending
    is a per-flush device stall; the same marker on a cold path (outside
    the dispatch tier) is exempt."""
    idx = _tree(tmp_path, {
        "tmtpu/crypto/batch.py": """
class BatchVerifier:
    def _verify_pending(self):
        mask = self._flush()
        return self._count(mask)

    def _count(self, mask):
        return mask.sum().item()
""",
        "tmtpu/consensus/cold.py": """
def config_height(arr):
    return arr[0].item()
""",
    })
    keys = _keys(_run(idx, "jax-hygiene"))
    assert any("host-sync:item" in k and "crypto/batch.py" in k
               for k in keys), keys
    assert not any("cold" in k for k in keys), keys


def test_jax_hygiene_bucket_bypass_and_quantized_dispatch(tmp_path):
    idx = _tree(tmp_path, {"tmtpu/tpu/k.py": """
import jax

@jax.jit
def _verify_jit(dev):
    return dev

def raw_dispatch(dev):
    return _verify_jit(dev)

def bucketed_dispatch(dev, n):
    return _verify_jit(pad_packed(dev, padded_lanes(n)))
"""})
    keys = _keys(_run(idx, "jax-hygiene"))
    assert "jax-hygiene::bucket-bypass::tmtpu/tpu/k.py::raw_dispatch" \
           "::_verify_jit" in keys
    assert not any("bucketed_dispatch" in k for k in keys), keys


def test_jax_hygiene_unguarded_dispatch_vs_breaker(tmp_path):
    """device_verify outside tmtpu/tpu/ needs breaker discipline; the
    sync point behind a breaker fallback (pbr.allow() in frame) is the
    sanctioned shape and stays clean."""
    idx = _tree(tmp_path, {"tmtpu/consensus/v.py": """
def naked(pks, msgs, sigs):
    return device_verify("ed25519", pks, msgs, sigs)

def guarded(pks, msgs, sigs, pbr):
    if not pbr.allow():
        return [one_verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    return device_verify("ed25519", pks, msgs, sigs)
"""})
    keys = _keys(_run(idx, "jax-hygiene"))
    assert "jax-hygiene::unguarded-dispatch::tmtpu/consensus/v.py" \
           "::naked::device_verify" in keys
    assert not any("::guarded::" in k for k in keys), keys


# ------------------------------------------------------------ result cache


def test_result_cache_roundtrip_and_invalidation(tmp_path):
    from tmtpu.analysis.cache import ResultCache

    files = {"tmtpu/consensus/l.py": """
class S:
    def bad(self):
        self._mtx.acquire()
        self.apply(self.block)
        self._mtx.release()
"""}
    idx = _tree(tmp_path, files)
    cache = ResultCache(str(tmp_path))
    stats: dict = {}
    r1 = registry.run(idx, ["exception-safety"], cache=cache, stats=stats)
    assert stats["exception-safety"]["cached"] is False
    cache.save()

    # warm: same tree, fresh cache object -> served from disk
    cache2 = ResultCache(str(tmp_path))
    stats2: dict = {}
    r2 = registry.run(idx, ["exception-safety"], cache=cache2,
                      stats=stats2)
    assert stats2["exception-safety"]["cached"] is True
    assert _keys(r2["exception-safety"]) == _keys(r1["exception-safety"])

    # an edit (content + size change) invalidates
    (tmp_path / "tmtpu/consensus/l.py").write_text("x = 1\n")
    idx3 = RepoIndex(str(tmp_path))
    cache3 = ResultCache(str(tmp_path))
    stats3: dict = {}
    r3 = registry.run(idx3, ["exception-safety"], cache=cache3,
                      stats=stats3)
    assert stats3["exception-safety"]["cached"] is False
    assert r3["exception-safety"] == []


def test_result_cache_doc_edit_invalidates_doc_reading_rule(tmp_path):
    """The index only knows .py files, but obs-docs reads
    docs/OBSERVABILITY.md — the fingerprint must cover non-Python files
    under the rule's triggers too, or a doc edit keeps serving the
    findings from before the edit (exactly the staleness that once
    broke the warm pre-commit gate)."""
    from tmtpu.analysis.cache import ResultCache

    _tree(tmp_path, {
        "tmtpu/libs/metrics.py":
            'tx_latency_x = DEFAULT.counter("tx", "latency_x_total")\n',
        "tmtpu/libs/txlat.py": 'TX_STAGES = ("submit",)\n',
    })
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs/OBSERVABILITY.md").write_text("nothing yet\n")
    cache = ResultCache(str(tmp_path))
    stats: dict = {}
    r1 = registry.run(RepoIndex(str(tmp_path)), ["obs-docs"],
                      cache=cache, stats=stats)
    assert "obs-docs::metric::tendermint_tx_latency_x_total" \
        in _keys(r1["obs-docs"])
    cache.save()

    # document everything: the doc edit ALONE must invalidate
    (tmp_path / "docs/OBSERVABILITY.md").write_text(
        "| `tendermint_tx_latency_x_total` | ... |\n"
        "| `submit` | ... |\n"
        "| `tx_latency` | ... |\n")
    cache2 = ResultCache(str(tmp_path))
    stats2: dict = {}
    r2 = registry.run(RepoIndex(str(tmp_path)), ["obs-docs"],
                      cache=cache2, stats=stats2)
    assert stats2["obs-docs"]["cached"] is False
    assert r2["obs-docs"] == []


def test_cli_sarif_output(capsys):
    from tools import lint

    assert lint.main(["--format", "sarif", "--rule", "blocking-lock",
                      "--no-cache"]) == 0
    sarif = json.loads(capsys.readouterr().out)
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert run["tool"]["driver"]["name"] == "tmtpu-lint"
    assert run["tool"]["driver"]["rules"][0]["id"] == "blocking-lock"
    # the baselined findings surface as suppressed results, not failures
    assert all("suppressions" in r for r in run["results"])
    assert all(r["partialFingerprints"]["lintKey"] for r in run["results"])


def test_cli_update_baseline_prunes_and_writes_meta(tmp_path, capsys,
                                                   monkeypatch):
    from tools import lint

    meta_path = tmp_path / "lint_meta.json"
    monkeypatch.setattr(lint, "META_PATH", str(meta_path))
    bl_path = tmp_path / "baseline.json"
    bl_path.write_text(json.dumps({"rules": {"timeline": {
        "status": "suppressions", "suppressions": [
            {"key": "timeline::gone::xyz", "reason": "stale entry"}]}}}))
    assert lint.main(["--rule", "timeline", "--no-cache",
                      "--baseline", str(bl_path),
                      "--update-baseline"]) == 0
    out = capsys.readouterr().out
    assert "pruned stale suppression [timeline] 'timeline::gone::xyz'" \
           in out
    assert json.loads(bl_path.read_text())["rules"]["timeline"] == \
           {"status": "clean"}
    meta = json.loads(meta_path.read_text())
    assert meta["rules"]["timeline"]["findings"] == 0
    assert meta["rules"]["timeline"]["seconds"] >= 0

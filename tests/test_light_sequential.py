"""The light client in sequential mode against the plain reference of a
light client's sync (benchmarks/reference/light.py, which imports nothing of
the program): a chain's light blocks fabricated from a seed are served by
in-process providers (the benchmark driver's,
benchmarks/drivers/light_sync.py) as protobuf bytes, and the client
``tmtpu light --sequential`` builds proves every header. CPU backend, 12
validators: the cell ``light175.sequential`` at a toy size."""

import ast
import os

import pytest

from benchmarks.drivers import light_sync as drv
from benchmarks.reference import light as rl
from tmtpu.blocksync import common, reactor
from tmtpu.crypto import batch as crypto_batch
from tmtpu.libs import metrics, trace
from tmtpu.libs.db import MemDB
from tmtpu.light import client as light_client
from tmtpu.light import verifier
from tmtpu.light.client import Client, SEQUENTIAL, SKIPPING, TrustOptions
from tmtpu.light.store import LightStore
from tmtpu.types import commit_verify as cv

CHAIN_ID = "light-seq-test"
N_VAL, N_ABSENT = 12, 1
PERIOD_NS = 14 * 86400 * 10**9
SESSION = 18            # runs of 5, 5, 5 and a tail of 3 at 64 lanes


@pytest.fixture(scope="module")
def chain60():
    spec = rl.ChainSpec(21, CHAIN_ID, 1_700_000_000 * 10**9, N_VAL, 1,
                        N_ABSENT)
    _vals, chain = rl.make_chain(spec, 60)
    return spec, chain


@pytest.fixture(autouse=True)
def _cpu_backend(monkeypatch):
    monkeypatch.setattr(crypto_batch, "_default_backend", "cpu")
    monkeypatch.setattr(common, "RUN_LANES", 64)


class _Pair:
    """The client of the cell, its providers, and the reference beside it."""

    def __init__(self, chain, mode=SEQUENTIAL, pruning_size=1000, **kw):
        self.chain = chain
        self.wire = {lb.height: lb.wire for lb in chain}
        self.now = chain[-1].header.time_ns + 10**9
        self.primary = drv.Serving("primary", self.wire)
        self.witness = drv.Serving("witness", self.wire)
        self.client = Client(
            CHAIN_ID, TrustOptions(PERIOD_NS, 1, chain[0].header.hash),
            self.primary, witnesses=[self.witness],
            store=LightStore(MemDB()), mode=mode, pruning_size=pruning_size,
            **kw)
        self.sync = rl.Sync(CHAIN_ID, chain[0], PERIOD_NS,
                            light_client.DEFAULT_MAX_CLOCK_DRIFT_NS,
                            pruning_size)

    def honest(self, h):
        return self.chain[h - 1]

    def stored(self):
        return {int(k[3:]): v
                for k, v in self.client.store.db.iter_prefix(b"lb/")}


def _counter(name, field=None, **labels):
    series = getattr(metrics, name).summary_series()
    key = ",".join(f"{k}={v}" for k, v in labels.items())
    if labels:
        v = series.get(key, 0)
        return v[field] if field and v else v
    return sum(v[field] if field else v for v in series.values())


# -- the sound chain -----------------------------------------------------------

def test_sync_ends_where_the_reference_does(chain60):
    _spec_, chain = chain60
    pair = _Pair(chain)
    for target in (19, 37, 55):
        pair.client.verify_light_block_at_height(target, pair.now)
        want = pair.sync.session(pair.honest, target, pair.now,
                                 witness=pair.honest)
        assert want.refused is None and want.trusted[-1] == target
        assert pair.client.last_trusted_height() == target
    # what the store holds is what was served, byte for byte
    assert pair.stored() == pair.sync.stored
    assert sorted(pair.stored()) == list(range(1, 56))


def test_the_store_keeps_the_newest_pruning_size(chain60):
    _spec_, chain = chain60
    pair = _Pair(chain, pruning_size=10)
    pair.client.verify_light_block_at_height(40, pair.now)
    pair.sync.session(pair.honest, 40, pair.now, witness=pair.honest)
    assert sorted(pair.stored()) == list(range(31, 41))
    assert pair.stored() == pair.sync.stored
    assert pair.client.first_trusted_height() == 31


def test_the_programs_light_block_is_the_references_bytes(chain60):
    from tmtpu.types import pb
    from tmtpu.types.light_block import LightBlock

    _spec_, chain = chain60
    for lb in chain[:3] + chain[-2:]:
        plb = LightBlock.from_proto(pb.LightBlock.decode(lb.wire))
        plb.validate_basic(CHAIN_ID)
        assert plb.header.hash() == lb.header.hash
        assert plb.to_proto().encode() == lb.wire
        assert plb.validator_set.proposer.address == \
            lb.vals.addrs[lb.proposer]
        assert [v.proposer_priority for v in plb.validator_set.validators] \
            == lb.priorities


def test_workers_sign_the_chain_a_single_process_signs(chain60):
    spec, chain = chain60
    _vals, again = rl.make_chain(spec, 60, workers=2)
    assert [lb.wire for lb in again] == [lb.wire for lb in chain]


# -- the faults ------------------------------------------------------------------

@pytest.mark.parametrize("kind,reason", [
    ("tampered", rl.BAD_SIGNATURE), ("starved", rl.LOW_POWER),
    ("broken_link", rl.BROKEN_LINK),
    ("witness_fork", rl.CONFLICTING_WITNESS)])
def test_fault_is_refused_as_the_reference_refuses_it(chain60, kind, reason):
    spec, chain = chain60
    pair = _Pair(chain)
    pair.client.verify_light_block_at_height(19, pair.now)
    pair.sync.session(pair.honest, 19, pair.now, witness=pair.honest)
    lie_p, lie_w, at = drv.fault_plan(kind, spec, chain, 19, SESSION, 5, 21)
    want = pair.sync.session(lambda h: lie_p.get(h) or chain[h - 1], 37,
                             pair.now,
                             witness=lambda h: lie_w.get(h) or chain[h - 1])
    assert want.refused == (at, reason) and want.trusted == []
    pair.primary.lie = {h: lb.wire for h, lb in lie_p.items()}
    pair.witness.lie = {h: lb.wire for h, lb in lie_w.items()}
    with pytest.raises(verifier.LightError) as ei:
        pair.client.verify_light_block_at_height(37, pair.now)
    assert drv.refusal(ei.value) == want.refused
    # a refused session stores nothing: trust stands where it stood
    assert pair.client.last_trusted_height() == pair.sync.last.height == 19
    assert sorted(pair.stored()) == list(range(1, 20))
    assert len(pair.primary.reported) == want.evidence_to_primary == \
        (1 if kind == "witness_fork" else 0)
    # and the same session from honest providers is trusted after it
    pair.primary.lie, pair.witness.lie = {}, {}
    pair.client.verify_light_block_at_height(37, pair.now)
    assert pair.sync.session(pair.honest, 37, pair.now,
                             witness=pair.honest).trusted[-1] == 37
    assert pair.stored() == pair.sync.stored


def test_a_signature_after_the_two_thirds_point_is_still_verified(chain60):
    """Stricter than VerifyCommitLight's early exit: the tampered slot lies
    beyond the point where more than 2/3 of the power is tallied."""
    _spec_, chain = chain60
    bad = rl.tampered(chain[24], 21)
    slot = next(i for i, (a, b) in enumerate(zip(bad.commit.sigs,
                                                 chain[24].commit.sigs))
                if a != b)
    before = sum(1 for s in bad.commit.sigs[:slot] if s[0] == 2)
    assert before > N_VAL * 2 // 3


@pytest.mark.parametrize("skip,kind", [
    ("signatures", "tampered"), ("power", "starved"),
    ("link", "broken_link"), ("witness", "witness_fork")])
def test_a_control_that_skips_a_check_trusts_the_fault(chain60, skip, kind):
    spec, chain = chain60
    control = rl.Sync(CHAIN_ID, chain[0], PERIOD_NS, 10**10, 1000, skip=skip)
    lie_p, lie_w, _at = drv.fault_plan(kind, spec, chain, 1, SESSION, 5, 21)
    out = control.session(lambda h: lie_p.get(h) or chain[h - 1], 19,
                          chain[-1].header.time_ns,
                          witness=lambda h: lie_w.get(h) or chain[h - 1])
    assert out.refused is None and out.trusted == list(range(2, 20))


def test_an_expired_root_refuses_the_session(chain60):
    _spec_, chain = chain60
    pair = _Pair(chain)
    late = chain[0].header.time_ns + PERIOD_NS
    with pytest.raises(verifier.ErrOldHeaderExpired):
        pair.client.verify_light_block_at_height(19, late)
    assert pair.sync.session(pair.honest, 19, late).refused == \
        (2, rl.EXPIRED)


# -- a run is sized in lanes and pinned to one warmed shape -----------------------

class _Set:
    def __init__(self, n):
        self.n = n

    def size(self):
        return self.n


@pytest.mark.parametrize("n_val,blocks,lanes", [
    (12, 512, 6144), (175, 35, 6144), (3072, 2, 6144), (3073, 1, 6144),
    (10000, 1, 10000)])
def test_run_shape_by_validator_set(monkeypatch, n_val, blocks, lanes):
    monkeypatch.setattr(common, "RUN_LANES", 6144)
    assert common.run_shape(_Set(n_val)) == (blocks, lanes)


def test_runs_are_sized_by_run_shape_and_a_short_tail_pads_alike(
        chain60, monkeypatch):
    """An 18-header session at 12 validators and 64 lanes: runs of 5, 5, 5
    and 3, every one handed the same ``min_lanes``."""
    _spec_, chain = chain60
    seen = []
    real = cv.verify_commits_light_batch

    def spy(entries, backend=None, min_lanes=0):
        seen.append((len(entries), min_lanes))
        return real(entries, backend=backend, min_lanes=min_lanes)
    monkeypatch.setattr(cv, "verify_commits_light_batch", spy)
    pair = _Pair(chain)
    pair.client.verify_light_block_at_height(19, pair.now)
    assert seen == [(5, 64), (5, 64), (5, 64), (3, 64)]
    assert not hasattr(Client, "_RUN_CHUNK")


@pytest.mark.parametrize("mode,backend,warmed", [
    (SEQUENTIAL, "tpu", [(64, False)]), (SEQUENTIAL, "cpu", []),
    (SEQUENTIAL, "sidecar", []), (SKIPPING, "tpu", [])])
def test_the_client_warms_its_run_shape_once(chain60, monkeypatch, mode,
                                             backend, warmed):
    """Once, when a sequential client is built on the device backend: after
    the trust root is fetched (its set sizes the shape) and before the first
    fetch of a run; the sessions after it warm nothing."""
    _spec_, chain = chain60
    events = []
    monkeypatch.setattr(
        crypto_batch, "_warm", lambda curve, sizes, tally:
        events.extend(("warm", n, tally) for n in sizes) or
        [(curve, n, tally, 0.0) for n in sizes])
    fetch = drv.Serving.light_block
    monkeypatch.setattr(drv.Serving, "light_block", lambda self, h:
                        events.append(("fetch", h)) or fetch(self, h))
    # the root's own check and the sessions stay on the CPU verifier
    real = crypto_batch._resolve_backend
    monkeypatch.setattr(crypto_batch, "new_batch_verifier",
                        lambda backend=None, min_lanes=0:
                        crypto_batch.CPUBatchVerifier(min_lanes))
    monkeypatch.setattr(crypto_batch, "_resolve_backend",
                        lambda b: backend if b == backend else real(b))
    pair = _Pair(chain, mode=mode, backend=backend)
    root = [("fetch", 1), ("fetch", 1)]     # the primary's, the witness's
    assert events == root + [("warm", n, t) for n, t in warmed]
    pair.client.verify_light_block_at_height(19, pair.now)
    pair.client.verify_light_block_at_height(37, pair.now)
    assert [e for e in events if e[0] == "warm"] == \
        [("warm", n, t) for n, t in warmed]


def test_blocksync_and_the_light_client_share_one_definition():
    for name in ("run_shape", "warm_run"):
        assert getattr(reactor, name) is getattr(light_client, name) \
            is getattr(common, name)
    src = open(light_client.__file__).read() + open(reactor.__file__).read()
    assert "def run_shape" not in src and "RUN_LANES =" not in src


# -- spans and counters ----------------------------------------------------------

def test_every_span_and_counter_moves_by_exact_counts(chain60):
    _spec_, chain = chain60
    pair = _Pair(chain)
    spans0 = dict(trace.span_totals())
    before = {
        "verified": _counter("light_blocks_verified"),
        "sessions": _counter("light_sessions"),
        "runs": _counter("light_run_blocks", "count"),
        "in_runs": _counter("light_run_blocks", "sum"),
        "primary": _counter("light_provider_calls", role="primary"),
        "witness": _counter("light_provider_calls", role="witness"),
    }
    pair.client.verify_light_block_at_height(19, pair.now)
    count = {k: v[0] - spans0.get(k, (0, 0.0))[0]
             for k, v in trace.span_totals().items()}
    assert _counter("light_blocks_verified") - before["verified"] == SESSION
    assert _counter("light_sessions") - before["sessions"] == 1
    assert _counter("light_run_blocks", "count") - before["runs"] == 4
    assert _counter("light_run_blocks", "sum") - before["in_runs"] == SESSION
    assert _counter("light_provider_calls", role="primary") \
        - before["primary"] == SESSION
    assert _counter("light_provider_calls", role="witness") \
        - before["witness"] == 1
    assert count["light.session"] == 1 and count["light.detect"] == 1
    assert count["light.fetch"] == SESSION + 1      # the witness's too
    assert count["light.store"] == SESSION
    for name in ("light.check", "light.verify_run", "commit_verify.collect",
                 "commit_verify.verify_commits_light_batch"):
        assert count[name] == 4, name


def test_a_refused_session_counts_as_a_session_and_verifies_nothing(chain60):
    spec, chain = chain60
    pair = _Pair(chain)
    lie_p, _w, _at = drv.fault_plan("tampered", spec, chain, 1, SESSION, 5, 21)
    pair.primary.lie = {h: lb.wire for h, lb in lie_p.items()}
    verified0 = _counter("light_blocks_verified")
    sessions0 = _counter("light_sessions")
    stores0 = trace.span_totals().get("light.store", (0, 0.0))[0]
    with pytest.raises(verifier.ErrVerificationFailed) as ei:
        pair.client.verify_light_block_at_height(19, pair.now)
    assert (ei.value.from_height, ei.value.to_height) == (8, 9)
    assert _counter("light_blocks_verified") == verified0
    assert _counter("light_sessions") - sessions0 == 1
    assert trace.span_totals().get("light.store", (0, 0.0))[0] == stores0


# -- the entry point ---------------------------------------------------------------

@pytest.mark.parametrize("flag,mode", [([], SKIPPING),
                                       (["--sequential"], SEQUENTIAL)])
def test_tmtpu_light_builds_the_client_the_driver_builds(
        chain60, monkeypatch, tmp_path, flag, mode):
    """``tmtpu light [--sequential]`` hands its flag to ``open_client``, the
    function the benchmark's driver calls: store on SQLite under the home,
    the mode asked for."""
    from tmtpu.cmd import __main__ as cli
    from tmtpu.light import proxy

    _spec_, chain = chain60
    pair = _Pair(chain)
    built = []
    real = light_client.open_client

    def spy(home, chain_id, opts, primary, witnesses, sequential=False):
        built.append(real(home, chain_id, opts, pair.primary,
                          [pair.witness], sequential=sequential))
        raise KeyboardInterrupt     # the daemon's loop is not the subject
    monkeypatch.setattr(light_client, "open_client", spy)
    monkeypatch.setattr(proxy, "LightProxy", None)
    monkeypatch.setenv("TMTPU_BASE_CRYPTO_BACKEND", "cpu")
    argv = ["--home", str(tmp_path), "light", CHAIN_ID, "--primary",
            "http://127.0.0.1:1", "--trusted-height", "1", "--trusted-hash",
            chain[0].header.hash.hex(), "--trusting-period",
            str(PERIOD_NS // 10**9)] + flag
    with pytest.raises(KeyboardInterrupt):
        cli.main(argv)
    client = built[0]
    assert client.mode == mode
    assert os.path.exists(tmp_path / "data" / "light.sqlite")
    assert client.pruning_size == light_client.DEFAULT_PRUNING_SIZE
    assert client.trust_level == light_client.DEFAULT_TRUST_LEVEL
    client.verify_light_block_at_height(19, pair.now)
    assert client.last_trusted_height() == 19


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "reference", "light.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [n.module or "" for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)]
    assert names and not [n for n in names if n.split(".")[0] == "tmtpu"]

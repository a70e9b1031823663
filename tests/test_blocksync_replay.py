"""The v0 blocksync loop against the plain reference of a late node's
replay (benchmarks/reference/blocks.py, which imports nothing of the
program): a chain fabricated from a seed is served by in-process peers
(the benchmark driver's, benchmarks/drivers/blocksync_replay.py) and the
reactor's own pool routine replays it. CPU backend, 12 validators, at
most 48 blocks: the cell ``valset175.replay`` at a toy size."""

import ast
import os
import time

import pytest

from benchmarks.drivers import blocksync_replay as drv
from benchmarks.reference import blocks as rb
from benchmarks.reference import commits as rc
from tmtpu.blocksync import common
from tmtpu.blocksync.common import BLOCKCHAIN_CHANNEL
from tmtpu.crypto import batch as crypto_batch
from tmtpu.libs import metrics, trace
from tmtpu.types import commit_verify as cv
from tmtpu.types.block import Block, BlockID

N_VAL, N_ABSENT, TXS, TX_BYTES = 12, 1, 3, 128
CFG = {"program": {"db_backend": "mem"}}


def _chain(seed, n_blocks, n_val=N_VAL):
    vals = rc.make_valset(seed, n_val, 1)
    p = rb.ChainParams("replay-test", 1_700_000_000 * 10**9)
    chain, tips = rb.make_chain(vals, p, seed, n_blocks, TXS, TX_BYTES,
                                N_ABSENT)
    return vals, p, chain, tips


class _Node:
    """The node of the cell, its peers and what it applied."""

    def __init__(self, vals, p, monkeypatch, backend="cpu"):
        monkeypatch.setattr(crypto_batch, "_default_backend", backend)
        self.reactor, self.parts = drv.build_node(CFG, "", vals, p)
        self.applied = []
        self.height = 0
        self.parts["event_bus"].subscribe("test", self._on_event)
        self.net = drv.Net(self.reactor, self)
        self.reactor.switch = self.net

    def _on_event(self, item):
        if item.type == "NewBlock":
            self.height = item.data["block"].header.height
            self.applied.append(self.height)
        return False

    def peer(self, name, blocks, announce=True):
        peer = drv.ServingPeer(name, self.reactor, BLOCKCHAIN_CHANNEL)
        peer.serve = {b.height: rb.block_response(b) for b in blocks}
        self.net.add(peer)
        if announce:
            peer.announce(min(peer.serve), max(peer.serve))
        return peer

    def wait(self, done, what, limit=30.0):
        t0 = time.monotonic()
        while not done():
            assert time.monotonic() - t0 < limit, \
                f"{what}: stuck at height {self.height}"
            time.sleep(0.01)

    def stop(self):
        self.reactor.on_stop()
        self.parts["proxy_app"].stop()


@pytest.fixture
def node_of(monkeypatch):
    made = []

    def make(vals, p, backend="cpu"):
        made.append(_Node(vals, p, monkeypatch, backend))
        return made[-1]
    yield make
    for n in made:
        n.stop()


def _counter(name, field=None):
    series = getattr(metrics, name).summary_series()
    return sum(v[field] if field else v for v in series.values())


def _valset_memo():
    return {f"{kind}.{what}": getattr(
                metrics, f"types_valset_memo_{kind}").summary_series().get(
                    f"what={what}", 0)
            for kind in ("hits", "misses") for what in ("hash", "encode")}


# -- the sound chain -----------------------------------------------------------

def test_replay_ends_where_the_reference_does(node_of):
    vals, p, chain, _tips = _chain(11, 48)
    ref = rb.Replay(vals, p)
    assert ref.run(chain).refused is None
    node = node_of(vals, p)
    node.peer("a", chain)
    node.reactor.on_start()
    node.wait(lambda: node.height == 47, "the replay")
    assert node.applied == list(range(1, 48)) == list(ref.block_ids)
    state = node.reactor.state
    assert state.last_block_height == ref.tip.height == 47
    assert state.app_hash == ref.tip.app_hash
    store = node.parts["block_store"]
    for h, bid in ref.block_ids.items():
        meta = store.load_block_meta(h)
        assert (meta.block_id.hash, meta.block_id.parts_total,
                meta.block_id.parts_hash) == bid
        assert store.load_seen_commit(h).to_proto().encode() == \
            rb.encode_commit(vals, chain[h].last_commit)
    from tmtpu.abci import types as abci

    query = node.parts["proxy_app"].query
    for key, value in list(ref.state.items())[::7]:
        assert bytes(query.query_sync(
            abci.RequestQuery(data=key)).value) == value


def test_every_span_and_counter_once_a_block(node_of):
    vals, p, chain, _tips = _chain(12, 41)
    node = node_of(vals, p)
    spans0 = dict(trace.span_totals())
    applied0 = _counter("blocksync_blocks_applied")
    runs0 = _counter("blocksync_run_blocks", "count")
    in_runs0 = _counter("blocksync_run_blocks", "sum")
    bad0 = _counter("blocksync_bad_blocks")
    memo0 = _valset_memo()
    paths0 = dict(metrics.state_validate_block.summary_series())
    node.peer("a", chain)
    node.reactor.on_start()
    node.wait(lambda: node.height == 40, "the replay")
    time.sleep(0.05)    # the counter follows the event by a few statements
    count = {k: v[0] - spans0.get(k, (0, 0.0))[0]
             for k, v in trace.span_totals().items()}
    runs = _counter("blocksync_run_blocks", "count") - runs0
    assert _counter("blocksync_blocks_applied") - applied0 == 40
    assert _counter("blocksync_run_blocks", "sum") - in_runs0 == 40
    assert _counter("blocksync_bad_blocks") - bad0 == 0
    for name in ("blocksync.apply", "blocksync.save_block", "state.exec_app",
                 "state.commit_app", "state.save_responses", "state.save"):
        assert count[name] == 40, name
    assert count["state.validate_block"] == 80     # the reactor's, apply's
    assert {k: v - paths0.get(k, 0) for k, v in
            metrics.state_validate_block.summary_series().items()} \
        == {"path=full": 40, "path=repeat": 40}
    assert count["blocksync.verify_run"] == runs
    assert count["commit_verify.verify_commits_light_batch"] == runs
    # the fused collect once a run; verify_commit of LastCommit once a
    # block from the second block on: apply's validate_block is a repeat
    assert count["commit_verify.collect"] == runs + 39
    assert count["blocksync.receive"] >= 41
    # the pool held every run whole: 512 blocks of 12 validators fit the
    # run's lanes, so the 41 blocks the peer served at once were one run
    assert runs == 1
    # the sets' kept bytes: a block, the full validate_block's two hashes
    # and three of save's four encodings (the new next_validators is the
    # fourth); a run, the reactor's hash; the chain's first sight of
    # validators and of next_validators are the only hashes computed
    memo = {k: v - memo0[k] for k, v in _valset_memo().items()}
    assert memo == {"hits.hash": 2 * 40 + runs - 2, "misses.hash": 2,
                    "hits.encode": 3 * 40, "misses.encode": 40}


# -- the faults ------------------------------------------------------------------

@pytest.mark.parametrize("kind,reason", [
    ("tampered", rb.BAD_SIGNATURE), ("starved", rb.LOW_POWER),
    ("wrong_id", rb.WRONG_BLOCK_ID)])
def test_fault_gives_the_references_outcome(node_of, kind, reason):
    vals, p, chain, tips = _chain(13, 30)
    node = node_of(vals, p)
    first = node.peer("a", chain[:20])          # heights 1..20
    node.reactor.on_start()
    node.wait(lambda: node.height == 19, "the sound part")
    ref = rb.Replay(vals, p)
    assert ref.run(chain[:20]).applied == node.applied

    served, refused = drv.fault_plan(kind, vals, p, chain, tips, 20, 13,
                                     TXS, TX_BYTES)
    run = [chain[19]] + [served[h] for h in sorted(served)]
    want = ref.run(run)
    assert want.refused == (refused, reason) and \
        want.applied == [20, 21, 22]
    before = len(node.applied)
    node.peer("liar", list(served.values()))
    node.wait(lambda: node.net.punished, "the verdict")
    assert node.applied[before:] == want.applied
    liar_id, why, applied_by_then = node.net.punished[0]
    assert (liar_id, applied_by_then + 1) == ("liar", refused)
    assert next(r for text, r in drv.REASONS if text in why) == reason
    assert "liar" not in node.net.peers and "a" in node.net.peers
    assert drv.fresh_lanes(run) == sum(
        1 for _b, nxt in zip(run, run[1:]) for s in nxt.last_commit.sigs
        if s[0] == rc.COMMIT) - (11 if kind == "wrong_id" else 0)

    # the good copy from the first peer is applied after
    first.serve.update({h: rb.block_response(chain[h - 1])
                        for h in (refused, refused + 1)})
    assert ref.run(chain[refused - 1:refused + 1]).applied == [refused]
    first.announce(1, refused + 1)
    node.wait(lambda: node.height == refused, "the good copy")
    assert node.reactor.state.app_hash == ref.tip.app_hash


@pytest.mark.parametrize("skip,kind", [
    ("signatures", "tampered"), ("power", "starved"),
    ("block_id", "wrong_id")])
def test_reference_with_a_check_removed_accepts_the_fault(skip, kind):
    """The controls: each lets its fault through, so a comparison with
    the program's outcome has to come out unequal."""
    vals, p, chain, tips = _chain(14, 12)
    served, refused = drv.fault_plan(kind, vals, p, chain, tips, 6, 14,
                                     TXS, TX_BYTES)
    run = chain[:6] + [served[h] for h in sorted(served)]
    want = rb.Replay(vals, p).run(run)
    got = rb.Replay(vals, p, skip=skip).run(run)
    assert want.refused[0] == refused and refused not in want.applied
    assert got != want and refused in got.applied


# -- the fused entry against the per-block one --------------------------------

@pytest.mark.parametrize("cache", ["cold", "warm"])
def test_fused_batch_equals_per_block_light_verify(cache):
    vals, p, chain, tips = _chain(15, 12)
    from tmtpu.crypto import ed25519 as prog_ed
    from tmtpu.types.validator import Validator, ValidatorSet

    pvals = ValidatorSet([Validator(prog_ed.PubKeyEd25519(pub), 1)
                          for pub in vals.pubs])
    firsts = list(chain[:10])
    seconds = list(chain[1:11])
    seconds[3] = rb.tampered_successor(vals, chain[4], 15)
    seconds[5] = rb.starved_successor(vals, chain[6], 15)
    firsts[7] = rb.another_block(vals, p, tips[7], 15, TXS, TX_BYTES)
    entries = []
    for first, second in zip(firsts, seconds):
        bid = BlockID(first.hash, first.parts_total, first.parts_hash)
        entries.append((pvals, p.chain_id, bid, first.height,
                        Block.decode(second.wire).last_commit))

    def one_by_one():
        out = []
        for e in entries:
            try:
                cv.verify_commit_light(*e, backend="cpu")
                out.append(None)
            except cv.VerificationError as err:
                out.append(type(err))
        return out

    if cache == "warm":
        assert cv.verify_commits_light_batch(entries, backend="cpu")
    fused = [None if r is None else type(r)
             for r in cv.verify_commits_light_batch(entries, backend="cpu",
                                                    min_lanes=4096)]
    assert fused == one_by_one()
    assert [r is None for r in fused] == [i not in (3, 5, 7)
                                          for i in range(10)]
    assert fused[5] is cv.ErrNotEnoughVotingPowerSigned


# -- the run's size and shape ----------------------------------------------------

class _Set:
    def __init__(self, n):
        self.n = n

    def size(self):
        return self.n


@pytest.mark.parametrize("n_val", [10, 175, 2000, 3072, 3073, 10000])
def test_run_fits_the_shape_it_warms(n_val):
    from tmtpu.tpu import dispatch

    blocks, lanes = common.run_shape(_Set(n_val))
    assert blocks >= 1 and blocks * n_val <= lanes
    shape = dispatch._pad_to_bucket(lanes)
    # whatever a run holds, one lane or every slot of every block, it pads
    # to the shape the warm-up's ``lanes`` copies compile
    for held in (1, n_val - n_val // 3, blocks * n_val):
        assert dispatch._pad_to_bucket(max(held, lanes)) == shape
    if n_val <= common.RUN_LANES:
        assert lanes == common.RUN_LANES and \
            (blocks + 1) * n_val > common.RUN_LANES


def test_a_one_block_run_pads_to_the_run_shape(node_of, monkeypatch):
    """The device backend, its compiled step replaced by one that answers
    at once (nothing of 6,144 lanes compiles in a test): the warm-up and
    every run, the one-block run first, meet the same padded width."""
    import dataclasses

    import jax.numpy as jnp

    from tmtpu.tpu import dispatch

    widths = []

    def step(packed, _table):
        widths.append(int(packed.shape[1]))
        return jnp.ones(packed.shape[1], dtype=bool)
    monkeypatch.setitem(dispatch.CURVES, "ed25519", dataclasses.replace(
        dispatch.CURVES["ed25519"], xla=step))
    monkeypatch.setattr(dispatch, "use_pallas_kernel", lambda: False)
    # one device, as the cell has: the tests' eight virtual ones would
    # route a 6,144-lane flush to the mesh
    monkeypatch.setenv("TMTPU_MESH_DEVICES", "1")
    vals, p, chain, _tips = _chain(16, 20)
    node = node_of(vals, p, backend="tpu")
    peer = node.peer("a", chain, announce=False)
    peer.base, peer.height = 1, 2       # what a status request is told
    node.reactor.on_start()
    node.wait(lambda: node.height == 1, "the one-block run")
    assert widths == [common.RUN_LANES] * 2     # the warm-up, then the run
    peer.announce(1, 20)
    node.wait(lambda: node.height == 19, "the rest")
    assert len(widths) >= 3 and set(widths) == {common.RUN_LANES}


@pytest.mark.parametrize("backend,warmed", [("cpu", []), ("sidecar", []),
                                            ("tpu", [(6144, False)])])
def test_warm_run_compiles_on_the_device_backend_only(monkeypatch, backend,
                                                      warmed):
    calls = []
    monkeypatch.setattr(
        crypto_batch, "_warm", lambda curve, sizes, tally:
        calls.extend((n, tally) for n in sizes) or
        [(curve, n, tally, 0.0) for n in sizes])
    vals, _p, _chain_, _tips = _chain(17, 1)
    from tmtpu.crypto import ed25519 as prog_ed
    from tmtpu.types.validator import Validator, ValidatorSet

    common.warm_run(ValidatorSet([Validator(prog_ed.PubKeyEd25519(pub), 1)
                                  for pub in vals.pubs]), backend)
    assert calls == warmed


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "reference", "blocks.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names] + [n.module or "" for n in ast.walk(tree)
                                  if isinstance(n, ast.ImportFrom)]
    assert names and not [n for n in names if n.split(".")[0] == "tmtpu"]

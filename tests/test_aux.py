"""Aux subsystem tests: pprof server, deadlock-detecting locks, trust
metric, SQL sink, mock peer, abci-cli, native hostprep."""

import sqlite3
import threading
import time
import urllib.request

import numpy as np
import pytest


def test_pprof_server_endpoints():
    from tmtpu.rpc.pprof import PprofServer

    srv = PprofServer("tcp://127.0.0.1:0")
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}/debug/pprof"
        stacks = urllib.request.urlopen(base + "/goroutine").read().decode()
        assert "thread" in stacks and "test_pprof_server_endpoints" in stacks
        heap = urllib.request.urlopen(base + "/heap").read().decode()
        assert "tracemalloc" in heap or "heap profile" in heap
        prof = urllib.request.urlopen(
            base + "/profile?seconds=0.3").read().decode()
        assert isinstance(prof, str)
        cmd = urllib.request.urlopen(base + "/cmdline").read().decode()
        assert "py" in cmd
    finally:
        srv.stop()


def test_deadlock_detection_reports():
    # the stall report goes through the structured logger (not raw
    # stderr), so capture by swapping the default logger's stream
    import io

    from tmtpu.libs import log
    from tmtpu.libs import sync as tmsync

    lock = tmsync._WatchedLock("test-lock")
    old_timeout = tmsync._timeout
    tmsync._timeout = 0.3
    buf = io.StringIO()
    old_logger = log._default
    log.configure(out=buf)
    try:
        holder_entered = threading.Event()
        release = threading.Event()

        def holder():
            with lock:
                holder_entered.set()
                release.wait(5)

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        holder_entered.wait(2)
        got = []

        def blocked():
            lock.acquire()
            got.append(True)
            lock.release()

        b = threading.Thread(target=blocked, daemon=True)
        b.start()
        time.sleep(0.8)  # > timeout: the report must have fired
        release.set()
        b.join(5)
        assert got == [True]
    finally:
        tmsync._timeout = old_timeout
        log._default = old_logger
    err = buf.getvalue()
    assert "POSSIBLE DEADLOCK" in err and "test-lock" in err


def test_mutex_factory_plain_by_default():
    from tmtpu.libs import sync as tmsync

    if not tmsync._enabled:
        m = tmsync.Mutex()
        assert type(m).__name__ in ("lock", "Lock") or hasattr(m, "acquire")


def test_trust_metric_decay_and_store():
    from tmtpu.libs.db import MemDB
    from tmtpu.p2p.trust import TrustMetric, TrustMetricStore

    t0 = 1000.0
    m = TrustMetric(now=t0)
    assert m.value(now=t0) == pytest.approx(1.0)
    for _ in range(10):
        m.bad_event(now=t0 + 1)
    v_bad = m.value(now=t0 + 15)
    assert v_bad < 0.6
    # a full good interval, once closed into history, recovers trust
    for _ in range(50):
        m.good_event(now=t0 + 31)
    assert m.value(now=t0 + 75) > v_bad

    db = MemDB()
    store = TrustMetricStore(db)
    store.get("peerA").bad_event()
    store.save()
    store2 = TrustMetricStore(db)
    assert store2.get("peerA") is not None


def test_sql_sink_indexes_blocks_txs():
    from tmtpu.state.sink_sql import SQLSink

    sink = SQLSink(sqlite3.connect(":memory:"), "test-chain")
    sink.index_block_events(1, 111, [("block_bonus", {"who": "val1"})])
    sink.index_tx_events(1, 111, 0, "AB" * 32, b"\x01\x02",
                         [("transfer", {"sender": "alice", "amount": "7"})])
    sink.index_tx_events(2, 222, 0, "CD" * 32, b"\x03",
                         [("transfer", {"sender": "bob", "amount": "9"})])
    assert sink.tx_count() == 2
    assert sink.find_tx_heights("transfer.sender", "alice") == [1]
    assert sink.find_tx_heights("transfer.sender", "bob") == [2]
    assert sink.find_tx_heights("block_bonus.who", "val1") == [1]


def test_mock_peer_reactor():
    from tmtpu.p2p.mock import MockPeer, MockReactor

    p = MockPeer()
    r = MockReactor([0x20, 0x21])
    r.add_peer(p)
    assert p.send(0x20, b"hello")
    assert p.sent_on(0x20) == [b"hello"]
    r.receive(0x21, p, b"payload")
    assert r.received[0][1] == 0x21
    p.stop()
    assert not p.send(0x20, b"nope")


def test_abci_cli_one_shots(tmp_path, capsys):
    from tmtpu.abci.cli import main, parse_value
    from tmtpu.abci.example.kvstore import KVStoreApplication
    from tmtpu.abci.server import SocketServer

    assert parse_value("0x6162") == b"ab"
    assert parse_value('"xy"') == b"xy"
    assert parse_value("plain") == b"plain"

    srv = SocketServer("tcp://127.0.0.1:0", KVStoreApplication())
    srv.start()
    addr = f"tcp://127.0.0.1:{srv.listen_port}"
    try:
        assert main(["--address", addr, "echo", "hi"]) == 0
        assert "hi" in capsys.readouterr().out
        assert main(["--address", addr, "deliver_tx", "k=v"]) == 0
        assert "OK" in capsys.readouterr().out
        assert main(["--address", addr, "commit"]) == 0
        assert "data.hex" in capsys.readouterr().out
        assert main(["--address", addr, "query", "k"]) == 0
        out = capsys.readouterr().out
        assert "OK" in out
        assert main(["--address", addr, "info"]) == 0
    finally:
        srv.stop()


_L = 2**252 + 27742317777372353535851937790883648493


def _native_prep_rows(pk, r, s, msgs, **kw):
    """native.prep_ed25519 read back lane-major: (pk, r, s, h rows [B, 32],
    s_ok, the SHA-512 that ran)."""
    from tmtpu import native

    B = pk.shape[0]
    plane = np.full((128, B + 3), 0xA5, dtype=np.uint8)
    sig = np.ascontiguousarray(np.concatenate([r, s], axis=1))
    s_ok, sha = native.prep_ed25519(pk, sig, msgs, plane, **kw)
    assert (plane[:, B:] == 0xA5).all()  # columns B.. are the caller's
    rows = [np.ascontiguousarray(plane[32 * k:32 * k + 32, :B].T)
            for k in range(4)]
    return rows, s_ok, sha


def test_native_hostprep_differential():
    import hashlib

    from tmtpu import native

    if native.load() is None:
        pytest.skip("no C toolchain")
    rng = np.random.default_rng(5)
    B = 300
    pk = rng.integers(0, 256, (B, 32), dtype=np.uint8)
    r = rng.integers(0, 256, (B, 32), dtype=np.uint8)
    s = rng.integers(0, 256, (B, 32), dtype=np.uint8)
    L = _L
    # adversarial s lanes: L-1, L, L+1, 2^256-1, 0
    for j, v in enumerate([L - 1, L, L + 1, 2**256 - 1, 0]):
        s[j] = np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
    msgs = [rng.integers(0, 256, int(n), dtype=np.uint8).tobytes()
            for n in rng.integers(0, 400, B)]
    msgs[0] = b""  # empty message edge
    (pk_o, r_o, s_o, h), sok, _sha = _native_prep_rows(pk, r, s, msgs)
    assert np.array_equal(pk_o, pk) and np.array_equal(r_o, r)
    for i in range(B):
        d = hashlib.sha512(r[i].tobytes() + pk[i].tobytes() + msgs[i])
        want = (int.from_bytes(d.digest(), "little") % L)
        assert h[i].tobytes() == want.to_bytes(32, "little"), i
        assert sok[i] == (int.from_bytes(s[i].tobytes(), "little") < L), i
        # a lane the host refuses carries s = 0 to the device
        assert s_o[i].tobytes() == (s[i].tobytes() if sok[i]
                                    else bytes(32)), i


@pytest.mark.parametrize("nthreads", [1, 3])
@pytest.mark.parametrize("sha", ["libcrypto", "portable"])
def test_native_hostprep_sha_block_edges(sha, nthreads):
    """R||A is 64 bytes, so these message lengths straddle SHA-512's
    padding edge (111/112 bytes in the last block) and its 128-byte
    blocks; every SHA-512 the build has gives hashlib's digest, in one
    thread and cut over several."""
    import hashlib

    from tmtpu import native

    if sha not in native.sha_impls():
        pytest.skip(f"no {sha} SHA-512 on this host")
    lens = [0, 1, 15, 16, 47, 48, 63, 64, 175, 176, 1000]
    rng = np.random.default_rng(33)
    # three lanes a length, and enough lanes that three threads get a tile
    lens = lens * 3 + [110] * 160
    B = len(lens)
    pk = rng.integers(0, 256, (B, 32), dtype=np.uint8)
    r = rng.integers(0, 256, (B, 32), dtype=np.uint8)
    s = rng.integers(0, 256, (B, 32), dtype=np.uint8)
    msgs = [rng.integers(0, 256, n, dtype=np.uint8).tobytes() for n in lens]
    (_pk, _r, _s, h), sok, ran = _native_prep_rows(
        pk, r, s, msgs, nthreads=nthreads, sha=sha)
    assert ran == sha
    for i in range(B):
        d = hashlib.sha512(r[i].tobytes() + pk[i].tobytes() + msgs[i])
        want = int.from_bytes(d.digest(), "little") % _L
        assert h[i].tobytes() == want.to_bytes(32, "little"), (i, lens[i])
        assert sok[i] == (int.from_bytes(s[i].tobytes(), "little") < _L), i


def test_native_hostprep_refuses_bad_buffers():
    """Sizes, dtype and contiguity are checked before a pointer goes to C."""
    from tmtpu import native

    if native.load() is None:
        pytest.skip("no C toolchain")
    pk = np.zeros((4, 32), dtype=np.uint8)
    sig = np.zeros((4, 64), dtype=np.uint8)
    msgs = [b"m"] * 4
    ok = np.zeros((128, 4), dtype=np.uint8)
    for bad_pk, bad_sig, bad_plane in (
        (pk[:, :31], sig, ok),
        (pk, sig[:3], ok),
        (pk, sig, np.zeros((128, 3), dtype=np.uint8)),
        (pk, sig, np.zeros((64, 4), dtype=np.uint8)),
        (pk, sig, np.zeros((128, 8), dtype=np.uint8)[:, ::2]),
        (pk.astype(np.int8), sig, ok),
    ):
        with pytest.raises(ValueError):
            native.prep_ed25519(bad_pk, bad_sig, msgs, bad_plane)
    with pytest.raises(ValueError):
        native.prep_ed25519(pk, sig, msgs, ok, sha="md5")
    # a lane whose len() is not its byte count would shift every offset
    with pytest.raises(ValueError):
        native.prep_ed25519(pk, sig, [memoryview(np.zeros(2, np.uint16))] * 4,
                            ok)


def test_step_transitions_observe_durations():
    """RoundState.step transitions feed the per-step duration
    histograms (consensus/metrics.go StepDurationSeconds analogue) —
    every assignment site gets the breakdown for free."""
    from tmtpu.consensus.types import (
        STEP_COMMIT, STEP_NEW_ROUND, STEP_PROPOSE, RoundState,
    )
    from tmtpu.libs import metrics

    def counts():
        return {name: metrics.consensus_step_duration.totals(step=name)[0]
                for name in ("NewHeight", "NewRound", "Propose", "Commit")}

    before = counts()
    rs = RoundState()
    rs.step = STEP_NEW_ROUND   # leaves NewHeight
    rs.step = STEP_PROPOSE     # leaves NewRound
    rs.step = STEP_PROPOSE     # no transition: no observation
    rs.step = STEP_COMMIT      # leaves Propose
    after = counts()
    assert after["NewHeight"] == before["NewHeight"] + 1
    assert after["NewRound"] == before["NewRound"] + 1
    assert after["Propose"] == before["Propose"] + 1
    assert after["Commit"] == before["Commit"]
    assert rs.step == STEP_COMMIT and rs.step_name() == "Commit"


def test_replay_speed_steps_do_not_pollute_histograms():
    from tmtpu.consensus.types import (
        STEP_COMMIT, STEP_PROPOSE, RoundState,
    )
    from tmtpu.libs import metrics

    rs = RoundState()
    before = metrics.consensus_step_duration.totals(step="NewHeight")[0]
    rs.metrics_paused = True  # what catchup_replay sets
    rs.step = STEP_PROPOSE
    rs.step = STEP_COMMIT
    assert metrics.consensus_step_duration.totals(
        step="NewHeight")[0] == before
    rs.metrics_paused = False
    rs.step = STEP_PROPOSE  # leaves Commit, live again
    assert metrics.consensus_step_duration.totals(step="Commit")[0] >= 1


@pytest.mark.slow
def test_node_with_psql_indexer_records_txs(tmp_path):
    """tx_index.indexer="psql" wires the SQL event sink into the node
    (node.go EventSinksFromConfig): a committed tx lands in the
    relational tables, and tx_search reports the sink unqueryable the
    way the reference's psql sink does."""
    import time as _time

    from tmtpu.config.config import Config
    from tmtpu.node.node import Node
    from tmtpu.privval.file_pv import FilePV
    from tmtpu.state.sink_sql import SQLTxIndexer
    from tmtpu.types.genesis import GenesisDoc, GenesisValidator

    home = tmp_path / "h"
    (home / "config").mkdir(parents=True)
    (home / "data").mkdir(parents=True)
    cfg = Config.test_config()
    cfg.base.home = str(home)
    cfg.base.crypto_backend = "cpu"
    cfg.rpc.laddr = ""
    cfg.tx_index.indexer = "psql"
    pv = FilePV.load_or_generate(
        cfg.rooted(cfg.base.priv_validator_key_file),
        cfg.rooted(cfg.base.priv_validator_state_file))
    gen = GenesisDoc(chain_id="psql-chain", genesis_time=_time.time_ns(),
                     validators=[GenesisValidator(pv.get_pub_key(), 10)])
    gen.save_as(cfg.genesis_path)
    n = Node(cfg)
    assert isinstance(n.tx_indexer, SQLTxIndexer)
    n.start()
    try:
        assert n.consensus.wait_for_height(1, timeout=60)
        n.mempool.check_tx(b"sink-key=sink-val")
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline and \
                n.tx_indexer.sink.tx_count() < 1:
            _time.sleep(0.2)
        assert n.tx_indexer.sink.tx_count() >= 1
        with pytest.raises(RuntimeError, match="not supported"):
            n.tx_indexer.search("tx.height=1")
        with pytest.raises(RuntimeError, match="not supported"):
            n.tx_indexer.get(b"\x00" * 32)
        # reindex over the same sink must not trip the blocks UNIQUE
        from tmtpu.state.txindex import reindex_events

        reindex_events(n.block_store, n.state_store, n.tx_indexer,
                       block_indexer=n.block_indexer)
    finally:
        n.stop()

"""Verify-once A/B on the 4-node localnet (ISSUE 4 acceptance): the same
real-TCP kvstore network as tools/localnet_bench.py, run twice — sigcache
OFF (pre-ISSUE behavior) then ON — counting every verify flush that
reaches the backend and every lane it carries.

What the cache should do here: each node verifies a vote's signature once
at ingestion (vote_set), then verify_commit re-proves the same 3-4
signatures at EnterPrecommit/ApplyBlock and blocksync-style replays. With
the cache ON those re-proofs resolve as hits and never reach
``_verify_pending`` — the dispatched-lane count collapses while block
rate holds.

Prints one JSON line per arm plus a combined summary:

    {"metric": "localnet_verify_ab", "off": {...}, "on": {...},
     "dispatch_reduction_pct": ..., "on_hit_rate": ...}

Run: python tools/localnet_ab.py [window_seconds]
"""

import json
import pathlib
import sys
import tempfile
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import tests.conftest  # noqa: F401  (forces jax onto CPU devices)

from tmtpu.config.config import Config  # noqa: E402
from tmtpu.crypto import batch as crypto_batch  # noqa: E402
from tmtpu.crypto import sigcache  # noqa: E402
from tmtpu.node.node import Node  # noqa: E402
from tmtpu.types.genesis import GenesisDoc, GenesisValidator  # noqa: E402
from tmtpu.privval.file_pv import FilePV  # noqa: E402


def _mk_net_nodes(n, tmp, power=10, cache_on=True):
    """Same 4-node full-mesh TCP net as tests/test_p2p.py::_mk_net_nodes,
    inlined so this tool imports on boxes where tests/test_p2p.py cannot
    (its module-level SecretConnection import needs `cryptography`; the
    node stack itself runs on the plaintext dev fallback)."""
    pvs = []
    for i in range(n):
        home = tmp / f"node{i}"
        (home / "config").mkdir(parents=True)
        (home / "data").mkdir(parents=True)
        cfg = Config.test_config()
        cfg.base.home = str(home)
        cfg.base.crypto_backend = "cpu"
        # the production knob, not a monkeypatch: Node construction calls
        # crypto_batch.configure(cfg.crypto), which would silently re-enable
        # the cache if we only flipped sigcache.DEFAULT beforehand
        cfg.crypto.sigcache_enable = cache_on
        cfg.rpc.laddr = ""
        pv = FilePV.load_or_generate(
            cfg.rooted(cfg.base.priv_validator_key_file),
            cfg.rooted(cfg.base.priv_validator_state_file))
        pvs.append((cfg, pv))
    gen = GenesisDoc(
        chain_id="ab-chain", genesis_time=time.time_ns(),
        validators=[GenesisValidator(pv.get_pub_key(), power)
                    for _, pv in pvs],
    )
    nodes = []
    for cfg, pv in pvs:
        gen.save_as(cfg.genesis_path)
        nodes.append(Node(cfg))
    addrs = [f"{nd.node_id}@127.0.0.1:{nd.p2p_port}" for nd in nodes]
    for i, nd in enumerate(nodes):
        nd.switch.set_persistent_peers([a for j, a in enumerate(addrs)
                                        if j != i])
    return nodes


def _run_arm(cache_on: bool, duration_s: float) -> dict:
    """One localnet window with the cache pinned on/off; returns the
    verify-flush counters alongside the block/tx rates."""
    flushes = [0]
    lanes = [0]
    real = crypto_batch.CPUBatchVerifier._verify_pending

    def counting(self, items, tally):
        flushes[0] += 1
        lanes[0] += len(items)
        return real(self, items, tally)

    crypto_batch.CPUBatchVerifier._verify_pending = counting
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="localnet-ab-"))
    nodes = _mk_net_nodes(4, tmp, cache_on=cache_on)
    assert sigcache.DEFAULT.enabled() == cache_on, \
        "node configure() did not pin the cache state for this arm"
    sigcache.DEFAULT.invalidate_all()
    try:
        for nd in nodes:
            nd.start()
        while any(nd.switch.num_peers() < 3 for nd in nodes):
            time.sleep(0.1)
        for nd in nodes:
            assert nd.consensus.wait_for_height(2, timeout=60)

        stop = threading.Event()

        def load():
            i = 0
            while not stop.is_set():
                try:
                    nodes[i % 4].mempool.check_tx(b"ab-%d=%d" % (i, i))
                except Exception:
                    pass
                i += 1
                time.sleep(0.002)

        t = threading.Thread(target=load, daemon=True)
        t.start()

        # counters reset AFTER warmup so both arms measure the same
        # steady-state window, not node boot + first-height noise
        flushes[0] = 0
        lanes[0] = 0
        st0 = sigcache.stats()
        h0 = nodes[0].block_store.height()
        t0 = time.monotonic()
        time.sleep(duration_s)
        stop.set()
        h1 = nodes[0].block_store.height()
        wall = time.monotonic() - t0
    finally:
        crypto_batch.CPUBatchVerifier._verify_pending = real
        for nd in nodes:
            nd.stop()

    st1 = sigcache.stats()
    hits = st1["hits"] - st0["hits"]
    misses = st1["misses"] - st0["misses"]
    out = {
        "cache": "on" if cache_on else "off",
        "window_s": round(wall, 2),
        "blocks": h1 - h0,
        "block_rate_per_min": round((h1 - h0) / wall * 60, 1),
        "verify_flushes": flushes[0],
        "verify_lanes_dispatched": lanes[0],
        "lanes_per_block": round(lanes[0] / max(1, h1 - h0), 1),
        "cache_hits": hits,
        "cache_misses": misses,
        "hit_rate": round(hits / max(1, hits + misses), 4),
    }
    print(json.dumps(out), file=sys.stderr)
    return out


def main(duration_s: float = 20.0):
    off = _run_arm(False, duration_s)
    on = _run_arm(True, duration_s)
    sigcache.DEFAULT.set_enabled(True)
    sigcache.DEFAULT.invalidate_all()
    reduction = 1.0 - (on["lanes_per_block"] /
                       max(1e-9, off["lanes_per_block"]))
    result = {
        "metric": "localnet_verify_ab",
        "off": off,
        "on": on,
        "dispatch_reduction_pct": round(reduction * 100, 1),
        "on_hit_rate": on["hit_rate"],
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main(float(sys.argv[1]) if len(sys.argv) > 1 else 20.0)

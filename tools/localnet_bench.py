"""4-node localnet benchmark (reference analogue: test/e2e/runner/benchmark.go
+ test/loadtime): real TCP, kvstore app, light tx load; reports block rate,
tx throughput and consensus round latency over a measurement window.

Run: python tools/localnet_bench.py [seconds]
"""

import json
import pathlib
import sys
import tempfile
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import tests.conftest  # noqa: F401  (forces jax onto CPU devices)

from tests.test_p2p import _mk_net_nodes  # noqa: E402


def main(duration_s: float = 20.0):
    return _run(duration_s)


def _run(duration_s: float):
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="localnet-bench-"))
    nodes = _mk_net_nodes(4, tmp)
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
                    nodes[i % 4].mempool.check_tx(
                        b"bench-%d=%d" % (i, i))
                except Exception:
                    pass
                i += 1
                time.sleep(0.002)  # ~500 tx/s offered

        t = threading.Thread(target=load, daemon=True)
        t.start()

        h0 = nodes[0].block_store.height()
        t0 = time.monotonic()
        time.sleep(duration_s)
        h1 = nodes[0].block_store.height()
        t1 = time.monotonic()
        stop.set()

        n_txs = 0
        intervals = []
        prev_time = None
        for h in range(h0 + 1, h1 + 1):
            blk = nodes[0].block_store.load_block(h)
            if blk is None:
                continue
            n_txs += len(blk.txs)
            if prev_time is not None:
                intervals.append((blk.header.time - prev_time) / 1e9)
            prev_time = blk.header.time

        wall = t1 - t0
        blocks = h1 - h0
        result = {
            "nodes": 4,
            "window_s": round(wall, 2),
            "blocks": blocks,
            "block_rate_per_min": round(blocks / wall * 60, 1),
            "txs_committed": n_txs,
            "tx_rate_per_min": round(n_txs / wall * 60, 1),
            "avg_block_interval_s": round(sum(intervals) / len(intervals), 4)
            if intervals else None,
        }
        print(json.dumps(result))
        return result
    finally:
        for nd in nodes:
            nd.stop()


if __name__ == "__main__":
    main(float(sys.argv[1]) if len(sys.argv) > 1 else 20.0)

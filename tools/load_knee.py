"""Sustained-load knee finder: sweep offered tx rates over a 4-node
subprocess testnet and report committed throughput + per-tx latency
percentiles at each point (reference: test/loadtime/report — the QA
knee-hunting procedure in docs/qa).

One testnet per rate point (fresh state, no backlog carryover); each
point offers load for --duration seconds after the net reaches height 3,
then reads the latency report from runner.benchmark(). The knee is the
highest offered rate whose committed rate keeps up (>= 90% of offered)
with bounded p95 latency.

Usage: python tools/load_knee.py [--rates 150,250,350] [--duration 20]
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tmtpu.e2e import Manifest, NodeSpec, Runner  # noqa: E402


def measure_point(rate: float, duration: float, size: int) -> dict:
    m = Manifest(
        chain_id=f"knee-{int(rate)}",
        target_height=3,
        timeout_s=90.0,
        nodes=[NodeSpec(name=f"v{i}") for i in range(4)],
    )
    m.load.rate = rate
    m.load.size = size
    out = tempfile.mkdtemp(prefix=f"tmtpu-knee-{int(rate)}-")
    r = Runner(m, out)
    try:
        r.setup()
        r.start()
        r.wait_for(3)
        h0 = r.nodes[0].height()
        r.start_load()
        time.sleep(duration)
        r.stop_load()
        # drain: let in-flight txs commit before reading the report
        time.sleep(3.0)
        stats = r.benchmark()
        h1 = r.nodes[0].height()
        offered = len(r.txs_sent)
        return {
            "offered_tx_s": round(offered / duration, 1),
            "committed_tx_s": round(
                stats.get("txs_committed", 0) / duration, 1),
            "committed_pct": round(
                100.0 * stats.get("txs_committed", 0) / max(1, offered), 1),
            "blocks": h1 - h0,
            "latency_p50_s": stats.get("latency_p50_s"),
            "latency_p95_s": stats.get("latency_p95_s"),
            "latency_max_s": stats.get("latency_max_s"),
        }
    finally:
        r.stop()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rates", default="150,250,350")
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--size", type=int, default=160)
    args = ap.parse_args()
    results = []
    for rate in (float(x) for x in args.rates.split(",")):
        point = measure_point(rate, args.duration, args.size)
        results.append(point)
        print(json.dumps(point), flush=True)
    knee = max(
        (p for p in results if p["committed_pct"] >= 90.0),
        key=lambda p: p["committed_tx_s"],
        default=None,
    )
    print(json.dumps({"knee": knee}))


if __name__ == "__main__":
    main()

"""Lightserve flood: >=10k concurrent light-client sessions, one daemon.

The serving-tier acceptance harness. Boots the shared 4-node localnet
(tools/ab_common.py), keeps the chain growing under open-loop tx load,
stands up an in-process :class:`LightserveServer` against node0's RPC
(the one-round-trip ``light_block`` method), warms a set of target
heights, then floods: ``--clients`` multiplexed connections each
holding ``--window`` pipelined sessions in flight — 16 x 640 = ~10k
concurrent sessions by default, far past what per-session verification
could survive on one host.

Reported (post-warmup window only):

- ``p50_ms`` / ``p99_ms`` — submit-to-answer session latency (this is
  open-loop overload: with ~10k sessions held in flight on purpose,
  latency is dominated by the pipeline queue the flood itself builds);
- ``dispatch_avoided_rate`` — fraction of sessions answered with ZERO
  verify dispatches (the "verify once, serve millions" figure; the
  acceptance bar is > 0.99);
- ``max_inflight`` — peak concurrent sessions actually held open.

Usage: python tools/lightserve_flood.py [--clients 16] [--window 640]
       [--duration 12] [--warmup 4] [--targets 8] [--load-interval 0.01]

Single JSON object on stdout (ABReport schema, one ``flood`` arm);
per-phase progress on stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
from collections import deque
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.ab_common import ABReport, boot, make_localnet, open_loop_load

CHAIN_ID = "lsflood"
WEEK_NS = 7 * 24 * 3600 * 1_000_000_000


def _pct(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=16,
                    help="multiplexed daemon connections")
    ap.add_argument("--window", type=int, default=640,
                    help="pipelined in-flight sessions per connection")
    ap.add_argument("--duration", type=float, default=12.0,
                    help="measured flood window, seconds (post-warmup)")
    ap.add_argument("--warmup", type=float, default=4.0,
                    help="flood seconds excluded from the report")
    ap.add_argument("--targets", type=int, default=8,
                    help="distinct target heights the flood rotates over")
    ap.add_argument("--load-interval", type=float, default=0.01,
                    help="tx load interval keeping the chain growing")
    args = ap.parse_args()

    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.light.client import TrustOptions
    from tmtpu.light.provider import HTTPProvider
    from tmtpu.lightserve.client import LightserveClient
    from tmtpu.lightserve.server import LightserveServer

    crypto_batch.set_default_backend("cpu")
    report = ABReport("lightserve_flood")

    with tempfile.TemporaryDirectory(prefix="lsflood-") as td:
        tmp = Path(td)

        def configure(cfg, i):
            if i == 0:
                cfg.rpc.laddr = "tcp://127.0.0.1:0"

        print("lightserve_flood: booting 4-node localnet...",
              file=sys.stderr)
        nodes = make_localnet(4, tmp, CHAIN_ID, configure=configure)
        try:
            boot(nodes, height=2, timeout_s=120.0)
            stop_load = open_loop_load(nodes, prefix=b"lsf",
                                       interval_s=args.load_interval)
            rpc = f"http://127.0.0.1:{nodes[0].rpc_server.port}"

            # grow past the flood targets before anchoring
            want = args.targets + 3
            assert nodes[0].consensus.wait_for_height(want, timeout=120.0)
            anchor_hash = \
                nodes[0].block_store.load_block_meta(1).header.hash()

            srv = LightserveServer(
                "tcp://127.0.0.1:0",
                HTTPProvider(CHAIN_ID, rpc, timeout=30.0),
                TrustOptions(WEEK_NS, 1, anchor_hash),
                CHAIN_ID,
                max_queue_sessions=args.clients * args.window + 1024)
            srv.start()
            try:
                tip = nodes[0].block_store.height() - 1
                targets = list(range(tip - args.targets + 1, tip + 1))
                warm = LightserveClient(srv.addr, chain_id=CHAIN_ID,
                                        client_id="warmer")
                t0 = time.perf_counter()
                for h in targets:
                    warm.sync(1, anchor_hash, h, deadline_s=60.0)
                warm.close()
                print(f"lightserve_flood: warmed {len(targets)} targets "
                      f"({targets[0]}..{targets[-1]}) in "
                      f"{time.perf_counter() - t0:.2f}s; flooding "
                      f"{args.clients} conns x {args.window} in-flight",
                      file=sys.stderr)

                flood_stop = threading.Event()
                record_from = [float("inf")]   # set once warmup elapses
                lock = threading.Lock()
                lat, avoided, served = [], [0], [0]
                inflight, max_inflight = [0], [0]
                errors = [0]

                def session_loop(ci):
                    cli = LightserveClient(srv.addr, chain_id=CHAIN_ID,
                                           client_id=f"flood-{ci}")
                    pending = deque()
                    i = ci
                    try:
                        while not flood_stop.is_set():
                            while len(pending) < args.window and \
                                    not flood_stop.is_set():
                                h = targets[i % len(targets)]
                                i += 1
                                pending.append(
                                    cli.sync_submit(1, anchor_hash, h))
                                with lock:
                                    inflight[0] += 1
                                    if inflight[0] > max_inflight[0]:
                                        max_inflight[0] = inflight[0]
                            handle = pending.popleft()
                            try:
                                r = handle.result(deadline_s=60.0)
                                done = time.perf_counter()
                                with lock:
                                    inflight[0] -= 1
                                    if done >= record_from[0]:
                                        served[0] += 1
                                        lat.append(done -
                                                   handle.submitted_at)
                                        if r.dispatches == 0:
                                            avoided[0] += 1
                            except Exception:
                                with lock:
                                    inflight[0] -= 1
                                    errors[0] += 1
                        for handle in pending:   # drain, uncounted
                            try:
                                handle.result(deadline_s=60.0)
                            except Exception:
                                pass
                            with lock:
                                inflight[0] -= 1
                    finally:
                        cli.close()

                threads = [threading.Thread(target=session_loop,
                                            args=(ci,), daemon=True)
                           for ci in range(args.clients)]
                for t in threads:
                    t.start()
                time.sleep(args.warmup)
                with lock:
                    record_from[0] = time.perf_counter()
                time.sleep(args.duration)
                flood_stop.set()
                for t in threads:
                    t.join(timeout=120.0)

                lat.sort()
                snap = srv.snapshot()
                rate = (avoided[0] / served[0]) if served[0] else 0.0
                report.add_arm({
                    "arm": "flood",
                    "sessions": served[0],
                    "sessions_s": round(served[0] / args.duration, 1),
                    "p50_ms": round(_pct(lat, 0.50) * 1e3, 2),
                    "p99_ms": round(_pct(lat, 0.99) * 1e3, 2),
                    "dispatch_avoided_rate": round(rate, 5),
                    "max_inflight": max_inflight[0],
                    "errors": errors[0],
                    "clients": args.clients,
                    "window": args.window,
                    "targets": len(targets),
                    "cache": snap["cache"],
                    "provider_calls": snap["provider_calls"],
                })
                report.finish(
                    ok=bool(served[0] and rate > 0.99 and
                            max_inflight[0] >= 10_000 and not errors[0]),
                )
            finally:
                srv.stop()
            stop_load.set()
        finally:
            for nd in nodes:
                try:
                    nd.stop()
                except Exception:
                    pass


if __name__ == "__main__":
    main()

"""Throughput-tier A/B on the 4-node localnet (ISSUE 10 acceptance): the
same real-TCP kvstore network as tools/localnet_ab.py, run twice over an
identical signed-tx workload —

  serial arm    pre-PR tx path: per-tx CheckTx round trips with a
                one-lane signature verify each (batch_check off), no
                gossip dedup (seen cache 0), serial ApplyBlock;
  pipelined arm this PR's path: gather-window batched CheckTx (one
                native signature flush + one pipelined ABCI burst per
                gather), per-peer dedup gossip, async ApplyBlock overlap.

Both arms run closed-loop at a fixed offered load: N pre-signed txs are
offered round-robin to every node's ``check_tx_nowait`` surface, and the
arm is timed until the kvstore has applied all N — so committed tx/s is
measured at a 100% commit rate by construction, and any arm that cannot
reach 100% fails loudly instead of flattering itself. Double-sign safety
rides along: every committed block on every node is scanned for
evidence, which must stay empty.

Latency rides along too (ISSUE 15): each offered tx is stamped "submit"
in the tx-lifecycle ring (the in-process offer bypasses RPC, which would
normally stamp it), so every arm also reports the submit→commit p50/p99
from the ``tendermint_tx_latency_submit_to_commit`` histogram delta —
latency vs load on the same run that measures throughput.

Prints one JSON line per arm plus a combined summary
(tools/ab_common.py schema):

    {"metric": "localnet_load_ab", "serial": {...}, "pipelined": {...},
     "speedup": ..., "txs": N}

Run: python tools/localnet_load_ab.py [num_txs]

Sweep mode (the committed-vs-offered knee curve for PERF.md): ONE
pipelined-arm net, a fixed-rate OPEN-loop offer window per rate — txs
are paced at the offered rate whether or not the net keeps up, a full
mempool drops the offer — so each row reports how much of the offered
load actually committed and at what latency. The knee is the first rate
where commit_rate falls off and p99 inflates:

    python tools/localnet_load_ab.py --sweep 50,100,200,400 [window_s]

    {"metric": "localnet_load_sweep", "rows": [{"offered_rate": ...,
     "committed_tx_per_s": ..., "commit_rate": ...,
     "submit_to_commit_p99_ms": ...}, ...]}
"""

import json
import pathlib
import sys
import tempfile
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import tests.conftest  # noqa: F401  (forces jax onto CPU devices)

from tmtpu.crypto import sigcache  # noqa: E402
from tmtpu.crypto.ed25519 import gen_priv_key  # noqa: E402
from tmtpu.libs import metrics as _m  # noqa: E402
from tmtpu.libs import txlat  # noqa: E402
from tmtpu.mempool import signed_tx  # noqa: E402
from tools import ab_common  # noqa: E402


def _mk_net_nodes(tmp, pipelined: bool):
    """The shared 4-node net with the throughput-tier knobs set per arm
    through the production config (ab_common.make_localnet configure
    hook) — never by monkeypatching the mempool after the fact."""

    def configure(cfg, _i):
        cfg.mempool.batch_check = pipelined
        cfg.mempool.gossip_seen_cache = 4096 if pipelined else 0
        cfg.consensus.async_exec = pipelined

    return ab_common.make_localnet(4, tmp, "load-ab-chain",
                                   configure=configure)


def _app_size(node) -> int:
    from tmtpu.abci import types as abci

    res = node.proxy_app.query.info_sync(abci.RequestInfo(version=""))
    return int(json.loads(res.data)["size"])


def _evidence_count(node) -> int:
    total = 0
    for h in range(1, node.block_store.height() + 1):
        blk = node.block_store.load_block(h)
        if blk is not None:
            total += len(blk.evidence)
    return total


def _lat_delta(before):
    """submit→commit p50/p99 (ms) over the histogram delta since
    ``before`` — all four nodes share this process's registry, so the
    delta is the whole arm's distribution."""
    after = _m.tx_latency_submit_to_commit.bucket_counts()
    if not after:
        return {"lat_txs": 0}
    base = before if before else (0,) * len(after)
    delta = [a - b for a, b in zip(after, base)]
    bounds = _m.tx_latency_submit_to_commit.buckets
    return {
        "lat_txs": delta[-1],
        "submit_to_commit_p50_ms": round(
            _m.percentile_from_buckets(bounds, delta, 0.50) * 1000, 1),
        "submit_to_commit_p99_ms": round(
            _m.percentile_from_buckets(bounds, delta, 0.99) * 1000, 1),
    }


def _run_arm(pipelined: bool, txs: list, drain_timeout_s: float) -> dict:
    arm = "pipelined" if pipelined else "serial"
    sigcache.DEFAULT.invalidate_all()
    txlat.clear()  # fresh journey ring per arm
    tmp = pathlib.Path(tempfile.mkdtemp(prefix=f"load-ab-{arm}-"))
    nodes = _mk_net_nodes(tmp, pipelined=pipelined)
    n_txs = len(txs)
    try:
        ab_common.boot(nodes, height=2, timeout_s=60)

        flushes0 = ab_common.counter_value(_m.mempool_batch_flushes)
        dedup0 = ab_common.counter_value(_m.mempool_gossip_dedup_skips)
        lat0 = _m.tx_latency_submit_to_commit.bucket_counts()
        t0 = time.monotonic()

        def offer(shard_txs, node):
            # fixed offered load: every tx in the shard is offered once;
            # nowait = the RPC/recv-thread admission surface. The offer
            # bypasses RPC, so stamp "submit" explicitly (first-stamp-
            # wins makes the re-offer retries harmless).
            for tx in shard_txs:
                txlat.stamp_tx(tx, "submit")
                while True:
                    try:
                        node.mempool.check_tx_nowait(tx)
                        break
                    except Exception:
                        time.sleep(0.01)  # mempool full: back off, re-offer

        threads = [threading.Thread(target=offer, args=(txs[i::4], nd),
                                    daemon=True)
                   for i, nd in enumerate(nodes)]
        for t in threads:
            t.start()

        deadline = time.monotonic() + drain_timeout_s
        committed = 0
        while committed < n_txs and time.monotonic() < deadline:
            committed = _app_size(nodes[0])
            time.sleep(0.05)
        elapsed = time.monotonic() - t0
        committed = _app_size(nodes[0])
        for t in threads:
            t.join(timeout=10)

        evidence = sum(_evidence_count(nd) for nd in nodes)
        heights = [nd.block_store.height() for nd in nodes]
        latency = _lat_delta(lat0)
    finally:
        for nd in nodes:
            nd.stop()

    out = {
        "arm": arm,
        "offered_txs": n_txs,
        "committed_txs": committed,
        "commit_rate": round(committed / n_txs, 4),
        "window_s": round(elapsed, 2),
        "committed_tx_per_s": round(committed / elapsed, 1),
        "blocks": max(heights),
        "batch_flushes": int(
            ab_common.counter_value(_m.mempool_batch_flushes) - flushes0),
        "gossip_dedup_skips": int(
            ab_common.counter_value(_m.mempool_gossip_dedup_skips)
            - dedup0),
        "double_sign_evidence": evidence,
    }
    out.update(latency)
    return out


def _paced_offer(nodes, txs, rate: float, window_s: float) -> int:
    """Open-loop offer: pace ``txs`` at ``rate`` tx/s round-robin for
    ``window_s``, never waiting on commit progress. A full mempool drops
    the offer (that IS the over-the-knee signal, surfaced as
    commit_rate < 1), unlike the closed-loop arms' re-offer retry."""
    interval = 1.0 / max(1e-9, rate)
    t0 = time.monotonic()
    offered = 0
    n = len(nodes)
    for i, tx in enumerate(txs):
        target = t0 + i * interval
        now = time.monotonic()
        if now - t0 >= window_s:
            break
        if now < target:
            time.sleep(target - now)
        txlat.stamp_tx(tx, "submit")
        try:
            nodes[i % n].mempool.check_tx_nowait(tx)
        except Exception:
            pass
        offered += 1
    return offered


def sweep(rates, window_s: float = 12.0, settle_s: float = 4.0):
    """One pipelined net, one open-loop window per offered rate; emits
    one knee-curve row per rate (stderr as they land, combined JSON on
    stdout)."""
    priv = gen_priv_key()
    budget = [int(r * window_s) + 8 for r in rates]
    n_total = sum(budget)
    print(f"pre-signing {n_total} txs for {len(rates)}-rate sweep...",
          file=sys.stderr)
    txs = [signed_tx.encode(b"sw-%d=%d" % (i, i), priv)
           for i in range(n_total)]
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="load-sweep-"))
    nodes = _mk_net_nodes(tmp, pipelined=True)
    rows = []
    try:
        ab_common.boot(nodes, height=2, timeout_s=60)
        idx = 0
        for r, n_arm in zip(rates, budget):
            txlat.clear()
            lat0 = _m.tx_latency_submit_to_commit.bucket_counts()
            size0 = _app_size(nodes[0])
            shard = txs[idx:idx + n_arm]
            idx += n_arm
            offered = _paced_offer(nodes, shard, r, window_s)
            time.sleep(settle_s)  # let the tail commit (or not)
            committed = _app_size(nodes[0]) - size0
            row = {
                "offered_rate": r,
                "offered_txs": offered,
                "committed_txs": committed,
                "committed_tx_per_s": round(committed / window_s, 1),
                "commit_rate": round(committed / max(1, offered), 4),
            }
            row.update(_lat_delta(lat0))
            rows.append(row)
            print(json.dumps(row), file=sys.stderr)
    finally:
        for nd in nodes:
            nd.stop()
    out = {"metric": "localnet_load_sweep", "window_s": window_s,
           "rows": rows}
    print(json.dumps(out))
    return out


def main(n_txs: int = 2000):
    priv = gen_priv_key()
    print(f"pre-signing {n_txs} txs...", file=sys.stderr)
    txs = [signed_tx.encode(b"ld-%d=%d" % (i, i), priv)
           for i in range(n_txs)]
    report = ab_common.ABReport("localnet_load_ab")
    serial = report.add_arm(
        _run_arm(False, txs, drain_timeout_s=600.0))
    pipelined = report.add_arm(
        _run_arm(True, txs, drain_timeout_s=600.0))
    return report.finish(
        txs=n_txs,
        speedup=round(pipelined["committed_tx_per_s"] /
                      max(1e-9, serial["committed_tx_per_s"]), 2),
        latency={
            arm: {k: v for k, v in out.items()
                  if k.startswith("submit_to_commit") or k == "lat_txs"}
            for arm, out in report.arms.items()
        },
    )


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--sweep":
        sweep([float(r) for r in sys.argv[2].split(",")],
              window_s=float(sys.argv[3]) if len(sys.argv) > 3 else 12.0)
    else:
        main(int(sys.argv[1]) if len(sys.argv) > 1 else 2000)

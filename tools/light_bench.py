"""BASELINE config bench: light-client sync over 100k blocks.

Reference counterpart: light/client_benchmark_test.go:29-84 (sequential vs
bisection sync over a generated chain). This tool fabricates an N-height
chain (default 100,000; 4 validators — the reference benchmark's shape),
then measures:

1. **bisection** (skipping verification, trust level 1/3) from height 1 to
   the tip — the reference's default client mode; cost is O(log N) hops.
2. **sequential** verification of every header 1..N — rerouted through
   ``verify_adjacent_run`` (tmtpu/light/verifier.py), which fuses each run
   of adjacent commits into ONE BatchVerifier dispatch (north-star reroute
   #4); the reference loops per-hop (light/client.go:613).

Usage: python tools/light_bench.py [--heights 100000] [--backend cpu|tpu]
       [--run 1024] [--sidecar unix:///path/sidecar.sock]

``--sidecar ADDR`` attaches the bench to a running verification sidecar
daemon: commit checks ride the daemon's cross-client coalescer instead
of an in-process backend, so a host-shared device serves the bench and
live nodes together.

Prints one JSON line per scenario, each carrying ``dispatches`` — the
verify dispatches that line cost (in-process batch dispatches plus
sidecar round trips), the denominator for any dispatches/block claim.
Chain fabrication signs heights × validators votes on host (~4 MockPV
ed25519 signs per height).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heights", type=int, default=100_000)
    ap.add_argument("--backend", default="cpu", choices=("cpu", "tpu"))
    ap.add_argument("--run", type=int, default=1024,
                    help="adjacent-run fused batch size (blocks/dispatch)")
    ap.add_argument("--sidecar", default="", metavar="ADDR",
                    help="attach to a running verification sidecar "
                         "(unix:///path.sock or tcp://host:port) instead "
                         "of an in-process backend")
    args = ap.parse_args()

    # one process per chip: "cpu" and --sidecar never import JAX (the
    # daemon owns the device); "tpu" makes THIS process the one on the
    # chip and refuses anything that is not one
    if args.backend == "tpu" and not args.sidecar:
        from tmtpu.tpu import compat

        compat.setup_compile_cache()
        compat.require_tpu("light_bench", allow_emulation=False)
    from tmtpu.crypto import batch as crypto_batch

    if args.sidecar:
        from tmtpu.config.config import SidecarConfig

        crypto_batch.configure_sidecar(SidecarConfig(addr=args.sidecar))
        crypto_batch.set_default_backend("sidecar")
        backend_name = "sidecar"
    else:
        crypto_batch.set_default_backend(args.backend)
        backend_name = args.backend

    from tmtpu.libs import metrics as _metrics

    def dispatch_count():
        """In-process device/CPU batch dispatches + sidecar round trips
        — every way a commit check can cost a dispatch."""
        n = sum(v["count"] for v in
                _metrics.crypto_batch_size.summary_series().values())
        n += sum(_metrics.sidecar_client_requests
                 .summary_series().values())
        return int(n)

    from tests.test_light import (
        CHAIN_ID, WEEK_NS, ChainProvider, FabChain,
    )
    from tmtpu.libs.db import MemDB
    from tmtpu.light.client import Client, TrustOptions
    from tmtpu.light.store import LightStore
    from tmtpu.light.verifier import verify_adjacent_run

    t0 = time.perf_counter()
    chain = FabChain(args.heights, n_vals=4)
    gen_s = time.perf_counter() - t0
    print(f"light_bench: fabricated {args.heights} heights "
          f"({4 * args.heights} sigs) in {gen_s:.1f}s", file=sys.stderr)

    now_ns = chain.blocks[args.heights].header.time + 1_000_000_000
    sigs_total = 4 * args.heights

    # 1. bisection to the tip
    provider = ChainProvider(chain)
    c = Client(
        CHAIN_ID,
        TrustOptions(WEEK_NS, 1, chain.blocks[1].header.hash()),
        provider, [ChainProvider(chain, "w1")],
        LightStore(MemDB()),
    )
    d0 = dispatch_count()
    t0 = time.perf_counter()
    lb = c.verify_light_block_at_height(args.heights, now_ns=now_ns)
    dt = time.perf_counter() - t0
    assert lb.height() == args.heights
    print(json.dumps({
        "metric": "light_bisection_sync",
        "heights": args.heights,
        "value": round(dt * 1e3, 1), "unit": "ms",
        "provider_calls": provider.calls,
        "dispatches": dispatch_count() - d0,
        "backend": backend_name,
    }))

    # 2. sequential: every header verified, commits fused per run
    trusted = chain.blocks[1]
    d0 = dispatch_count()
    t0 = time.perf_counter()
    h = 2
    verified = 0
    while h <= args.heights:
        run = [chain.blocks[i]
               for i in range(h, min(h + args.run, args.heights + 1))]
        n = verify_adjacent_run(trusted, run, WEEK_NS, now_ns, 10_000_000_000)
        assert n == len(run), f"run verify stopped at {h + n}"
        verified += n
        trusted = run[-1]
        h += n
    dt = time.perf_counter() - t0
    blocks_s = verified / dt
    print(json.dumps({
        "metric": "light_sequential_sync_fused",
        "heights": args.heights,
        "value": round(blocks_s, 1), "unit": "blocks/s",
        "run": args.run,
        "wall_s": round(dt, 2),
        "sig_s": round(4 * verified / dt, 1),
        "dispatches": dispatch_count() - d0,
        "backend": backend_name,
    }))


if __name__ == "__main__":
    main()

"""ed25519 batch-verify throughput for a 10k-validator VoteSet, measured on
the chip (BASELINE.md: Go stdlib serial verify ≈ 50-60 µs/sig ⇒ ~18.2k
sig/s per core).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "sig/s", "vs_baseline": N,
   "device": {"platform": "tpu", "kind": ..., "count": N}, ...}

What is measured, end to end: the full bytes → validity-mask +
power-tally + bitarray pipeline for 10,000 REAL distinct votes (distinct
keys, distinct canonical vote sign-bytes) — host prep (length and
canonicality checks, SHA-512 challenge hashing, mod-L reduction), ONE
packed [128, B] host→device transfer per batch, and the device
verify+tally step — under a small set of pipeline STRUCTURES:
  - sync:     prep → put → step → drain, one 10240-lane VoteSet at a time
  - ahead:    one batch in flight while the next preps (double-buffered)
  - threads2/3: independent submit threads
  - sync4/ahead4/threads2_4x: four VoteSets fused into one 40960-lane
              dispatch (commit-verify batches runs of blocks the same
              way: tmtpu/types/commit_verify.py)
All structures run full prep for every batch on rotating distinct data;
per-structure numbers are in the JSON and the headline is the best one.

One process, one chip, no fallback: the measurement runs in this process
and exits non-zero, naming the platform JAX found, when that is not a
TPU — a number from XLA:CPU is never printed under this metric's name.
What is measured is the builders' original choice and calls the device
step directly rather than the path a node takes; ROADMAP.md Speed item 0
replaces it with a cell matrix driven through ``new_batch_verifier``.
"""

import argparse
import json
import queue
import sys
import threading
import time

GO_SERIAL_SIG_S = 1e6 / 55.0  # 55 µs/sig Go stdlib midpoint (BASELINE.md)
LANES = 10_000  # MaxVotesCount (types/vote_set.go:18)


def _make_votes(n: int, seed: int):
    """n distinct validators, one signed precommit each — real canonical
    sign-bytes (types/vote.go:93 semantics), distinct per lane because the
    timestamps differ (types/block.go:807). Keys derive from ``seed``."""
    import numpy as np
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    from tmtpu.types.block import BlockID
    from tmtpu.types.vote import PRECOMMIT, Vote

    rng = np.random.default_rng(seed)
    seeds = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    sks = [Ed25519PrivateKey.from_private_bytes(seeds[i].tobytes())
           for i in range(n)]
    raw = serialization.Encoding.Raw, serialization.PublicFormat.Raw
    pks = [k.public_key().public_bytes(*raw) for k in sks]
    bid = BlockID(hash=bytes(range(32)), parts_total=1, parts_hash=bytes(32))
    base_ns = 1_700_000_000 * 10**9
    msgs = [
        Vote(type=PRECOMMIT, height=12345, round=0, block_id=bid,
             timestamp=base_ns + i, validator_address=bytes(20),
             validator_index=i).sign_bytes("bench-chain")
        for i in range(n)
    ]
    sigs = [sks[i].sign(msgs[i]) for i in range(n)]
    return pks, msgs, sigs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7,
                    help="every key and vote derives from it")
    args = ap.parse_args()

    from tmtpu.tpu import compat

    cache_dir = compat.setup_compile_cache()
    device = compat.require_tpu("bench.py", allow_emulation=False)
    print(f"bench: {device['count']} x {device['kind']} "
          f"({device['platform']}), compile cache {cache_dir}",
          file=sys.stderr)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tmtpu.tpu import dispatch
    from tmtpu.tpu import kernel as tk
    from tmtpu.tpu import sharding as sh
    from tmtpu.tpu import verify as tv

    lanes = LANES
    t0 = time.perf_counter()
    base = _make_votes(lanes, args.seed)
    # 4 rotations of the same votes: distinct per-batch bytes for ~free
    sets = [base] + [
        tuple(x[k:] + x[:k] for x in base) for k in (1, 2, 3)
    ]
    print(f"bench: generated {lanes} votes in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

    tile = tk.DEFAULT_TILE
    pad1 = ((lanes + tile - 1) // tile) * tile
    step = jax.jit(sh.verify_tally_packed_kernel)

    def powers_for(k: int):
        return jnp.asarray(sh.powers_to_limbs(
            ([1000] * lanes + [0] * (pad1 - lanes)) * k))

    powers1 = powers_for(1)

    def prep(i: int, k: int = 1):
        """Full host prep of k rotated VoteSets -> ONE packed numpy array."""
        planes = []
        for j in range(k):
            packed, host_ok = tv.prepare_batch_packed(*sets[(i + j) % 4])
            assert host_ok.all()
            planes.append(dispatch.pad_packed(packed, pad1))
        return planes[0] if k == 1 else np.concatenate(planes, axis=1)

    def check(out, k: int):
        assert bool(jnp.all(out[0][:lanes])), "bench lanes must verify"
        assert sh.limb_sums_to_int(out[1]) == 1000 * lanes * k

    # warmup / compile (shape 1), phase-separated: host prep, the single
    # packed-plane transfer, and the first (compiling) dispatch each get
    # their own wall-clock number so the `phases` object explains where
    # a slow run spent its time
    phases = {k: 0.0 for k in ("prepare", "transfer", "compile",
                               "execute", "readback")}
    t0 = time.perf_counter()
    host_plane = prep(0)
    phases["prepare"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    dev_plane = jax.block_until_ready(jnp.asarray(host_plane))
    phases["transfer"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(step(dev_plane, powers1))
    warm_dt = time.perf_counter() - t0
    check(out, 1)
    t0 = time.perf_counter()
    np.asarray(out[0])
    phases["readback"] = time.perf_counter() - t0
    print(f"bench: compile+warmup {warm_dt:.1f}s", file=sys.stderr)

    # device-only steady state (pre-staged args), for the breakdown
    staged = jnp.asarray(prep(0))
    t0 = time.perf_counter()
    n_dev = 3
    for _ in range(n_dev):
        out = jax.block_until_ready(step(staged, powers1))
    dev_dt = (time.perf_counter() - t0) / n_dev
    # steady-state dispatch = execute; compile = first dispatch minus one
    # steady execute (jit caches on shape, so the warmup run carried the
    # whole trace + lower + compile)
    phases["execute"] = dev_dt
    phases["compile"] = max(0.0, warm_dt - dev_dt)

    def run_sync(n_iters, k, powers):
        t0 = time.perf_counter()
        for i in range(n_iters):
            out = jax.block_until_ready(
                step(jnp.asarray(prep(i, k)), powers))
        check(out, k)
        return (lanes * k * n_iters) / (time.perf_counter() - t0)

    def run_ahead(n_iters, k, powers):
        t0 = time.perf_counter()
        pending = None
        for i in range(n_iters):
            nxt = step(jnp.asarray(prep(i, k)), powers)
            if pending is not None:
                jax.block_until_ready(pending)
            pending = nxt
        jax.block_until_ready(pending)
        check(pending, k)
        return (lanes * k * n_iters) / (time.perf_counter() - t0)

    def run_threads(n_iters_each, nthreads, k, powers):
        results = queue.Queue()

        def work(tid):
            try:
                for i in range(n_iters_each):
                    out = jax.block_until_ready(
                        step(jnp.asarray(prep(tid + nthreads * i, k)),
                             powers))
                results.put(out)
            except Exception as e:  # noqa: BLE001 — propagate to main thread
                results.put(e)

        ts = [threading.Thread(target=work, args=(t,))
              for t in range(nthreads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        outs = [results.get_nowait() for _ in ts]  # one item per worker
        for out in outs:
            if isinstance(out, Exception):
                raise out
        check(outs[0], k)
        return (lanes * k * n_iters_each * nthreads) / dt

    structures = {}

    def measure(name, fn, *a):
        structures[name] = fn(*a)
        print(f"bench: {name}: {structures[name]:,.0f} sig/s",
              file=sys.stderr)

    measure("sync", run_sync, 4, 1, powers1)
    measure("ahead", run_ahead, 4, 1, powers1)
    measure("threads2", run_threads, 2, 2, 1, powers1)
    # fused 4-VoteSet dispatch (new shape: one more compile)
    powers4 = powers_for(4)
    t0 = time.perf_counter()
    out = jax.block_until_ready(step(jnp.asarray(prep(0, 4)), powers4))
    check(out, 4)
    print(f"bench: 4x-shape compile {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    measure("sync4", run_sync, 3, 4, powers4)
    measure("ahead4", run_ahead, 3, 4, powers4)
    measure("threads2_4x", run_threads, 2, 2, 4, powers4)
    measure("threads3", run_threads, 2, 3, 1, powers1)

    best = max(structures, key=structures.get)
    sig_s = structures[best]
    out = {
        "metric": "ed25519_batch_verify_10k_voteset_e2e",
        "value": round(sig_s, 1),
        "unit": "sig/s",
        "vs_baseline": round(sig_s / GO_SERIAL_SIG_S, 2),
        "device": device,
        "impl": "pallas",
        "device_only_sig_s": round(lanes / dev_dt, 1),
        "pipeline": best,
        "structures": {k: round(v, 1) for k, v in structures.items()},
        "lanes": lanes,
        "seed": args.seed,
        "phases": {k: round(v, 4) for k, v in phases.items()},
        # per-batch LATENCY of one 10k VoteSet (prep -> put -> step ->
        # drain), from the measured sync structure — deliberately NOT the
        # inverse of the pipelined-throughput headline, which overlaps
        # batches
        "e2e_ms_per_10k": round(1e3 * LANES / structures["sync"], 2),
    }
    # the BASELINE "Curves" row in the same artifact: sr25519 + secp256k1
    # device rates (ed25519 is the headline above)
    from tools.curve_bench import curve_measurements

    out["curves"] = curve_measurements(1024, 1024)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

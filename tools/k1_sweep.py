"""secp256k1 fused-kernel tile sweep on the chip.

The compiled (Mosaic) form of a Pallas kernel traces a different program
from the interpret mode the CPU tests run, and a kernel's tile choice
moves its device step severalfold (spills past a VMEM budget). This tool
measures, on the device:

  - per-tile device-only step time for the fused kernel (pre-staged
    packed batch, tiles 128/256/512),
  - the plain-XLA device path for comparison,
  - end-to-end rate (host prep + packed H2D + step) at the best tile,
  - the serial-CPU baseline over a sample (the honest comparator:
    reference crypto/secp256k1/secp256k1.go:195-197 verifies via
    libsecp256k1-backed Go; OpenSSL ECDSA measured 2,522 sig/s serial).

One process on the chip; exits non-zero, naming the platform, when JAX
finds no TPU.

Usage: python tools/k1_sweep.py [--lanes 4096] [--tiles 128,256,512]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=4096)
    ap.add_argument("--tiles", default="128,256,512")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()
    tiles = [int(t) for t in args.tiles.split(",")]

    from tmtpu.tpu import compat

    compat.setup_compile_cache()
    device = compat.require_tpu("k1_sweep", allow_emulation=False)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tools.curve_bench import gen_k1

    from tmtpu.crypto import secp256k1 as k1
    from tmtpu.tpu import k1_kernel as kk
    from tmtpu.tpu import k1_verify as kv
    from tmtpu.tpu.dispatch import pad_packed

    import math

    lcm = math.lcm(*tiles)
    lanes = max(args.lanes, lcm)
    lanes = (lanes // lcm) * lcm  # multiple of every tile
    t0 = time.perf_counter()
    pks, msgs, sigs = gen_k1(lanes)
    print(f"k1_sweep: generated {lanes} sigs in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

    # serial CPU baseline (sample)
    sample = min(lanes, 50)
    t0 = time.perf_counter()
    assert all(k1.PubKeySecp256k1(pks[i]).verify_signature(msgs[i], sigs[i])
               for i in range(sample))
    serial_rate = sample / (time.perf_counter() - t0)
    print(f"k1_sweep: serial cpu {serial_rate:,.0f} sig/s", file=sys.stderr)

    t0 = time.perf_counter()
    packed_np, host_ok = kv.prepare_k1_batch_packed(pks, msgs, sigs)
    assert host_ok.all()
    prep_s = time.perf_counter() - t0
    packed_np = pad_packed(packed_np, lanes)
    print(f"k1_sweep: host prep {prep_s:.2f}s "
          f"({lanes / prep_s:,.0f} lanes/s)", file=sys.stderr)

    staged = jax.block_until_ready(jnp.asarray(packed_np))
    planes, parity = kv.split_packed_k1(staged)
    # stage the split planes too: the sweep times the KERNEL, not the split
    planes = [jax.block_until_ready(p) for p in planes]
    parity = jax.block_until_ready(parity)

    def step_tile(tile):
        return kk.k1_verify_compact_kernel(
            planes[0], parity, *planes[1:], tile=tile, interpret=False)

    sweep = {}
    for tile in tiles:
        try:
            t0 = time.perf_counter()
            mask = jax.block_until_ready(step_tile(tile))
            compile_s = time.perf_counter() - t0
            ok = bool(np.asarray(mask).all())
            t0 = time.perf_counter()
            for _ in range(args.iters):
                mask = jax.block_until_ready(step_tile(tile))
            step_ms = 1e3 * (time.perf_counter() - t0) / args.iters
            sweep[str(tile)] = {
                "step_ms": round(step_ms, 1),
                "device_sig_s": round(lanes / (step_ms / 1e3), 1),
                "compile_s": round(compile_s, 1),
                "all_verified": ok,
            }
            print(f"k1_sweep: tile={tile}: {step_ms:.1f}ms "
                  f"({lanes / (step_ms / 1e3):,.0f} sig/s device-only), "
                  f"ok={ok}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            sweep[str(tile)] = {"error": repr(e)[:500]}
            print(f"k1_sweep: tile={tile} FAILED: {e!r}", file=sys.stderr)

    # plain-XLA device path for comparison
    xla = None
    try:
        table = kv.base_table_f32()
        t0 = time.perf_counter()
        mask = jax.block_until_ready(kv._k1_verify_packed_jit(staged, table))
        xla_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.iters):
            mask = jax.block_until_ready(
                kv._k1_verify_packed_jit(staged, table))
        xla_ms = 1e3 * (time.perf_counter() - t0) / args.iters
        xla = {"step_ms": round(xla_ms, 1),
               "device_sig_s": round(lanes / (xla_ms / 1e3), 1),
               "compile_s": round(xla_compile, 1),
               "all_verified": bool(np.asarray(mask).all())}
        print(f"k1_sweep: xla: {xla_ms:.1f}ms "
              f"({lanes / (xla_ms / 1e3):,.0f} sig/s device-only)",
              file=sys.stderr)
    except Exception as e:  # noqa: BLE001
        xla = {"error": repr(e)[:500]}

    good = {int(t): v for t, v in sweep.items() if "step_ms" in v
            and v["all_verified"]}
    out = {
        "metric": "secp256k1_kernel_tile_sweep",
        "lanes": lanes,
        "device": device,
        "sweep": sweep,
        "xla": xla,
        "serial_cpu_sig_s": round(serial_rate, 1),
        "host_prep_lanes_s": round(lanes / prep_s, 1),
    }
    def measure_e2e(step, impl):
        """Fresh prep + H2D + ``step`` per iteration; annotates ``out``."""
        def once():
            t0 = time.perf_counter()
            p, _hok = kv.prepare_k1_batch_packed(pks, msgs, sigs)
            d = jnp.asarray(pad_packed(p, lanes))
            jax.block_until_ready(step(d))
            return time.perf_counter() - t0

        once()  # warm the fresh-prep composition
        e2e_rate = lanes * args.iters / sum(once()
                                            for _ in range(args.iters))
        out["e2e_sig_s"] = round(e2e_rate, 1)
        out["speedup_vs_serial"] = round(e2e_rate / serial_rate, 2)
        out["impl"] = impl
        print(f"k1_sweep: e2e [{impl}]: {e2e_rate:,.0f} sig/s "
              f"({e2e_rate / serial_rate:.1f}x serial)", file=sys.stderr)

    if good:
        best_tile = min(good, key=lambda t: good[t]["step_ms"])
        out["best_tile"] = best_tile

        def kernel_step(d):
            pl_, par_ = kv.split_packed_k1(d)
            return kk.k1_verify_compact_kernel(
                pl_[0], par_, *pl_[1:], tile=best_tile, interpret=False)

        measure_e2e(kernel_step, "pallas-fused")
    elif isinstance(xla, dict) and xla.get("all_verified"):
        # every tile was rejected or wrong: the XLA device path is still
        # a chip number, labelled as what it is
        measure_e2e(lambda d: kv._k1_verify_packed_jit(d, table), "xla")
    print(json.dumps(out))


if __name__ == "__main__":
    main()

"""Per-curve device batch-verify throughput on the chip (the BASELINE
"Curves" row: ed25519, sr25519, secp256k1 batches). ed25519's headline is
bench.py; this tool measures the other two curves' device paths end-to-end
(host prep + H2D + device) and their serial-CPU baselines, printing one
JSON line per curve. One process on the chip; exits non-zero, naming the
platform, when JAX finds no TPU.

Usage: python tools/curve_bench.py [--lanes-sr 512] [--lanes-k1 2048]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure_curve(name, lanes, gen, batch_fn, serial_fn, iters=3) -> dict:
    """One curve's end-to-end batch rate + serial baseline, as a dict
    (bench.py embeds these in its single JSON line; main() prints them)."""
    t0 = time.perf_counter()
    pks, msgs, sigs = gen(lanes)
    gen_s = time.perf_counter() - t0
    print(f"{name}: generated {lanes} sigs in {gen_s:.1f}s", file=sys.stderr)

    # serial CPU baseline over a sample
    sample = min(lanes, 50)
    t0 = time.perf_counter()
    ok = [serial_fn(pks[i], msgs[i], sigs[i]) for i in range(sample)]
    serial_rate = sample / (time.perf_counter() - t0)
    assert all(ok)

    # compile + warm
    t0 = time.perf_counter()
    mask = batch_fn(pks, msgs, sigs)
    assert mask.all()
    print(f"{name}: compile+first {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    t0 = time.perf_counter()
    for _ in range(iters):
        mask = batch_fn(pks, msgs, sigs)
    rate = lanes * iters / (time.perf_counter() - t0)
    return {
        "metric": f"{name}_batch_verify_e2e",
        "value": round(rate, 1), "unit": "sig/s",
        "lanes": lanes,
        "serial_cpu_sig_s": round(serial_rate, 1),
        "speedup_vs_serial": round(rate / serial_rate, 2),
    }


def gen_sr(n):
    from tmtpu.crypto import sr25519 as sr

    keys = [sr.gen_priv_key_from_secret(b"cb%d" % i) for i in range(n)]
    msgs = [b"curve-bench-sr-%d" % i for i in range(n)]
    return ([k.pub_key().bytes() for k in keys], msgs,
            [k.sign(m) for k, m in zip(keys, msgs)])


def gen_k1(n):
    from tmtpu.crypto import secp256k1 as k1

    keys = [k1.gen_priv_key() for _ in range(n)]
    msgs = [b"curve-bench-k1-%d" % i for i in range(n)]
    return ([k.pub_key().bytes() for k in keys], msgs,
            [k.sign(m) for k, m in zip(keys, msgs)])


def gen_mixed(n):
    """Round-robin ed25519/sr25519/secp256k1 lanes (a mixed-curve valset's
    commit, the BASELINE 'mixed sets' config)."""
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    from tmtpu.crypto import secp256k1 as k1
    from tmtpu.crypto import sr25519 as sr
    from tmtpu.crypto.ed25519 import PubKeyEd25519

    raw = serialization.Encoding.Raw, serialization.PublicFormat.Raw
    msgs, sigs, pk_objs = [], [], []
    for i in range(n):
        msg = b"curve-bench-mixed-%d" % i
        if i % 3 == 0:
            sk = Ed25519PrivateKey.from_private_bytes(
                (b"%032d" % i)[:32])
            sigs.append(sk.sign(msg))
            pk_objs.append(PubKeyEd25519(sk.public_key().public_bytes(*raw)))
        elif i % 3 == 1:
            sk = sr.gen_priv_key_from_secret(b"mx%d" % i)
            sigs.append(sk.sign(msg))
            pk_objs.append(sk.pub_key())
        else:
            sk = k1.gen_priv_key()
            sigs.append(sk.sign(msg))
            pk_objs.append(sk.pub_key())
        msgs.append(msg)
    return pk_objs, msgs, sigs


def _batch_verify_mixed(pk_objs, msgs, sigs):
    """One TPUBatchVerifier pass over the mixed set (per-curve device
    dispatch under the hood — tmtpu/crypto/batch.py _split)."""
    import numpy as np

    from tmtpu.crypto import batch as crypto_batch

    bv = crypto_batch.TPUBatchVerifier()
    for pk, m, s in zip(pk_objs, msgs, sigs):
        bv.add(pk, m, s)
    _all_ok, mask = bv.verify()
    return np.asarray(mask)


def curve_measurements(lanes_sr: int, lanes_k1: int, only=None) -> dict:
    """sr25519 + secp256k1 + mixed-set device-path rates keyed by curve.
    The caller has already established that JAX's platform is a TPU.
    ``only``: optional iterable of curve names to measure (signature
    generation for the skipped curves is skipped too)."""
    from tmtpu.crypto import secp256k1 as k1
    from tmtpu.crypto import sr25519 as sr
    from tmtpu.tpu import dispatch

    def device(curve):
        return lambda p, m, s: dispatch.device_verify(curve, p, m, s)[0]

    out = {}
    for name, lanes, gen, batch_fn, serial_fn in (
        ("sr25519", lanes_sr, gen_sr, device("sr25519"),
         lambda p, m, s: sr.PubKeySr25519(p).verify_signature(m, s)),
        ("secp256k1", lanes_k1, gen_k1, device("secp256k1"),
         lambda p, m, s: k1.PubKeySecp256k1(p).verify_signature(m, s)),
        ("mixed", min(lanes_sr, lanes_k1) * 3, gen_mixed,
         _batch_verify_mixed,
         lambda pk, m, s: pk.verify_signature(m, s)),
    ):
        if only is not None and name not in only:
            continue
        out[name] = measure_curve(name, lanes, gen, batch_fn, serial_fn)
        if name == "sr25519":
            # serial_cpu_sig_s above is THIS repo's pure-Python
            # schnorrkel (the only serial impl in the image); the fair
            # reference comparator is go-schnorrkel
            # (crypto/sr25519/pubkey.go:50), estimated low-thousands
            # sig/s/core — no Go toolchain exists here to measure it, so
            # speedup claims must quote this row, not the pure-Python one
            out[name]["fair_serial_baseline"] = {
                "impl": "go-schnorrkel (reference crypto/sr25519)",
                "est_sig_s": [2000, 4000],
                "method": "estimate; Go toolchain absent in image",
            }
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes-sr", type=int, default=512)
    ap.add_argument("--lanes-k1", type=int, default=2048)
    ap.add_argument("--curves", default=None,
                    help="comma list: sr25519,secp256k1,mixed (default all)")
    args = ap.parse_args()
    only = None
    if args.curves:
        only = {c.strip() for c in args.curves.split(",") if c.strip()}
        known = {"sr25519", "secp256k1", "mixed"}
        bad = only - known
        if bad or not only:
            ap.error(f"unknown curves {sorted(bad)}; choose from "
                     f"{sorted(known)}")

    from tmtpu.tpu import compat

    compat.setup_compile_cache()
    device = compat.require_tpu("curve_bench", allow_emulation=False)
    results = curve_measurements(args.lanes_sr, args.lanes_k1, only=only)
    for res in results.values():
        print(json.dumps(dict(res, device=device)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

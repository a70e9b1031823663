#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tendermint-tpu still starts on
the chip: the verify path driven once through the entry points a user
calls, at the protocol's full width, checked against the serial CPU
reference.

The parent imports no JAX. It runs two parts one after the other; in each
exactly ONE process owns the chip:

* Part A — the in-process backend (``crypto_backend = "tpu"``), one child
  process: per curve a full-width flush through ``new_batch_verifier("tpu")``
  (ed25519 10,000 distinct votes via ``verify_tally`` and ``verify``;
  sr25519 and secp256k1 2,048 lanes each; ~1% adversarial lanes), masks
  and tallies equal to ``CPUBatchVerifier`` lane for lane; one LIVE
  consensus height at 10,000 validators (tmtpu/e2e/flood_round.py) and
  ``verify_commit`` on the stored commit; with two or more devices, the
  same flushes through the mesh route with per-device lane counts.
* Part B — the served path: ``tmtpu sidecar --backend tpu`` (the one
  process on the chip) plus ``tmtpu init`` / ``start --crypto-backend
  sidecar`` as a second process in which importing jax is made to fail;
  the parent, as an RPC client, submits 2,000 signed-envelope txs, waits
  until all are committed exactly once, reads a sample back with
  ``abci_query`` and reads the daemon's stats.

It fails — non-zero exit, no result line — when JAX finds no TPU, when a
mask, tally or read-back is wrong, or when ANY safety ladder fired on the
way: a dispatch off platform ``tpu`` or off the Pallas kernel, a
device-error / deadline / breaker-open / probe-failed CPU fallback, a
mesh fallback, a breaker with a recorded failure, a sidecar client
fallback. On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

All keys, votes and txs come from ``--seed``; the native host-prep
library is rebuilt from ``hostprep.c``; nothing is read from a previous
run except the persistent compile cache (tmtpu/tpu/compat.py).
"""

from __future__ import annotations

import argparse
import base64
import collections
import concurrent.futures
import hashlib
import json
import os
import queue
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "CHIP_SMOKE_PART_A_RESULT "

# a fallback for one of these reasons means the device path broke and a
# safety rung served the lanes; small-batch / unsupported are policy
FORBIDDEN_FALLBACKS = ("device-error", "deadline", "breaker-open",
                       "probe-failed")
BREAKERS = ("crypto.tpu", "crypto.mesh", "pallas.ed25519",
            "pallas.sr25519", "pallas.secp256k1")
DEVICE_IMPLS = ("pallas", "mesh-pallas", "mesh-xla")

# the widths the contract fixes; cut only by --part / the tests
ED_LANES = 10_000          # MaxVotesCount, one full VoteSet
CURVE_LANES = 2_048
N_VALIDATORS = 10_000
N_TXS = 2_000


def say(msg: str) -> None:
    print(msg, flush=True)


class Failures(list):
    """Every broken expectation, in order; empty means the run is good."""

    def fail(self, msg: str) -> None:
        self.append(msg)
        say(f"FAIL: {msg}")


# ---------------------------------------------------------------------------
# Part A — runs in the child: the one process on the chip.


class CompileWatch:
    """JAX's own compile telemetry (jax.monitoring): seconds lowering
    and compiling, and persistent-cache hits and writes. Trace seconds
    are not summed — nested jits report overlapping durations — so
    tracing is what remains of a first flush's wall time."""

    EVENTS = {
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }

    def __init__(self):
        import jax

        self.totals = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, seconds: float, **_kw) -> None:
        key = self.EVENTS.get(event)
        if key:
            self.totals[key] += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.totals["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.totals["cache_misses"] += 1

    def read(self) -> collections.Counter:
        return collections.Counter(self.totals)


def _series(metric) -> dict:
    return {k: dict(v) for k, v in metric.summary_series().items()}


def _series_delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        b = before.get(k, {"count": 0, "sum": 0.0})
        if v["count"] != b["count"]:
            out[k] = {"count": v["count"] - b["count"],
                      "sum": v["sum"] - b["sum"]}
    return out


class Meter:
    """Differences the crypto metric set and the compile telemetry around
    one phase's DEVICE work (never around a CPU reference run)."""

    def __init__(self, watch=None):
        self.watch = watch
        self.device_series: dict = {}   # accumulated over all phases
        self.ladders0 = ladder_counters()   # the gate's baseline

    def __enter__(self):
        from tmtpu.e2e import flood_round
        from tmtpu.libs import metrics as _m

        self._t0 = time.perf_counter()
        self._lat0 = _series(_m.crypto_verify_latency)
        self._tot0 = flood_round.dispatch_totals()
        self._c0 = self.watch.read() if self.watch else None
        return self

    def __exit__(self, *exc):
        from tmtpu.e2e import flood_round
        from tmtpu.libs import metrics as _m

        self.wall_s = time.perf_counter() - self._t0
        self.dispatched = _series_delta(
            _series(_m.crypto_verify_latency), self._lat0)
        # dispatches, lanes, and seconds inside the dispatch calls (prep
        # through readback; on first sight trace + lower + compile too)
        self.totals = {k: v - self._tot0[k] for k, v
                       in flood_round.dispatch_totals().items()}
        self.compile = (self.watch.read() - self._c0) if self.watch \
            else collections.Counter()
        for k, v in self.dispatched.items():
            acc = self.device_series.setdefault(k, {"count": 0, "sum": 0.0})
            acc["count"] += v["count"]
            acc["sum"] += v["sum"]
        return False

    def line(self) -> str:
        c, t = self.compile, self.totals
        per = t["lanes"] / t["dispatches"] if t["dispatches"] else 0
        impls = ",".join(sorted(self.dispatched)) or "none"
        # a shape worth caching (compile >= 1 s) is either read from the
        # persistent cache or compiled and written to it
        shapes = c["cache_hits"] + c["cache_misses"]
        return (f"wall={self.wall_s:.2f}s dispatches={t['dispatches']:.0f} "
                f"lanes/dispatch={per:.0f} in_dispatch={t['seconds']:.2f}s "
                f"shapes_compiled={shapes} "
                f"(pcache_hits={c['cache_hits']} "
                f"pcache_writes={c['cache_misses']}) "
                f"lower={c['lower_s']:.1f}s "
                f"compile={c['compile_s']:.1f}s [{impls}]")


def _flip(b: bytes, i: int, bit: int = 0) -> bytes:
    ba = bytearray(b)
    ba[i] ^= 1 << bit
    return bytes(ba)


def ed25519_votes(seed: int, n: int):
    """``n`` distinct validators' precommits for one VoteSet: real
    canonical sign-bytes (distinct per lane: index and timestamp), keys
    from ``seed``, non-uniform powers. -> [(PubKey, msg, sig, power)]."""
    from tmtpu.e2e import flood_round
    from tmtpu.types.block import BlockID
    from tmtpu.types.vote import PRECOMMIT, Vote

    bid = BlockID(hash=hashlib.sha256(b"smoke-%d" % seed).digest(),
                  parts_total=1, parts_hash=bytes(32))
    items = []
    for i in range(n):
        pv = flood_round.co_signer(seed, i - 1, mixed=False)
        pk = pv.get_pub_key()
        msg = Vote(type=PRECOMMIT, height=7, round=0, block_id=bid,
                   timestamp=1_700_000_000 * 10**9 + i,
                   validator_address=pk.address(),
                   validator_index=i).sign_bytes("chip-smoke")
        items.append((pk, msg, pv.priv_key.sign(msg), 1 + i % 7))
    return items


def corrupt_ed25519(items, bad):
    """The ed25519 adversarial lanes the differential tests use
    (tests/test_tpu_verify.py): flipped R / s bit, s >= L, non-canonical
    A.y, wrong message, bad length, flipped R sign, another key."""
    from tmtpu.crypto import ed25519 as ed
    from tmtpu.crypto import ed25519_ref as ref

    for j, i in enumerate(bad):
        pk, msg, sig, power = items[i]
        kind = j % 8
        if kind == 0:
            sig = _flip(sig, 0)
        elif kind == 1:
            sig = _flip(sig, 40)
        elif kind == 2:
            s = int.from_bytes(sig[32:], "little") + ref.L
            sig = sig[:32] + s.to_bytes(32, "little")
        elif kind == 3:
            pk = ed.PubKeyEd25519((ref.P + 1).to_bytes(32, "little"))
        elif kind == 4:
            msg = _flip(msg, 3)
        elif kind == 5:
            sig = sig[:63]
        elif kind == 6:
            sig = _flip(sig, 31, 7)
        else:
            pk = items[(i + 1) % len(items)][0]
        items[i] = (pk, msg, sig, power)


def sr25519_lanes(seed: int, n: int):
    from tmtpu.crypto import sr25519 as sr

    items = []
    for i in range(n):
        k = sr.gen_priv_key_from_secret(b"smoke-sr-%d-%d" % (seed, i))
        msg = b"smoke-sr-msg-%d-%d" % (seed, i)
        items.append((k.pub_key(), msg, k.sign(msg), 1 + i % 5))
    return items


def corrupt_sr25519(items, bad):
    """tests/test_tpu_sr25519.py's lanes: corrupt R, wrong message, wrong
    key, marker bit cleared, s + L, bad ristretto encoding of R (odd) and
    of A (>= p), truncated, corrupt s."""
    from tmtpu.crypto import sr25519 as sr

    for j, i in enumerate(bad):
        pk, msg, sig, power = items[i]
        kind = j % 9
        if kind == 0:
            sig = _flip(sig, 3, 6)
        elif kind == 1:
            msg = msg + b"!"
        elif kind == 2:
            pk = items[(i + 1) % len(items)][0]
        elif kind == 3:
            sig = sig[:63] + bytes([sig[63] & 0x7F])
        elif kind == 4:
            s = int.from_bytes(sig[32:63] + bytes([sig[63] & 0x7F]),
                               "little")
            if s + sr.L < 1 << 255:
                sig = sig[:32] + ((s + sr.L) | (1 << 255)).to_bytes(
                    32, "little")
            else:
                sig = _flip(sig, 33)
        elif kind == 5:
            sig = bytes([sig[0] | 1]) + sig[1:]
        elif kind == 6:
            pk = sr.PubKeySr25519((2**255 - 18).to_bytes(32, "little"))
        elif kind == 7:
            sig = sig[:40]
        else:
            sig = _flip(sig, 40, 3)
        items[i] = (pk, msg, sig, power)


def secp256k1_lanes(seed: int, n: int):
    from tmtpu.crypto import secp256k1 as k1

    items = []
    for i in range(n):
        d = int.from_bytes(hashlib.sha256(
            b"smoke-k1-%d-%d" % (seed, i)).digest(), "big")
        k = k1.PrivKeySecp256k1((d % (k1.N - 1) + 1).to_bytes(32, "big"))
        msg = b"smoke-k1-msg-%d-%d" % (seed, i)
        items.append((k.pub_key(), msg, k.sign(msg), 1 + i % 3))
    return items


def corrupt_secp256k1(items, bad):
    """tests/test_tpu_k1.py's lanes: corrupt r, wrong message, wrong
    key, high-S, r = 0, r >= n, bad prefix, x off the curve, truncated,
    corrupt s."""
    from tmtpu.crypto import secp256k1 as k1

    for j, i in enumerate(bad):
        pk, msg, sig, power = items[i]
        kind = j % 10
        if kind == 0:
            sig = _flip(sig, 5, 5)
        elif kind == 1:
            msg = msg + b"x"
        elif kind == 2:
            pk = items[(i + 1) % len(items)][0]
        elif kind == 3:
            s = int.from_bytes(sig[32:], "big")
            sig = sig[:32] + (k1.N - s).to_bytes(32, "big")
        elif kind == 4:
            sig = bytes(32) + sig[32:]
        elif kind == 5:
            sig = k1.N.to_bytes(32, "big") + sig[32:]
        elif kind == 6:
            pk = k1.PubKeySecp256k1(b"\x05" + pk.bytes()[1:])
        elif kind == 7:
            pk = k1.PubKeySecp256k1(b"\x02" + bytes(32))
        elif kind == 8:
            sig = sig[:50]
        else:
            sig = _flip(sig, 45, 2)
        items[i] = (pk, msg, sig, power)


CURVES = {
    "ed25519": (ed25519_votes, corrupt_ed25519),
    "sr25519": (sr25519_lanes, corrupt_sr25519),
    "secp256k1": (secp256k1_lanes, corrupt_secp256k1),
}


def make_lanes(curve: str, seed: int, n: int):
    """``n`` distinct lanes of ``curve`` with about 1% (at least one of
    every kind the batch can hold) adversarial. -> (items, bad indices)"""
    import random

    gen, corrupt = CURVES[curve]
    items = gen(seed, n)
    rng = random.Random(seed)
    bad = sorted(rng.sample(range(n), min(n, max(10, n // 100))))
    corrupt(items, bad)
    return items, bad


def _run_verifier(backend: str, items, tally: bool):
    """One flush through the public batch API with the sigcache cleared,
    so every lane reaches the backend. -> (mask, tallied or None)"""
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.crypto import sigcache

    sigcache.DEFAULT.invalidate_all()
    bv = crypto_batch.new_batch_verifier(backend)
    for pk, msg, sig, power in items:
        bv.add(pk, msg, sig, power)
    if tally:
        _ok, mask, tallied = bv.verify_tally()
        return list(mask), tallied
    _ok, mask = bv.verify()
    return list(mask), None


def reference(items):
    """The plain serial reference: ``CPUBatchVerifier``'s mask and the
    host sum of the valid lanes' powers."""
    mask, _ = _run_verifier("cpu", items, tally=False)
    return mask, sum(it[3] for it, ok in zip(items, mask) if ok)


def flush_phase(name: str, items, bad, want, tally: bool, fails: Failures,
                meter: Meter, backend: str = "tpu", reps: int = 2) -> dict:
    """``reps`` identical flushes of ``items`` through
    ``new_batch_verifier(backend)`` (the first pays the compile, the
    last is the steady one), each checked lane for lane against the
    reference ``want = (mask, tally)``."""
    want_mask, want_tally = want
    walls = []
    for rep in range(reps):
        with meter:
            mask, tallied = _run_verifier(backend, items, tally)
        walls.append(meter.wall_s)
        say(f"  {name} rep{rep}: lanes={len(items)} {meter.line()}")
        wrong = [i for i, (a, b) in enumerate(zip(mask, want_mask))
                 if a != b]
        if wrong or len(mask) != len(want_mask):
            fails.fail(f"{name}: mask differs from CPUBatchVerifier on "
                       f"{len(wrong)} lanes, first {wrong[:8]} "
                       f"(adversarial lanes: {len(bad)})")
        if tally and tallied != want_tally:
            fails.fail(f"{name}: device tally {tallied} != host sum "
                       f"{want_tally}")
    if reps > 1:
        say(f"  {name}: first flush {walls[0]:.2f}s, steady "
            f"{walls[-1]:.3f}s -> first-sight cost "
            f"{walls[0] - walls[-1]:.1f}s (trace + lower + compile)")
    return {"name": name, "lanes": len(items), "bad": len(bad),
            "valid": sum(want_mask), "first_s": walls[0],
            "steady_s": walls[-1], "mask": mask, "tallied": tallied}


def flush_plan(ed_lanes: int, curve_lanes: int, mesh: bool = False):
    """The flushes of one configuration as (curve, lanes, tally): the
    full VoteSet through ed25519's tally step, then one mask flush per
    curve — on the single device the ed25519 mask flush is full-width
    too, through the mesh route it is ``curve_lanes`` like the others."""
    return (("ed25519", ed_lanes, True),
            ("ed25519", curve_lanes if mesh else ed_lanes, False),
            ("sr25519", curve_lanes, False),
            ("secp256k1", curve_lanes, False))


def mesh_curve_lanes(curve_lanes: int) -> int:
    """Lanes for the per-curve flushes on a multi-chip host: a flush
    rides the mesh at ``shard_min_lanes`` (2,048) DEVICE lanes, and the
    adversarial bad-length lanes never reach the device, so the flush
    is made a little wider than the threshold."""
    return curve_lanes + curve_lanes // 32


def flush_phases(seed: int, plan, fails: Failures, meter: Meter,
                 backend: str = "tpu", lanes_cache: dict = None) -> dict:
    """Run ``plan`` (see ``flush_plan``). A curve's lanes and reference
    answers are generated once, at the widest flush the first plan asks
    of it, and kept in ``lanes_cache`` for the next configuration (the
    mesh); a narrower flush takes the first lanes of the set. Results
    are keyed ``curve.step.lanes``."""
    cache = lanes_cache if lanes_cache is not None else {}
    out = {}
    for curve, n, tally in plan:
        if curve not in cache:
            widest = max(p[1] for p in plan if p[0] == curve)
            t0 = time.perf_counter()
            items, bad = make_lanes(curve, seed, widest)
            gen_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            want_mask, _tally = reference(items)
            say(f"  {curve}: {widest} lanes generated in {gen_s:.1f}s, "
                f"{len(bad)} adversarial; CPU reference "
                f"{time.perf_counter() - t0:.1f}s, {sum(want_mask)} valid")
            if any(want_mask[i] for i in bad):
                # every mutation must be a rejection (the tests pin it)
                fails.fail(f"{curve}: an adversarial lane verified on "
                           f"the CPU reference")
            cache[curve] = (items, bad, want_mask)
        items, bad, want_mask = cache[curve]
        items, want_mask = items[:n], want_mask[:n]
        want = (want_mask, sum(it[3] for it, ok in zip(items, want_mask)
                               if ok))
        name = f"{curve}.{'tally' if tally else 'mask'}.{n}"
        out[name] = flush_phase(name, items, [i for i in bad if i < n],
                                want, tally, fails, meter, backend=backend)
    return out


def live_round_phase(n_validators: int, seed: int, fails: Failures,
                     meter: Meter, backend: str = "tpu",
                     consensus_config=None) -> dict:
    """One live consensus height with ``n_validators`` validators, then
    ``verify_commit`` on the stored commit with the sigcache cleared."""
    from tmtpu.crypto import sigcache
    from tmtpu.e2e import flood_round

    n_co = n_validators - 1
    with meter:
        r = flood_round.run(n_co, backend=backend, seed=seed,
                            consensus_config=consensus_config,
                            timeout=600.0)
    for curve, lanes, tally, sec in r["warmed"]:
        say(f"  warmed {curve} lanes={lanes} tally={tally} {sec:.1f}s")
    say(f"  live round: validators={r['validators']} "
        f"keygen={r['keygen_s']:.1f}s sign={r['sign_s']:.1f}s "
        f"proposal->commit={r['round_s']:.2f}s "
        f"inject->commit={r['inject_to_commit_s']:.2f}s "
        f"precommits_in_commit={r['precommits_in_commit']} "
        f"round_dispatches={r['dispatches']} "
        f"round_lanes={r['lanes_dispatched']} "
        f"round_in_dispatch={r['dispatch_s']:.2f}s")
    say(f"  live round total (warm-up + round): {meter.line()}")
    # by power, as the protocol counts: the live validator's own is 40, so
    # fewer than 2/3 of the signatures can close a small set's commit
    vals = r["vals"]
    power = sum(v.voting_power for v, s in
                zip(vals.validators, r["commit"].signatures)
                if not s.is_absent())
    if 3 * power <= 2 * vals.total_voting_power():
        fails.fail(f"live round: commit holds only "
                   f"{r['precommits_in_commit']} precommits, {power} of "
                   f"{vals.total_voting_power()} in power")
    if n_co >= 64 and r["lanes_dispatched"] < 1.5 * n_co:
        # all prevotes plus the 2/3 of precommits that closed the commit
        # must have ridden batched dispatches
        fails.fail(f"live round: only {r['lanes_dispatched']} of "
                   f"~{2 * n_co} votes rode batched dispatches")
    sigcache.DEFAULT.invalidate_all()
    with meter:
        try:
            r["vals"].verify_commit(r["chain_id"], r["block_id"],
                                    r["height"], r["commit"],
                                    backend=backend)
        except Exception as e:  # noqa: BLE001 — a wrong commit is a result
            fails.fail(f"verify_commit on the stored commit raised {e!r}")
    say(f"  verify_commit: {meter.line()}")
    return {k: r[k] for k in ("validators", "round_s", "inject_to_commit_s",
                              "dispatches", "lanes_dispatched",
                              "dispatch_s", "precommits_in_commit")}


def ladder_counters() -> dict:
    """The counters a fired safety ladder leaves behind, as one flat
    ``{"metric{labels}": value}`` reading."""
    from tmtpu.libs import metrics as _m

    out = {}
    for metric in (_m.crypto_cpu_fallback, _m.crypto_batch_deadline_exceeded,
                   _m.crypto_mesh_fallback_total, _m.crypto_breaker_failures):
        for key, v in metric.summary_series().items():
            out[f"{metric.name}{{{key}}}"] = v
    return out


def gate(fails: Failures, meter: Meter, expect_device: bool = True) -> None:
    """Fail unless every device phase ran where and how it should, and no
    safety ladder fired since ``meter`` was made: see the module
    docstring."""
    from tmtpu.libs import breaker as _bk

    say("dispatches by curve/platform/impl:")
    for key, v in sorted(meter.device_series.items()):
        say(f"  {key}: dispatches={v['count']} seconds={v['sum']:.2f}")
        labels = dict(kv.split("=", 1) for kv in key.split(","))
        if not expect_device:
            continue
        if labels.get("backend") != "tpu":
            fails.fail(f"a dispatch ran on platform "
                       f"{labels.get('backend')!r}: {key}")
        if labels.get("impl") not in DEVICE_IMPLS:
            fails.fail(f"a dispatch left the Pallas kernel: {key}")
    if expect_device and not any("impl=pallas" in k
                                 for k in meter.device_series):
        fails.fail("no dispatch ran impl=pallas")
    for key, now in sorted(ladder_counters().items()):
        v = now - meter.ladders0.get(key, 0)
        if not v:
            continue
        say(f"  {key}: +{v:.0f}")
        if "cpu_fallback" in key and not any(
                f"reason={r}" in key for r in FORBIDDEN_FALLBACKS):
            continue    # small-batch / unsupported lanes are policy
        fails.fail(f"a safety ladder fired: {key} +{v:.0f}")
    snaps = _bk.snapshot_all()
    for name in BREAKERS:
        snap = snaps.get(name) or _bk.get(name).snapshot()
        say(f"  breaker {name}: {snap['state']}"
            + (f" last_error={snap['last_error']}"
               if snap["last_error"] else ""))
        if snap["state"] != "closed":
            fails.fail(f"breaker {name} is {snap['state']}: "
                       f"{snap['last_error']}")


def preflight() -> dict:
    """Place the compile cache, open JAX, refuse anything but a TPU,
    rebuild the native library; print what was found."""
    from tmtpu.tpu import compat

    cache_dir = compat.setup_compile_cache()
    import jax
    import jaxlib

    dev = compat.device_info()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a label only
        libtpu = "unknown"
    say(f"platform={dev['platform']} device_kind={dev['kind']!r} "
        f"devices={dev['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"compile_cache={cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
        f" entries)")
    if dev["platform"] != "tpu":
        say(f"chip_smoke: JAX found platform {dev['platform']!r}, not a "
            f"TPU; refusing to run")
        raise SystemExit(2)   # before any lane is generated
    from tmtpu import native

    lib = native.rebuild()
    say(f"native hostprep: rebuilt from hostprep.c, bound={lib is not None}")
    if lib is None:
        say("chip_smoke: hostprep.c did not build or bind; the numpy "
            "host path is not what ships")
        raise SystemExit(2)
    return dev


def _mesh_phase(seed: int, dev: dict, single: dict, fails: Failures,
                meter: Meter, lanes_cache: dict) -> None:
    """The 10,000-lane tally flush and one 2,048-lane flush per curve
    through the mesh route: answers equal to the single-device ones,
    every device holding lanes, and which implementation each route ran
    beside its single-device seconds."""
    from tmtpu.tpu import mesh_dispatch

    meshed = flush_phases(
        seed, flush_plan(ED_LANES, mesh_curve_lanes(CURVE_LANES), mesh=True),
        fails, meter, lanes_cache=lanes_cache)
    for name, m in meshed.items():
        one = single[name]
        if m["mask"] != one["mask"] or m["tallied"] != one["tallied"]:
            fails.fail(f"{name}: mesh answer differs from the "
                       f"single-device answer")
        say(f"  {name}: single device steady {one['steady_s']:.3f}s, "
            f"mesh steady {m['steady_s']:.3f}s")
    snap = mesh_dispatch.snapshot()
    say(f"  mesh: devices={snap['devices']} "
        f"dispatches={snap['dispatches']} "
        f"lanes by device id={snap['occupancy_lanes']}")
    if snap["dispatches"] < 2 * len(meshed):
        fails.fail(f"mesh route took {snap['dispatches']} of "
                   f"{2 * len(meshed)} flushes")
    if snap["devices"] != dev["count"] or \
            len(snap["occupancy_lanes"]) != dev["count"] or \
            not all(snap["occupancy_lanes"].values()):
        fails.fail(f"mesh did not place lanes on all {dev['count']} "
                   f"devices: {snap['occupancy_lanes']}")


def part_a(seed: int) -> int:
    import traceback

    t_start = time.perf_counter()
    dev = preflight()
    from tmtpu.config.config import ConsensusConfig, CryptoConfig
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.tpu import mesh_dispatch

    fails = Failures()
    meter = Meter(CompileWatch())
    crypto_batch.set_default_backend("tpu")
    multi = dev["count"] >= 2
    lanes_cache: dict = {}

    def phase(title: str, fn) -> None:
        # a phase that raises is a failure, not the end of the run: the
        # later phases still say what they can
        say(f"== Part A {title} [t={time.perf_counter() - t_start:.0f}s]")
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — recorded, run goes on
            traceback.print_exc(file=sys.stdout)
            fails.fail(f"{title}: raised {e!r}")

    single: dict = {}

    def flushes_single():
        # on a multi-chip host [crypto] mesh_devices = 1 is the switch
        # that keeps every flush on one chip; there the mesh phase's
        # narrower ed25519 mask flush runs here too, for its seconds
        crypto_batch.configure(CryptoConfig(mesh_devices=1 if multi else 0))
        plan = flush_plan(ED_LANES, CURVE_LANES)
        if multi:
            wide = mesh_curve_lanes(CURVE_LANES)
            plan = flush_plan(ED_LANES, wide) + \
                flush_plan(ED_LANES, wide, mesh=True)[1:2]
        single.update(flush_phases(seed, plan, fails, meter,
                                   lanes_cache=lanes_cache))
        if mesh_dispatch.dispatch_count():
            fails.fail("a single-device flush rode the mesh")

    def flushes_mesh():
        crypto_batch.configure(CryptoConfig())
        _mesh_phase(seed, dev, single, fails, meter, lanes_cache)

    def live_round():
        crypto_batch.configure(CryptoConfig())
        live_round_phase(N_VALIDATORS, seed, fails, meter,
                         consensus_config=ConsensusConfig())

    phase("flushes, single device", flushes_single)
    if multi:
        phase(f"flushes, mesh route over {dev['count']} devices",
              flushes_mesh)
    phase(f"live consensus height, {N_VALIDATORS} validators", live_round)
    phase("gate", lambda: gate(fails, meter))
    say(RESULT_TAG + json.dumps({
        "ok": not fails, "failures": list(fails), "device": dev,
        "seconds": round(time.perf_counter() - t_start, 1)}))
    return 1 if fails else 0


# ---------------------------------------------------------------------------
# Part B — the served path. Runs in the parent, which never imports JAX.


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """A started process whose output is copied to ours line by line."""

    def __init__(self, name: str, argv, env):
        self.name = name
        self.lines: list = []
        self._cond = threading.Condition()
        # same process group as the parent: whoever kills the group at
        # a time limit takes the chip's owner with it
        self.proc = subprocess.Popen(
            argv, env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        self._pump = threading.Thread(target=self._copy, daemon=True,
                                      name=f"pump-{name}")
        self._pump.start()

    def _copy(self) -> None:
        for line in self.proc.stdout:
            with self._cond:
                self.lines.append(line.rstrip("\n"))
                self._cond.notify_all()
            say(f"  [{self.name}] {line.rstrip()}")

    def wait_for_line(self, needle: str, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        with self._cond:
            while not any(needle in ln for ln in self.lines):
                left = deadline - time.monotonic()
                if left <= 0 or self.proc.poll() is not None:
                    return False
                self._cond.wait(timeout=min(left, 1.0))
            return True

    def finish(self, timeout: float) -> int:
        """Wait for the process to end by itself (killed past
        ``timeout``) and for its output to drain. -> exit code"""
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
        rc = self.proc.wait()
        self._pump.join(timeout=5.0)
        return rc

    def terminate(self, timeout: float = 60.0) -> int:
        """SIGTERM, then ``finish``. -> exit code"""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        return self.finish(timeout)


def signed_txs(seed: int, n: int):
    """``n`` distinct signed-envelope txs (mempool/signed_tx.py) from 50
    senders. The kvstore app keys on the bytes before the first ``=`` of
    the FULL tx, envelope included. -> [(tx, app key, app value)]"""
    from tmtpu.crypto import ed25519 as ed
    from tmtpu.mempool import signed_tx

    senders = [ed.gen_priv_key_from_secret(b"smoke-tx-%d-%d" % (seed, s))
               for s in range(50)]
    out = []
    for i in range(n):
        tx = signed_tx.encode(b"smoke-%d-%d=%d" % (seed, i, i),
                              senders[i % len(senders)])
        k, _, v = tx.partition(b"=")
        out.append((tx, k, v))
    return out


def part_b(seed: int, fails: Failures, n_txs: int = N_TXS,
           sidecar_backend: str = "tpu", expect_device: bool = True,
           start_timeout: float = 900.0) -> None:
    from tmtpu.rpc.client import HTTPClient
    from tmtpu.sidecar.client import SidecarClient

    work = tempfile.mkdtemp(prefix="chip-smoke-")
    home = os.path.join(work, "home")
    sock = f"unix://{work}/sidecar.sock"
    rpc_port = _free_port()
    # the node must not be able to open the device its daemon owns:
    # importing jax in that process is made to fail outright
    poison = os.path.join(work, "nojax", "jax")
    os.makedirs(poison)
    with open(os.path.join(poison, "__init__.py"), "w") as f:
        f.write('raise ImportError("a crypto_backend=sidecar node must '
                'not import jax: the daemon owns the chip")\n')
    env = dict(os.environ, PYTHONPATH=REPO, PYTHONUNBUFFERED="1",
               TMTPU_SIDECAR_ADDR=sock,
               # a single-node deployment: the widest joint dispatch is
               # one mempool gather (256) plus the node's own votes; with
               # the cap at 512 the daemon's warm() covers every shape it
               # can dispatch, so no client request meets a compile
               TMTPU_SIDECAR_MAX_LANES_PER_DISPATCH="512",
               TMTPU_RPC_LADDR=f"tcp://127.0.0.1:{rpc_port}",
               TMTPU_P2P_LADDR=f"tcp://127.0.0.1:{_free_port()}")
    node_env = dict(env, PYTHONPATH=os.path.dirname(poison) + os.pathsep
                    + REPO)
    tm = [sys.executable, "-m", "tmtpu.cmd"]
    children = []
    try:
        subprocess.run(tm + ["init", "--home", home], env=node_env,
                       cwd=REPO, check=True, timeout=120)
        t0 = time.perf_counter()
        daemon = Child("sidecar", tm + [
            "sidecar", "--home", home, "--backend", sidecar_backend,
            "--addr", sock], env)
        children.append(daemon)
        if not daemon.wait_for_line("Sidecar listening", start_timeout):
            fails.fail("sidecar daemon did not come up")
            return
        say(f"  sidecar up (warm) in {time.perf_counter() - t0:.1f}s")
        node = Child("node", tm + [
            "start", "--home", home, "--crypto-backend", "sidecar"],
            node_env)
        children.append(node)
        if not node.wait_for_line("Node started", 120.0):
            fails.fail("node did not start")
            return
        url = f"http://127.0.0.1:{rpc_port}"

        txs = signed_txs(seed, n_txs)
        # 48 keep-alive connections opened one at a time (the server's
        # listen backlog is small), then used concurrently so the
        # mempool's gather windows fill well past TMTPU_TPU_MIN_BATCH
        idle: "queue.Queue" = queue.Queue()
        for _ in range(48):
            c = HTTPClient(url, timeout=120.0)
            c.status()
            idle.put(c)

        def submit(tx: bytes):
            c = idle.get()
            try:
                return c.broadcast_tx_sync(tx)
            finally:
                idle.put(c)

        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(48) as pool:
            results = list(pool.map(submit, [t[0] for t in txs]))
        while not idle.empty():
            idle.get().close()
        rejected = [r for r in results if int(r.get("code", 0)) != 0]
        say(f"  submitted {n_txs} signed txs in "
            f"{time.perf_counter() - t0:.1f}s, {len(rejected)} rejected")
        if rejected:
            fails.fail(f"{len(rejected)} txs rejected at CheckTx: "
                       f"{rejected[0]}")

        rpc = HTTPClient(url, timeout=60.0)
        want = collections.Counter(t[0] for t in txs)
        seen: collections.Counter = collections.Counter()
        scanned = 0
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            latest = int(rpc.status()["sync_info"]["latest_block_height"])
            for h in range(scanned + 1, latest + 1):
                for raw in rpc.block(h)["block"]["data"]["txs"] or []:
                    seen[base64.b64decode(raw)] += 1
            scanned = latest
            if sum(seen[t] for t in want) >= n_txs:
                break
            time.sleep(0.5)
        missing = [t for t in want if seen[t] == 0]
        dup = [t for t in want if seen[t] > 1]
        say(f"  committed: {n_txs - len(missing)}/{n_txs} over "
            f"{scanned} heights in {time.perf_counter() - t0:.1f}s, "
            f"{len(dup)} more than once")
        if missing or dup:
            fails.fail(f"{len(missing)} txs not committed, {len(dup)} "
                       f"committed more than once")
        # read a sample back; a sender whose pubkey holds a "=" byte
        # gives all its txs one app key, so sample the unambiguous ones
        n_key = collections.Counter(k for _tx, k, _v in txs)
        unique = [t for t in txs if n_key[t[1]] == 1]
        say(f"  reading back {min(20, len(unique))} of {len(unique)} "
            f"uniquely keyed txs")
        if not unique:
            fails.fail("no uniquely keyed tx to read back")
        for _tx, k, v in unique[:: max(1, len(unique) // 20)][:20]:
            got = rpc.abci_query(data="0x" + k.hex())["response"]
            if base64.b64decode(got.get("value") or "") != v:
                fails.fail(f"abci_query read back {got} for key "
                           f"{k[-16:]!r}, want {v!r}")
                break

        fb = rpc.metrics()["metrics"][
            "tendermint_sidecar_client_fallback_total"]["series"]
        say(f"  node sidecar_client_fallback: {fb or 0}")
        if any(fb.values()):
            fails.fail(f"node sidecar_client_fallback = {fb}")
        rpc.close()

        sc = SidecarClient(sock, client_id="chip-smoke")
        try:
            stats = sc.stats(deadline_s=30.0)
        finally:
            sc.close()
        say(f"  daemon: backend={stats['backend']} device={stats['device']}"
            f" dispatches={stats['coalescer']['dispatches']}")
        for s in stats["warmed_shapes"]:
            say(f"  daemon warmed {s}")
        say(f"  daemon dispatched: {stats['dispatched']}")
        say(f"  daemon cpu_fallback: {stats['cpu_fallback']}")
        if expect_device:
            dev_keys = [k for k in stats["dispatched"]
                        if "backend=tpu" in k and "impl=pallas" in k]
            other = [k for k in stats["dispatched"] if k not in dev_keys]
            if stats["backend"] != "tpu" or not dev_keys or other:
                fails.fail(f"daemon backend {stats['backend']!r} "
                           f"dispatched {stats['dispatched']}")
            for key, v in stats["cpu_fallback"].items():
                if any(f"reason={r}" in key
                       for r in FORBIDDEN_FALLBACKS) and v:
                    fails.fail(f"daemon crypto_cpu_fallback {key} = {v}")
            for name, br in stats["breakers"].items():
                if br["state"] != "closed" or br["last_error"]:
                    fails.fail(f"daemon breaker {name}: {br['state']} "
                               f"{br['last_error']}")
    finally:
        for child in reversed(children):
            rc = child.terminate()
            say(f"  {child.name} exit code after SIGTERM: {rc}")
            if rc != 0:
                fails.fail(f"{child.name} exited {rc} on SIGTERM")
        shutil.rmtree(work, ignore_errors=True)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="every key, vote and tx derives from it")
    ap.add_argument("--part", choices=("all", "a", "b"), default="all",
                    help="run one part only (no result line is printed "
                         "unless all ran)")
    ap.add_argument("--child", choices=("a",), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    if args.child == "a":
        return part_a(args.seed)

    # a time limit's SIGTERM must reach the finally blocks that stop the
    # children: nothing may be left holding the chip
    signal.signal(signal.SIGTERM, lambda *_a: sys.exit(143))
    t0 = time.perf_counter()
    fails = Failures()
    device = None
    if args.part in ("all", "a"):
        say("== Part A: in-process backend, one child process on the chip")
        child = Child("A", [sys.executable, os.path.abspath(__file__),
                            "--child", "a", "--seed", str(args.seed)],
                      dict(os.environ, PYTHONPATH=REPO,
                           PYTHONUNBUFFERED="1"))
        try:
            rc = child.finish(timeout=1100.0)
        finally:
            child.terminate(timeout=10.0)
        for line in child.lines:
            if line.startswith(RESULT_TAG):
                res = json.loads(line[len(RESULT_TAG):])
                device = res["device"]
                fails.extend(f"part A: {f}" for f in res["failures"])
                say(f"Part A finished in {res['seconds']}s, "
                    f"{len(res['failures'])} failures")
        if device is None:
            say(f"chip_smoke: part A child exited {rc} without a result")
            return rc or 1   # no TPU, or it crashed: nothing more to run
        if rc != 0 and not fails:
            fails.fail(f"part A child exited {rc}")
    if args.part in ("all", "b"):
        say(f"== Part B: sidecar daemon on the chip, JAX-free node "
            f"[t={time.perf_counter() - t0:.0f}s]")
        part_b(args.seed, fails)
    say(f"chip_smoke: {len(fails)} failures in "
        f"{time.perf_counter() - t0:.0f}s")
    for f in fails:
        say(f"  - {f}")
    if fails:
        return 1
    if args.part != "all":
        say(f"chip_smoke: part {args.part} passed; no result line without "
            f"--part all")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

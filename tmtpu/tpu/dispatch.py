"""The device dispatch, written once: one table, one function.

A flush is the same seven steps on every curve, for the mask and for the
fused verify+tally step, on one chip and on the mesh: Pallas or the XLA
graph → the padded shape → host prep, straight into the operand at that
width → a fused flush's power limbs at that width → one transfer →
execute → read back and ``observe_crypto_batch``. ``CURVES`` has one row
a curve naming what differs (host prep, the jitted steps, the kernel's
tile floor, the sharded builders, the chaos site); ``device_verify`` is
the only way production code reaches the device, called from one place
(``crypto/batch.py TPUBatchVerifier._verify_pending``, under the
``crypto.tpu`` breaker and the per-batch deadline).

The ladder, once: the mesh when ``mesh_dispatch.route`` says so (a
failure there counts against ``crypto.mesh`` only and the same flush
goes on single-device inside the same call) → the Pallas kernel under
the ``pallas.<curve>`` breaker → the XLA graph on the operands already
padded and transferred. Every rung returns the exact per-lane result or
raises; the caller re-verifies serially what raised.

The policy shared by all rows lives here too: which implementation
(``use_pallas_kernel``), the Pallas breakers, the shape quantizer
(``padded_lanes`` over ``_pad_to_bucket``).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tmtpu.libs import breaker as _bk
from tmtpu.libs import faultinject, trace
from tmtpu.libs import metrics as _m
from tmtpu.tpu import k1_kernel as kk
from tmtpu.tpu import k1_verify as kv
from tmtpu.tpu import kernel as tk
from tmtpu.tpu import mesh_dispatch as md
from tmtpu.tpu import sharding as sh
from tmtpu.tpu import sr_verify as srv
from tmtpu.tpu import verify as tv


@dataclass(frozen=True)
class Curve:
    """One row of the table. A step is a jitted callable; a ``mesh_*``
    field is a builder ``mesh -> jitted sharded callable`` (built once a
    mesh, ``mesh_dispatch.sharded``). Fields left ``None``: a row with no
    fused tally step has its powers summed on the host by the caller; a
    row with no ``mesh_mask`` runs its mask flush on the mesh through the
    fused tally callable with zero powers (one compiled entry serves both
    steps)."""
    name: str                # metric label, span prefix, pallas.<name> breaker
    fault: str               # chaos site on the dispatch boundary
    prepare: Callable        # (pks, msgs, sigs, padded) -> (packed uint8
    #                          [rows, padded], lanes B.. = lane 0; host_ok [B])
    kernel: Callable         # Pallas mask step: packed -> mask
    tile: int                # the kernel's tile: the floor of its padded shapes
    xla: Callable            # XLA mask step: (packed, table) -> mask
    table: Callable          # () -> the XLA graph's fixed-base table
    mesh_mask: Optional[Callable] = None
    tally_kernel: Optional[Callable] = None   # (packed, limbs) -> (mask, sums, bits)
    tally_xla: Optional[Callable] = None      # (packed, limbs, table) -> same
    mesh_tally_kernel: Optional[Callable] = None
    mesh_tally_xla: Optional[Callable] = None


CURVES: Dict[str, Curve] = {row.name: row for row in (
    Curve(
        name="ed25519",
        fault=faultinject.register("tpu.ed25519.batch"),
        prepare=tv.prepare_batch_packed,
        kernel=tv._verify_packed_kernel_jit,
        tile=tk.DEFAULT_TILE,
        xla=tv._verify_packed_jit,
        table=tv.base_table_f32,
        tally_kernel=sh.verify_tally_packed_kernel_jit,
        tally_xla=sh.verify_tally_packed_compact_jit,
        mesh_tally_kernel=sh.sharded_verify_tally_packed_kernel,
        mesh_tally_xla=sh.sharded_verify_tally_packed,
    ),
    Curve(
        name="sr25519",
        fault=faultinject.register("tpu.sr25519.batch"),
        prepare=srv.prepare_sr_batch_packed,
        kernel=srv._sr_kernel_packed_jit,
        tile=tk.DEFAULT_TILE,
        xla=srv._sr_verify_packed_jit,
        table=tv.base_table_f32,
        mesh_mask=sh.sharded_verify_sr,
    ),
    Curve(
        name="secp256k1",
        fault=faultinject.register("tpu.secp256k1.batch"),
        prepare=kv.prepare_k1_batch_packed,
        kernel=kv._k1_kernel_packed_jit,
        tile=kk.DEFAULT_TILE,
        xla=kv._k1_verify_packed_jit,
        table=kv.base_table_f32,
        mesh_mask=sh.sharded_verify_k1,
    ),
)}


# --- shared policy -----------------------------------------------------------


def use_pallas_kernel() -> bool:
    """Device-graph implementation choice. The fused Pallas kernels are
    the production path on real TPUs; the plain-XLA graphs remain for
    CPU/virtual-mesh runs (tests, multichip dryrun), where Mosaic isn't
    in play and XLA:CPU compiles the scatter form much faster. Override
    with TMTPU_TPU_IMPL=pallas|xla."""
    impl = os.environ.get("TMTPU_TPU_IMPL", "")
    if impl == "pallas":
        return True
    if impl == "xla":
        return False
    # a failing jax.devices() surfaces: quietly choosing the XLA graph
    # would hide a broken runtime behind a tenfold slower, green run
    return jax.devices()[0].platform == "tpu"


# substrings identifying a deterministic compile/lowering rejection —
# retrying those pays full trace+lowering cost per batch for nothing,
# while transient runtime faults (device OOM, a preempted runtime)
# deserve one retry before the breaker trips.
_COMPILE_ERR_MARKERS = ("mosaic", "lowering", "unsupported", "unimplemented",
                        "cannot lower", "pallas")


def is_compile_error(e: Exception) -> bool:
    if isinstance(e, NotImplementedError):
        return True
    s = f"{type(e).__name__}: {e}".lower()
    return any(m in s for m in _COMPILE_ERR_MARKERS)


# Pallas-fallback breakers, one per kernel family: a compile/lowering
# rejection is deterministic → trip permanently; transient runtime
# faults open after 2 consecutive failures and RE-PROBE after backoff,
# so one bad minute does not degrade the process to XLA until restart.
# half_open_probes=1: one good batch re-trusts the kernel.
PALLAS_BREAKER_DEFAULTS = dict(failure_threshold=2, backoff_base_s=30.0,
                               backoff_max_s=600.0, half_open_probes=1)


def pallas_breaker(curve_name: str):
    return _bk.get(f"pallas.{curve_name}", **PALLAS_BREAKER_DEFAULTS)


def note_pallas_failure(br, e: Exception) -> None:
    """Shared failure policy for a Pallas kernel dispatch exception."""
    if is_compile_error(e):
        br.trip_permanent(f"{type(e).__name__}: {e}")
    else:
        br.record_failure(e)


def _pad_to_bucket(n: int) -> int:
    """Round the batch up to a small set of sizes so jit caches stay warm
    (recompiling per odd batch size would dwarf the verify itself).
    The floor is 64: every consensus-sized flush (a vote burst, a commit
    slice) shares ONE compiled shape instead of churning 8/16/32 variants
    — the pad lanes are microseconds of device time while each extra
    shape is a fresh multi-second XLA compile. Above that, powers of two
    up to 4096, then multiples of 2048 (a 10k VoteSet pads to 10240
    instead of 16384 — padding waste matters more than cache entries at
    commit-verify scale)."""
    if n > 4096:
        return (n + 2047) // 2048 * 2048
    b = 64
    while b < n:
        b *= 2
    return b


def padded_lanes(lanes: int, tile: int = 0, n_devices: int = 1) -> int:
    """The one shape quantizer: the bucket, then the kernel's tile floor
    (``tile`` 0 = the XLA graph), then on a mesh the quantum that gives
    every shard equal lanes in whole tiles — or, for the XLA graph, in
    whole uint32 words of the packed bitarray (``md.WORD_LANES``)."""
    padded = max(tile, _pad_to_bucket(lanes))
    if n_devices > 1:
        q = max(tile, md.WORD_LANES) * n_devices
        padded = -(-padded // q) * q
    return padded


def pad_packed(packed: np.ndarray, padded: int) -> np.ndarray:
    """numpy [rows, B] -> [rows, padded], replicating lane 0 (well-formed;
    pad results are discarded). Row-count agnostic: ed25519/sr25519 pack
    128 rows, secp256k1 packs 168. A flush does not copy through here (a
    row's ``prepare`` writes at the padded width); tools and tests that
    hold an unpadded plane do."""
    B = packed.shape[1]
    if padded == B:
        return packed
    return np.concatenate(
        [packed, np.repeat(packed[:, :1], padded - B, axis=1)], axis=1
    )


def backend_label() -> str:
    """The jax device platform for metric labels ('cpu', 'tpu', ...) —
    only consulted after a dispatch, so the backend is already up."""
    return jax.devices()[0].platform


# --- the function ------------------------------------------------------------


def device_verify(curve: str, pks, msgs, sigs, powers=None,
                  min_lanes: int = 0) -> Tuple[np.ndarray, Optional[int]]:
    """One flush of one curve's lanes: ``(mask, tallied)``.

    ``mask`` is bool [B], exactly per-signature serial verification (no
    batch equation: each lane is checked on its own, so a mixed batch
    yields the exact mask with no re-run). With ``powers`` on a row that
    has a fused tally step, ``tallied`` is the summed power of the valid
    lanes, reduced on the device (lanes the host prep rejects count
    zero); otherwise ``None`` and the caller sums on the host.

    ``min_lanes`` pads the flush as if it held at least that many lanes:
    a caller whose flushes vary in length but must all meet one compiled
    shape (a blocksync run, crypto/batch.py ``warm_pinned``) gives the
    longest it makes.
    """
    row = CURVES[curve]
    if len(sigs) == 0:
        return np.zeros(0, dtype=bool), None
    faultinject.fire(row.fault)
    routed = max(len(sigs), min_lanes)
    if md.route(curve, routed):
        try:
            return _flush(row, pks, msgs, sigs, powers, min_lanes,
                          md.get_mesh())
        except Exception as e:  # noqa: BLE001 — broken collectives must
            # not take down verification: crypto.mesh counts it (never
            # crypto.tpu, whose single-device path may be healthy)
            md.note_failure(curve, routed, e)
    return _flush(row, pks, msgs, sigs, powers, min_lanes, None)


def _flush(row: Curve, pks, msgs, sigs, powers, min_lanes: int, mesh
           ) -> Tuple[np.ndarray, Optional[int]]:
    """The seven steps, on one device (``mesh`` None) or lane-sharded
    over ``mesh``. The shape is settled before host prep (it depends on
    the lane count, the row's tile and the implementation, none of which
    host prep changes), so the operands are built once, at that width. On
    the mesh every mask route is the lane-sharded XLA graph and only the
    fused tally runs the kernel (under shard_map, the power reduction one
    psum); nothing there is retried on another implementation — a failure
    is the caller's to take single-device."""
    B = len(sigs)
    fused = powers is not None and row.tally_kernel is not None
    n = int(mesh.devices.size) if mesh is not None else 1
    with_limbs = fused or (mesh is not None and row.mesh_mask is None)
    name, label, attrs = ("crypto.batch_verify", "", {}) if mesh is None \
        else ("crypto.mesh_verify", "mesh-", {"shards": n})
    t0 = time.perf_counter()
    with trace.span(name + ("_tally" if fused else ""), curve=row.name,
                    lanes=B, **attrs) as sp:
        if mesh is None:
            pbr = pallas_breaker(row.name)
            use_kernel = use_pallas_kernel() and pbr.allow()
            k_step, x_step = (row.tally_kernel, row.tally_xla) if fused \
                else (row.kernel, row.xla)
        else:
            pbr = None  # a fault on the mesh is crypto.mesh's to count
            use_kernel = fused and use_pallas_kernel()
            k_step = md.sharded(row.mesh_tally_kernel, mesh) \
                if use_kernel else None
            x_step = md.sharded(row.mesh_tally_xla if with_limbs
                                else row.mesh_mask, mesh)
        impl = label + ("pallas" if use_kernel else "xla")
        padded = padded_lanes(max(B, min_lanes),
                              row.tile if use_kernel else 0, n)
        sp.set(impl=impl, padded=padded)
        with trace.span(f"{row.name}.prepare", lanes=B):
            packed, host_ok = row.prepare(pks, msgs, sigs, padded)
        with trace.span(f"{row.name}.pad", padded=padded):
            if fused:
                # pad lanes replicate lane 0's BYTES only — their power
                # limbs stay zero, so padding can never leak into the tally
                limbs = np.zeros((sh.POWER_LIMBS, padded), dtype=np.int32)
                sh.powers_to_limbs(
                    np.where(host_ok, np.asarray(powers, dtype=np.int64), 0),
                    out=limbs[:, :B])
                more = int(np.count_nonzero(limbs[1:, :B].any(axis=0)))
                _m.crypto_tally_power_lanes.inc(B - more, limbs="one")
                _m.crypto_tally_power_lanes.inc(more, limbs="more")
        with trace.span(f"{row.name}.device_put"):
            args = (jnp.asarray(packed),)  # ONE transfer
        with trace.span(f"{row.name}.execute", impl=impl):
            if with_limbs:
                args += (jnp.asarray(limbs) if fused else jnp.zeros(
                    (sh.POWER_LIMBS, padded), dtype=jnp.int32),)
            out = None
            if use_kernel:
                try:
                    out = jax.block_until_ready(k_step(*args))
                    if pbr is not None:
                        pbr.record_success()
                except Exception as e:  # noqa: BLE001 — kernel fault:
                    # breaker decides latch-vs-retry, XLA serves THIS batch
                    if pbr is None:
                        raise
                    note_pallas_failure(pbr, e)
                    impl = "xla"
                    sp.set(impl=impl)
            if out is None:
                out = jax.block_until_ready(x_step(*args, row.table()))
        with trace.span(f"{row.name}.readback"):
            dev_mask = out[0] if with_limbs else out
            mask = np.asarray(dev_mask)[:B] & host_ok
            t1 = time.perf_counter()
            tallied = sh.limb_sums_to_int(out[1]) if fused else None
            psum_s = time.perf_counter() - t1
    total = time.perf_counter() - t0
    if mesh is not None:
        md.note_dispatch(row.name, B, padded, dev_mask, psum_s, total, impl)
    _m.observe_crypto_batch(row.name, backend_label(), impl, B, padded, total)
    return mask, tallied

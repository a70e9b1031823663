"""Mesh-aware dispatch: shard production verify flushes across chips.

The four sharded primitives in tpu/sharding.py are MULTICHIP-certified
but, until this layer, nothing in the production path called them —
``crypto/batch.py`` and the sidecar coalescer dispatched to one device.
This module owns the process-wide device :class:`~jax.sharding.Mesh`
and the per-curve sharded callables, and routes any flush of at least
``crypto.shard_min_lanes`` lanes across every chip on the host:

- ed25519 rides the fused verify+tally step with the voting-power
  reduction psum'd ON DEVICE, so the host reads back one packed mask
  plus five int32 limb sums regardless of mesh size;
- sr25519 / secp256k1, and every mask-only ed25519 flush, ride their
  lane-sharded XLA graphs (verification is embarrassingly parallel — no
  collective at all). Only the ed25519 tally step runs the fused Pallas
  kernel under shard_map; the metric label says which one ran
  (``mesh-pallas`` / ``mesh-xla``).

Contract with the callers: every entry point here either returns the
EXACT single-device result or raises. ``crypto.batch.TPUBatchVerifier``
wraps each call in its own try — a mesh failure records against the
``crypto.mesh`` breaker (never ``crypto.tpu``) and the flush falls
through to the single-device path inside the same dispatch window, so
the degradation ladder is mesh → single-device → CPU-serial with exact
masks at every rung.

Padding: the packed bitarray output shards one uint32 word per 32
lanes, so sharded lane counts must be a multiple of ``32 x n_devices``
(the dryrun_multichip quantum); on top of that the padded size reuses
``tv._pad_to_bucket`` so the jit cache sees the same handful of shapes
the single-device path does. Pad lanes replicate lane 0's bytes but
carry ZERO power limbs, so they can never contribute to the tally.

jax is imported lazily — ``configure()`` runs in every node at startup,
including CPU-only ones that must not pay backend init.

Tier-1 testability: under ``XLA_FLAGS=--xla_force_host_platform_device_
count=N`` (tests/conftest.py) the whole path runs on a virtual CPU
mesh; ``TMTPU_MESH_DEVICES`` / ``TMTPU_SHARD_MIN_LANES`` are call-time
env overrides for tests and tools.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from tmtpu.libs import breaker as _bk

# mesh failures get their own failure budget: a broken collective on one
# host must degrade to single-device dispatch WITHOUT opening crypto.tpu
# (the single-device path may be perfectly healthy)
MESH_BREAKER_NAME = "crypto.mesh"

ED25519 = "ed25519"
SR25519 = "sr25519"
SECP256K1 = "secp256k1"

_lock = threading.Lock()
# defaults mirror config/config.py CryptoConfig; configure() overwrites
_cfg = {"mesh_devices": 0, "shard_min_lanes": 2048}
_state: Dict = {
    "mesh": None,          # cached jax Mesh
    "mesh_key": None,      # (n, device ids) the cache was built for
    "fns": {},             # (kind, mesh_key) -> jitted sharded callable
    "dispatches": 0,
    "occupancy": {},       # device id -> cumulative lanes placed there
    "last": None,          # last dispatch summary (sidecar Stats)
}


class MeshUnavailable(RuntimeError):
    """No multi-device mesh can be built (one device, or init failed)."""


def breaker() -> "_bk.CircuitBreaker":
    return _bk.get(MESH_BREAKER_NAME)


def configure(crypto_cfg) -> None:
    """Apply CryptoConfig mesh knobs. Safe to call on config reload;
    a device-count change drops the cached mesh and callables."""
    set_overrides(
        mesh_devices=getattr(crypto_cfg, "mesh_devices", 0),
        shard_min_lanes=getattr(crypto_cfg, "shard_min_lanes", 2048))


def set_overrides(mesh_devices: Optional[int] = None,
                  shard_min_lanes: Optional[int] = None) -> None:
    """Direct knob setter (sidecar daemon startup, tools). None leaves
    a knob untouched."""
    with _lock:
        if mesh_devices is not None and \
                mesh_devices != _cfg["mesh_devices"]:
            _cfg["mesh_devices"] = int(mesh_devices)
            _state["mesh"] = None
            _state["mesh_key"] = None
            _state["fns"].clear()
        if shard_min_lanes is not None:
            _cfg["shard_min_lanes"] = int(shard_min_lanes)


def reset() -> None:
    """Drop every cache and counter (tests)."""
    with _lock:
        _state["mesh"] = None
        _state["mesh_key"] = None
        _state["fns"].clear()
        _state["dispatches"] = 0
        _state["occupancy"] = {}
        _state["last"] = None


def mesh_devices() -> int:
    """Configured mesh width; 0 = every visible device. The env var is
    read at call time (same pattern as batch_deadline_s) so tests and
    tools can steer without a config file."""
    raw = os.environ.get("TMTPU_MESH_DEVICES", "")
    if raw:
        try:
            return int(raw)
        except ValueError:
            pass
    return _cfg["mesh_devices"]


def shard_min_lanes() -> int:
    raw = os.environ.get("TMTPU_SHARD_MIN_LANES", "")
    if raw:
        try:
            return int(raw)
        except ValueError:
            pass
    return _cfg["shard_min_lanes"]


def _get_mesh():
    """The cached Mesh, rebuilt when the configured width changes.
    Raises :class:`MeshUnavailable` when fewer than 2 devices answer."""
    import jax

    from tmtpu.tpu import sharding as sh

    want = mesh_devices()
    devs = jax.devices()
    n = len(devs) if want <= 0 else min(want, len(devs))
    if n < 2:
        raise MeshUnavailable(
            f"mesh needs >=2 devices, have {len(devs)} "
            f"(mesh_devices={want})")
    key = (n, tuple(d.id for d in devs[:n]))
    with _lock:
        if _state["mesh"] is not None and _state["mesh_key"] == key:
            return _state["mesh"]
    mesh = sh.make_mesh(n)
    with _lock:
        _state["mesh"] = mesh
        _state["mesh_key"] = key
        _state["fns"].clear()
    from tmtpu.libs import metrics as _m

    _m.crypto_mesh_devices.set(n)
    return mesh


def device_count() -> int:
    """Devices a sharded dispatch would span right now; 0 when the mesh
    cannot be built (never raises — route() gates on it). A one-device
    host is the ordinary case and silent; any other failure to build
    the mesh is counted, so a host whose chips went missing shows up in
    ``crypto_mesh_fallback_total{reason="mesh-init"}``."""
    try:
        return int(_get_mesh().devices.size)
    except MeshUnavailable:
        return 0
    except Exception:  # noqa: BLE001 — unavailable == 0
        from tmtpu.libs import metrics as _m

        _m.crypto_mesh_fallback_total.inc(curve="any", reason="mesh-init")
        return 0


def route(curve: str, lanes: int) -> bool:
    """Gate: should this flush ride the mesh? False below the lane
    threshold, on a <2-device host, or while the crypto.mesh breaker is
    open (the open-breaker skip is counted as a fallback so operators
    can see sharded capacity sitting unused)."""
    if lanes < max(1, shard_min_lanes()):
        return False
    if device_count() < 2:
        return False
    if not breaker().allow():
        from tmtpu.libs import metrics as _m

        _m.crypto_mesh_fallback_total.inc(lanes, curve=curve,
                                          reason="breaker-open")
        return False
    return True


def note_failure(curve: str, lanes: int, exc: Exception) -> None:
    """A sharded dispatch raised: record against crypto.mesh (only) and
    count the lanes that will re-ride the single-device path."""
    breaker().record_failure(exc)
    from tmtpu.libs import metrics as _m

    _m.crypto_mesh_fallback_total.inc(lanes, curve=curve,
                                      reason="device-error")


def padded_lanes(b: int, n_devices: int) -> int:
    """Bucket-pad B (jit-cache stability, tv._pad_to_bucket), then round
    up to the mesh quantum 32 x n so every shard gets whole bitarray
    words and equal lane counts."""
    from tmtpu.tpu import verify as tv

    q = 32 * n_devices
    base = max(b, tv._pad_to_bucket(b))
    return ((base + q - 1) // q) * q


def _fn(kind: str, mesh, builder):
    key = (kind, _state["mesh_key"])
    with _lock:
        f = _state["fns"].get(key)
    if f is None:
        f = builder(mesh)
        with _lock:
            _state["fns"][key] = f
    return f


def _shard_lanes(mask) -> Dict[int, int]:
    """Observed placement of a sharded result: device id -> lanes of
    the mask that device computed (read off the output's own shards, so
    a mesh that put everything on device 0 shows as such)."""
    out: Dict[int, int] = {}
    for s in mask.addressable_shards:
        out[s.device.id] = out.get(s.device.id, 0) + int(s.data.shape[0])
    return out


def _note_dispatch(curve: str, lanes: int, padded: int,
                   shard_lanes: Dict[int, int], psum_s: float,
                   total_s: float, impl: str) -> None:
    from tmtpu.libs import metrics as _m
    from tmtpu.libs import timeline as _tl

    n = len(shard_lanes)
    per_shard = max(shard_lanes.values())
    with _lock:
        _state["dispatches"] += 1
        seq = _state["dispatches"]
        for d, got in shard_lanes.items():
            _state["occupancy"][d] = _state["occupancy"].get(d, 0) + got
        _state["last"] = {
            "seq": seq, "curve": curve, "lanes": lanes,
            "padded": padded, "devices": n, "shard_lanes": per_shard,
            "impl": impl, "seconds": round(total_s, 6),
        }
    _m.crypto_mesh_devices.set(n)
    _m.crypto_mesh_dispatches_total.inc(curve=curve)
    _m.crypto_mesh_shard_lanes.observe(per_shard, curve=curve)
    _m.crypto_mesh_pad_ratio.observe(padded / max(1, lanes), curve=curve)
    _m.crypto_mesh_psum_seconds.observe(psum_s)
    _tl.record_flush(backend="mesh", curve=curve, lanes=lanes,
                     shards=n, shard_lanes=per_shard,
                     seconds=round(total_s, 6))


def dispatch_count() -> int:
    with _lock:
        return _state["dispatches"]


def snapshot() -> Dict:
    """Mesh occupancy for sidecar Stats / health surfaces: cumulative
    sharded lanes per device id, as placed, plus the last dispatch's
    shape and implementation."""
    with _lock:
        return {
            "devices": (_state["mesh_key"][0]
                        if _state["mesh_key"] else 0),
            "shard_min_lanes": shard_min_lanes(),
            "dispatches": _state["dispatches"],
            "occupancy_lanes": {str(d): v for d, v
                                in sorted(_state["occupancy"].items())},
            "last": dict(_state["last"]) if _state["last"] else None,
            "breaker": breaker().state,
        }


# --- sharded entry points ---------------------------------------------------


def batch_verify_tally_mesh(pks, msgs, sigs, powers
                            ) -> Tuple[np.ndarray, int]:
    """ed25519 fused verify + tally across the host mesh: bit-exact twin
    of sharding.batch_verify_tally with the power reduction psum'd over
    the "sig" axis. Raises on any device/mesh failure (caller degrades
    to single-device)."""
    import jax
    import jax.numpy as jnp

    from tmtpu.libs import trace
    from tmtpu.tpu import sharding as sh
    from tmtpu.tpu import verify as tv

    b = len(sigs)
    if b == 0:
        return np.zeros(0, dtype=bool), 0
    mesh = _get_mesh()
    n = int(mesh.devices.size)
    t0 = time.perf_counter()
    with trace.span("crypto.mesh_verify_tally", curve=ED25519,
                    lanes=b, shards=n) as sp:
        packed, host_ok = tv.prepare_batch_packed(pks, msgs, sigs)
        p = np.asarray(powers, dtype=np.int64).copy()
        p[~host_ok] = 0
        use_kernel = tv.use_pallas_kernel()
        padded = padded_lanes(b, n)
        if use_kernel:
            from tmtpu.tpu import kernel as tk

            q = tk.DEFAULT_TILE * n
            padded = ((padded + q - 1) // q) * q
        impl = "mesh-pallas" if use_kernel else "mesh-xla"
        sp.set(padded=padded, impl=impl)
        # pad lanes replicate lane 0's BYTES only — their power limbs
        # stay zero, so padding can never leak into the tally
        power_limbs = np.zeros((sh.POWER_LIMBS, padded), dtype=np.int32)
        power_limbs[:, :b] = sh.powers_to_limbs(p)
        packed_h = tv.pad_packed(packed, padded)
        if use_kernel:
            fn = _fn("ed25519-kernel", mesh,
                     sh.sharded_verify_tally_packed_kernel)
            mask, power_sums, _bits = fn(jnp.asarray(packed_h),
                                         jnp.asarray(power_limbs))
        else:
            fn = _fn("ed25519-xla", mesh, sh.sharded_verify_tally_packed)
            mask, power_sums, _bits = fn(jnp.asarray(packed_h),
                                         jnp.asarray(power_limbs),
                                         tv.base_table_f32())
        mask = jax.block_until_ready(mask)
        t_mask = time.perf_counter()
        tallied = sh.limb_sums_to_int(power_sums)   # the psum readback
        psum_s = time.perf_counter() - t_mask
        placed = _shard_lanes(mask)
        mask = np.asarray(mask)[:b] & host_ok
    total = time.perf_counter() - t0
    _note_dispatch(ED25519, b, padded, placed, psum_s, total, impl)
    breaker().record_success()
    from tmtpu.libs import metrics as _m

    _m.observe_crypto_batch(ED25519, tv.backend_label(), impl, b,
                            padded, total)
    return mask, tallied


def batch_verify_mesh(curve: str, pks, msgs, sigs,
                      min_lanes: int = 0) -> np.ndarray:
    """Mask-only lane-sharded batch verify for any supported curve —
    bit-exact twin of the single-device batch_verify/batch_verify_sr/
    batch_verify_k1. Raises on failure. ``min_lanes`` as in
    ``tv.batch_verify``: the flush pads as if it held that many."""
    import jax
    import jax.numpy as jnp

    from tmtpu.libs import trace
    from tmtpu.tpu import sharding as sh
    from tmtpu.tpu import verify as tv

    b = len(sigs)
    if b == 0:
        return np.zeros(0, dtype=bool)
    mesh = _get_mesh()
    n = int(mesh.devices.size)
    t0 = time.perf_counter()
    with trace.span("crypto.mesh_verify", curve=curve, lanes=b,
                    shards=n) as sp:
        if curve == ED25519:
            packed, host_ok = tv.prepare_batch_packed(pks, msgs, sigs)
            table = tv.base_table_f32()

            def build(m):
                return sh.sharded_verify_tally_packed(m)
        elif curve == SR25519:
            from tmtpu.tpu import sr_verify as srv

            packed, host_ok = srv.prepare_sr_batch_packed(pks, msgs, sigs)
            table = tv.base_table_f32()
            build = sh.sharded_verify_sr
        elif curve == SECP256K1:
            from tmtpu.tpu import k1_verify as kv

            packed, host_ok = kv.prepare_k1_batch_packed(pks, msgs, sigs)
            table = kv.base_table_f32()
            build = sh.sharded_verify_k1
        else:
            raise ValueError(f"unsupported mesh curve {curve!r}")
        padded = padded_lanes(max(b, min_lanes), n)
        # every mask-only mesh route is the lane-sharded XLA graph
        impl = "mesh-xla"
        sp.set(padded=padded, impl=impl)
        packed_h = tv.pad_packed(packed, padded)
        if curve == ED25519:
            # reuse the fused tally callable with zero powers: one jit
            # cache entry serves both verify and verify_tally flushes
            fn = _fn("ed25519-xla", mesh, build)
            zeros = jnp.zeros((sh.POWER_LIMBS, padded), dtype=jnp.int32)
            mask, _sums, _bits = fn(jnp.asarray(packed_h), zeros, table)
        else:
            fn = _fn(curve, mesh, build)
            mask = fn(jnp.asarray(packed_h), table)
        mask = jax.block_until_ready(mask)
        placed = _shard_lanes(mask)
        mask = np.asarray(mask)[:b] & host_ok
    total = time.perf_counter() - t0
    _note_dispatch(curve, b, padded, placed, 0.0, total, impl)
    breaker().record_success()
    from tmtpu.libs import metrics as _m

    _m.observe_crypto_batch(curve, tv.backend_label(), impl, b,
                            padded, total)
    return mask

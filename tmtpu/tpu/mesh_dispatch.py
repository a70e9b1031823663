"""Mesh-aware dispatch: shard production verify flushes across chips.

This module owns what is the mesh's own: the process-wide device
:class:`~jax.sharding.Mesh`, the sharded callables built for it, the
gate (``route``), the ``crypto.mesh`` breaker, occupancy and
``snapshot()``. The flush itself is ``tpu/dispatch.py``'s, which sends
any flush of at least ``crypto.shard_min_lanes`` lanes across every chip
on the host:

- ed25519 rides the fused verify+tally step with the voting-power
  reduction psum'd ON DEVICE, so the host reads back one packed mask
  plus five int32 limb sums regardless of mesh size;
- sr25519 / secp256k1, and every mask-only ed25519 flush, ride their
  lane-sharded XLA graphs (verification is embarrassingly parallel — no
  collective at all). Only the ed25519 tally step runs the fused Pallas
  kernel under shard_map; the metric label says which one ran
  (``mesh-pallas`` / ``mesh-xla``).

Contract: a sharded flush either returns the EXACT single-device
result or raises. ``dispatch.device_verify`` records a mesh failure
against the ``crypto.mesh`` breaker (never ``crypto.tpu``) and the flush
falls through to the single-device path inside the same dispatch window,
so the degradation ladder is mesh → single-device → CPU-serial with
exact masks at every rung.

Padding: the packed bitarray output shards one uint32 word per
``WORD_LANES`` lanes, so sharded lane counts must be a multiple of
``32 x n_devices`` (the dryrun_multichip quantum; ``dispatch.padded_lanes``
rounds the bucket up to it). Pad lanes replicate lane 0's bytes but carry
ZERO power limbs, so they can never contribute to the tally.

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
from typing import Dict, Optional

from tmtpu.libs import breaker as _bk

# mesh failures get their own failure budget: a broken collective on one
# host must degrade to single-device dispatch WITHOUT opening crypto.tpu
# (the single-device path may be perfectly healthy)
MESH_BREAKER_NAME = "crypto.mesh"

# the packed bitarray a sharded step returns holds this many lanes a
# uint32 word, and every shard must hold whole words
WORD_LANES = 32

_lock = threading.Lock()
# defaults mirror config/config.py CryptoConfig; configure() overwrites
_cfg = {"mesh_devices": 0, "shard_min_lanes": 2048}
_state: Dict = {
    "mesh": None,          # cached jax Mesh
    "mesh_key": None,      # (n, device ids) the cache was built for
    "fns": {},             # (builder, mesh_key) -> jitted sharded callable
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


def get_mesh():
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
        return int(get_mesh().devices.size)
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


def sharded(builder, mesh):
    """``builder(mesh)``, a tpu/sharding.py sharded callable, built once
    a mesh and kept until the mesh changes."""
    key = (builder, _state["mesh_key"])
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


def note_dispatch(curve: str, lanes: int, padded: int, dev_mask,
                  psum_s: float, total_s: float, impl: str) -> None:
    """A sharded flush came back: occupancy as placed (``dev_mask`` is
    the device-side mask), the mesh metric set, the timeline, and a
    success for ``crypto.mesh``."""
    from tmtpu.libs import metrics as _m
    from tmtpu.libs import timeline as _tl

    shard_lanes = _shard_lanes(dev_mask)
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
    breaker().record_success()


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

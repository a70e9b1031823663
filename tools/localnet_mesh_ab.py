"""Mesh-dispatch A/B on the 4-node localnet (ISSUE 6 acceptance): the
same real-TCP kvstore network as tools/localnet_sidecar_ab.py, run twice
with every node on the device verify path (``crypto_backend=tpu``) —
single-device dispatch (``TMTPU_MESH_DEVICES=1``, mesh off) vs every
flush sharded across a 4-device mesh (``TMTPU_MESH_DEVICES=4`` with
``TMTPU_SHARD_MIN_LANES=1`` so consensus-sized flushes qualify).

What the mesh should do here: the SAME flushes ride the sharded
primitives instead of one device — identical masks and tallies (block
rate holds), mesh_dispatches ≈ device flushes in arm B and exactly 0 in
arm A, and the per-chip occupancy spread shows every device carrying an
equal lane share (the padding quantum guarantees equal shards). On this
CPU-forced host the mesh is 4 virtual XLA:CPU devices, so the numbers
prove ROUTING and EXACTNESS, not chip speedup — ``chip_smoke.py`` on a
multi-chip host prints mesh seconds beside single-device seconds.

Prints one JSON line per arm plus a combined summary
(tools/ab_common.py schema):

    {"metric": "localnet_mesh_ab", "single_device": {...},
     "mesh": {...}, "mesh_dispatch_share": ...,
     "block_rate_ratio": ..., "occupancy_lanes": {...}}

Run: python tools/localnet_mesh_ab.py [window_seconds]
"""

import os
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import tests.conftest  # noqa: F401  (forces jax onto 8 CPU devices)

# both arms: device path for every flush (the post-sigcache consensus
# flush is ~8 lanes — below the default device threshold)
os.environ["TMTPU_TPU_MIN_BATCH"] = "1"

from tmtpu.crypto import batch as crypto_batch  # noqa: E402
from tmtpu.libs import breaker as _bk  # noqa: E402
from tmtpu.tpu import mesh_dispatch as md  # noqa: E402
from tools import ab_common  # noqa: E402


def _mk_net_nodes(tmp):
    def configure(cfg, _i):
        cfg.base.crypto_backend = "tpu"

    return ab_common.make_localnet(4, tmp, "mesh-ab-chain",
                                   configure=configure)


def _run_window(nodes, duration_s, reset_counters):
    return ab_common.run_window(nodes, duration_s, reset_counters,
                                prefix=b"mab", warm_timeout_s=120)


def _run_arm(name: str, duration_s: float, mesh_devices: int,
             shard_min_lanes: int) -> dict:
    """One arm: same net, same backend, only the mesh routing knobs
    differ (applied via the call-time env overrides so both in-process
    arms steer the shared mesh_dispatch module cleanly)."""
    os.environ["TMTPU_MESH_DEVICES"] = str(mesh_devices)
    os.environ["TMTPU_SHARD_MIN_LANES"] = str(shard_min_lanes)
    md.reset()
    md.breaker().reset()
    _bk.get(crypto_batch.BREAKER_NAME).reset()

    flushes = [0]
    lanes = [0]
    real = crypto_batch.TPUBatchVerifier._verify_pending

    def counting(self, items, tally):
        flushes[0] += 1
        lanes[0] += len(items)
        return real(self, items, tally)

    crypto_batch.TPUBatchVerifier._verify_pending = counting
    mesh0 = [0]
    tmp = pathlib.Path(tempfile.mkdtemp(prefix=f"mesh-ab-{name}-"))
    nodes = _mk_net_nodes(tmp)
    assert crypto_batch._default_backend == "tpu", \
        "node construction did not select the tpu backend"
    try:
        def reset():
            flushes[0] = 0
            lanes[0] = 0
            mesh0[0] = md.dispatch_count()

        blocks, wall = _run_window(nodes, duration_s, reset)
    finally:
        crypto_batch.TPUBatchVerifier._verify_pending = real
        for nd in nodes:
            nd.stop()
        crypto_batch.set_default_backend("cpu")

    mesh_dispatches = md.dispatch_count() - mesh0[0]
    snap = md.snapshot()
    out = {
        "arm": name,
        "mesh_devices": mesh_devices,
        "shard_min_lanes": shard_min_lanes,
        "window_s": round(wall, 2),
        "blocks": blocks,
        "block_rate_per_min": round(blocks / wall * 60, 1),
        "device_flushes": flushes[0],
        "lanes": lanes[0],
        "lanes_per_block": round(lanes[0] / max(1, blocks), 1),
        "mesh_dispatches": mesh_dispatches,
        "mesh_dispatch_share": round(
            mesh_dispatches / max(1, flushes[0]), 2),
        "occupancy_lanes": snap["occupancy_lanes"],
        "mesh_breaker": snap["breaker"],
    }
    return out


def main(duration_s: float = 20.0):
    report = ab_common.ABReport("localnet_mesh_ab")
    single = report.add_arm(_run_arm("single_device", duration_s,
                                     mesh_devices=1,
                                     shard_min_lanes=1))
    mesh = report.add_arm(_run_arm("mesh", duration_s,
                                   mesh_devices=4,
                                   shard_min_lanes=1))
    occ = [v for v in mesh["occupancy_lanes"].values()]
    return report.finish(
        mesh_dispatch_share=mesh["mesh_dispatch_share"],
        single_arm_mesh_dispatches=single["mesh_dispatches"],
        block_rate_ratio=round(
            mesh["block_rate_per_min"] /
            max(1e-9, single["block_rate_per_min"]), 2),
        occupancy_lanes=mesh["occupancy_lanes"],
        occupancy_balanced=bool(occ and min(occ) == max(occ)),
    )


if __name__ == "__main__":
    main(float(sys.argv[1]) if len(sys.argv) > 1 else 20.0)

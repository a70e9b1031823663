"""Sidecar A/B on the 4-node localnet (ISSUE 5 acceptance): the same
real-TCP kvstore network as tools/localnet_ab.py, run twice — every node
verifying in-process (``crypto_backend=cpu``) vs all four sharing ONE
verification daemon (``crypto_backend=sidecar`` against a single
SidecarServer on a unix socket).

What the sidecar should do here: four per-process verifiers each cut
their own small flushes (one per node per verify site); the shared
daemon coalesces concurrent nodes' lanes into joint dispatches, so
dispatches/block collapses while block rate holds and the mean
requests-per-dispatch rises above 1 — coalescing made visible on a
real network, not a synthetic two-client test. (All four nodes share
this process and multiplex one daemon connection, so the coalescing
unit reported is requests, not distinct client_ids; run the nodes as
separate processes against the same socket to see dispatch_clients>1.)

Prints one JSON line per arm plus a combined summary
(tools/ab_common.py schema):

    {"metric": "localnet_sidecar_ab", "per_process": {...},
     "sidecar": {...}, "dispatch_reduction_pct": ...,
     "mean_requests_per_dispatch": ..., "block_rate_ratio": ...}

Run: python tools/localnet_sidecar_ab.py [window_seconds]
"""

import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import tests.conftest  # noqa: F401  (forces jax onto CPU devices)

from tmtpu.crypto import batch as crypto_batch  # noqa: E402
from tmtpu.libs import breaker as _bk  # noqa: E402
from tmtpu.libs import metrics as _m  # noqa: E402
from tmtpu.sidecar.server import SidecarServer  # noqa: E402
from tools import ab_common  # noqa: E402


def _mk_net_nodes(tmp, backend="cpu", sidecar_addr=""):
    """The shared 4-node net with the crypto backend and the [sidecar]
    address as the A/B variables. Node construction applies both through
    the production path (set_default_backend + configure_sidecar), not a
    monkeypatch."""

    def configure(cfg, _i):
        cfg.base.crypto_backend = backend
        cfg.sidecar.addr = sidecar_addr

    return ab_common.make_localnet(4, tmp, "sidecar-ab-chain",
                                   configure=configure)


def _run_window(nodes, duration_s, reset_counters):
    return ab_common.run_window(nodes, duration_s, reset_counters,
                                prefix=b"sab")


def _run_per_process(duration_s: float) -> dict:
    """Arm A: every node verifies in its own process space — count every
    flush that reaches the CPU backend, the unit a per-process deployment
    pays per verify site per node."""
    flushes = [0]
    lanes = [0]
    real = crypto_batch.CPUBatchVerifier._verify_pending

    def counting(self, items, tally):
        flushes[0] += 1
        lanes[0] += len(items)
        return real(self, items, tally)

    crypto_batch.CPUBatchVerifier._verify_pending = counting
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="sidecar-ab-pp-"))
    nodes = _mk_net_nodes(tmp, backend="cpu")
    try:
        def reset():
            flushes[0] = 0
            lanes[0] = 0

        blocks, wall = _run_window(nodes, duration_s, reset)
    finally:
        crypto_batch.CPUBatchVerifier._verify_pending = real
        for nd in nodes:
            nd.stop()

    out = {
        "arm": "per_process",
        "window_s": round(wall, 2),
        "blocks": blocks,
        "block_rate_per_min": round(blocks / wall * 60, 1),
        "dispatches": flushes[0],
        "lanes": lanes[0],
        "dispatches_per_block": round(flushes[0] / max(1, blocks), 1),
        "lanes_per_block": round(lanes[0] / max(1, blocks), 1),
    }
    return out


def _run_sidecar(duration_s: float) -> dict:
    """Arm B: one shared daemon; all four nodes ship lanes to it. Count
    joint dispatches at the daemon and fallback flushes at the nodes
    (which must stay ~0 — the breaker never opens in a healthy run)."""
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="sidecar-ab-sc-"))
    srv = SidecarServer(f"unix://{tmp}/daemon.sock", backend="cpu",
                        server_id="ab-daemon")
    srv.start()

    # count at the coalescer cut: one _dispatch call = one joint device
    # dispatch carrying len(batch) node requests. All four nodes live in
    # this one process and multiplex one sidecar connection, so
    # requests/dispatch (not distinct client_ids) is the coalescing
    # signal here; a real multi-process deployment would also show
    # dispatch_clients > 1.
    dispatches = [0]
    requests = [0]
    lanes = [0]
    real_dispatch = srv.coalescer._dispatch

    def counting_dispatch(curve, batch):
        dispatches[0] += 1
        requests[0] += len(batch)
        lanes[0] += sum(len(r.items) for r in batch)
        return real_dispatch(curve, batch)

    srv.coalescer._dispatch = counting_dispatch
    fallback0 = [0.0]
    nodes = _mk_net_nodes(tmp, backend="sidecar",
                          sidecar_addr=srv.addr)
    assert crypto_batch._default_backend == "sidecar", \
        "node construction did not select the sidecar backend"
    br = _bk.get(crypto_batch.SIDECAR_BREAKER_NAME)
    br.reset()
    try:
        def reset():
            dispatches[0] = 0
            requests[0] = 0
            lanes[0] = 0
            fallback0[0] = ab_common.counter_value(
                _m.sidecar_client_fallback)

        blocks, wall = _run_window(nodes, duration_s, reset)
    finally:
        for nd in nodes:
            nd.stop()
        srv.coalescer._dispatch = real_dispatch
        srv.stop()
        crypto_batch.set_default_backend("cpu")
        crypto_batch.reset_sidecar_client()
        br.reset()

    fallback = ab_common.counter_value(_m.sidecar_client_fallback) \
        - fallback0[0]
    out = {
        "arm": "sidecar",
        "window_s": round(wall, 2),
        "blocks": blocks,
        "block_rate_per_min": round(blocks / wall * 60, 1),
        "dispatches": dispatches[0],
        "requests_coalesced": requests[0],
        "lanes": lanes[0],
        "dispatches_per_block": round(dispatches[0] / max(1, blocks), 1),
        "lanes_per_block": round(lanes[0] / max(1, blocks), 1),
        "mean_requests_per_dispatch": round(
            requests[0] / max(1, dispatches[0]), 2),
        "fallback_lanes": fallback,
        "breaker_state": br.state,
    }
    return out


def main(duration_s: float = 20.0):
    report = ab_common.ABReport("localnet_sidecar_ab")
    pp = report.add_arm(_run_per_process(duration_s))
    sc = report.add_arm(_run_sidecar(duration_s))
    reduction = 1.0 - (sc["dispatches_per_block"] /
                       max(1e-9, pp["dispatches_per_block"]))
    return report.finish(
        dispatch_reduction_pct=round(reduction * 100, 1),
        mean_requests_per_dispatch=sc["mean_requests_per_dispatch"],
        block_rate_ratio=round(
            sc["block_rate_per_min"] / max(1e-9, pp["block_rate_per_min"]),
            2),
    )


if __name__ == "__main__":
    main(float(sys.argv[1]) if len(sys.argv) > 1 else 20.0)

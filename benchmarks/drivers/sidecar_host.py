#!/usr/bin/env python3
"""The verify daemon of the ``served_tx`` driver, hosted by the benchmark:
the one process that holds the chip, and so the only one that can trace
it. It makes the calls ``tmtpu sidecar`` makes (cmd/__main__.py
cmd_sidecar: load the home's config, ``crypto_batch.configure``,
``start_backend``, ``SidecarServer(...)``, ``start()``, ``warm()``) and
then answers the runner on standard input:

    snapshot      -> the daemon's stats(), its metric registry, compilations
    trace_start   -> profile from now on
    trace_stop    -> stop, reduce the trace (lib/tracered.py), reply with it
    stop          -> drain as on SIGTERM, stop, exit 0

Replies are lines ``@@<word> <json>``. The program has no profiler hook;
one in ``tmtpu sidecar`` would make this file unnecessary (PERF.md).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def reply(word: str, obj) -> None:
    print(f"@@{word} {json.dumps(obj)}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--home", required=True)
    ap.add_argument("--addr", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--no-chip-check", action="store_true",
                    help="the tests' CPU rehearsal only")
    args = ap.parse_args()

    from tmtpu.cmd.__main__ import _load_config
    from tmtpu.crypto import batch as crypto_batch
    from tmtpu.libs import metrics as prog_metrics
    from tmtpu.sidecar.server import SidecarServer

    from benchmarks.lib import devtrace, tracered

    cfg = _load_config(args.home)
    os.makedirs(os.path.join(args.home, "data"), exist_ok=True)
    crypto_batch.configure(cfg.crypto)
    crypto_batch.start_backend(cfg.sidecar.backend, "sidecar")
    device = devtrace.device_facts()
    if not args.no_chip_check and (device["platform"] != "tpu"
                                   or device["count"] < args.chips):
        print(f"sidecar_host: needs {args.chips} TPU chip(s), JAX found "
              f"{device}; not measuring", flush=True)
        return 2
    compiles = devtrace.CompileCount()
    chip_reach_s = time.perf_counter() - T0
    server = SidecarServer(
        args.addr, backend=cfg.sidecar.backend,
        max_queue_lanes=cfg.sidecar.max_queue_lanes,
        max_lanes_per_dispatch=cfg.sidecar.max_lanes_per_dispatch,
        max_frame_bytes=cfg.sidecar.max_frame_bytes,
        request_deadline_s=cfg.sidecar.request_deadline_ns / 1e9,
        health_laddr=cfg.sidecar.health_laddr,
        mesh_devices=cfg.sidecar.mesh_devices,
        shard_min_lanes=cfg.sidecar.shard_min_lanes)
    server.start()
    warm_s = server.warm() if cfg.sidecar.warm_on_start else 0.0
    gc.collect()
    gc.freeze()
    tracer = devtrace.Tracer(emulated=args.no_chip_check)
    reply("ready", {"device": device, "chip_reach_s": chip_reach_s,
                    "warm_s": warm_s, "backend": server.backend_name(),
                    "warmed_shapes": len(server.warmed_shapes)})
    try:
        for line in sys.stdin:
            cmd = line.strip()
            if cmd == "snapshot":
                reply("snapshot", {
                    "stats": server.snapshot(),
                    "registry": prog_metrics.summary(),
                    "compiles": compiles.n,
                    "memory_peak_bytes": devtrace.memory_peak_bytes()})
            elif cmd == "trace_start":
                tracer.start()
                reply("trace_started", {})
            elif cmd == "trace_stop":
                red = tracer.stop()
                reply("trace", {"reduced": red, "breakdown":
                                tracered.breakdown(red) if red else None})
            elif cmd == "stop":
                break
    finally:
        server.drain(timeout=cfg.sidecar.request_deadline_ns / 1e9 + 5.0)
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())

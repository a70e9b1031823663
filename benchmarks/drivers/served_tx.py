"""Driver ``served_tx``: the served path at saturation. One validator
node running the kvstore app with ``crypto_backend = sidecar`` (a child in
which importing jax fails), one verify daemon that owns the chip
(drivers/sidecar_host.py) and the load generator (drivers/loadgen.py), a
closed loop of keep-alive RPC connections. This process starts and stops
them, reads the daemon's and the node's counters as the window opens and
closes, and never imports JAX.

The window opens at the first block boundary after warm traffic has run
for a few blocks and closes at the first boundary after ``--seconds``:
``committed_tx_per_s`` is the txs in the blocks between over (last
boundary - first). ``tx_commit_p99_ms`` is over every valid tx sent in the
window, from its send to the generator's sight of the block that holds it.

``correct`` (reference/kvstore.py): every acknowledged tx is in exactly
one block, a sample reads back through ``abci_query`` as the reference's
final state has it, every tampered envelope is refused at CheckTx and
committed nowhere, the node never fell back from the daemon and the
daemon never left the Pallas kernel on the TPU.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
from urllib.parse import urlparse

from benchmarks.drivers.loadgen import Rpc
from benchmarks.lib import gates, readers
from benchmarks.lib.procs import Child
from benchmarks.lib.report import Checks
from benchmarks.lib.result import RunResult

HERE = os.path.dirname(os.path.abspath(__file__))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _rpc(url: str, method: str, **params):
    conn = Rpc(url, timeout=60.0)
    try:
        return conn.call(method, **params)
    finally:
        conn.close()


def node_argv(home: str, cfg: dict) -> list:
    """The node's command line (the tests put a broken node here)."""
    return [sys.executable, "-m", "tmtpu.cmd", "start", "--home", home,
            "--crypto-backend", "sidecar"] + list(cfg["program"]["node_args"])


def _ask(daemon: Child, cmd: str, word: str, timeout: float) -> dict:
    at = len(daemon.lines)
    daemon.send(cmd)
    line = daemon.wait_for("@@" + word + " ", timeout, after=at)
    if line is None:
        raise SystemExit(f"the daemon host gave no {word} to {cmd!r}")
    return json.loads(line.split(" ", 1)[1])


def run(ctx) -> RunResult:
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    clock = time.perf_counter
    seconds = min(ctx.seconds, float(mix["trace_run_seconds"])) if ctx.trace \
        else ctx.seconds
    work = tempfile.mkdtemp(prefix="bench-kv-")
    home = os.path.join(work, "home")
    sock_path = os.path.join(work, "sidecar.sock")
    # a unix socket's path holds at most 107 bytes; TMPDIR may be deep
    addr = f"unix://{sock_path}" if len(sock_path) < 100 \
        else f"tcp://127.0.0.1:{_free_port()}"
    url = f"http://127.0.0.1:{_free_port()}"
    # the node must not be able to open the chip its daemon owns:
    # importing jax in that process is made to fail outright
    poison = os.path.join(work, "nojax", "jax")
    os.makedirs(poison)
    with open(os.path.join(poison, "__init__.py"), "w") as f:
        f.write('raise ImportError("a crypto_backend=sidecar node must not '
                'import jax: the daemon owns the chip")\n')
    env = dict(os.environ, PYTHONPATH=ctx.root, PYTHONUNBUFFERED="1",
               TMTPU_SIDECAR_ADDR=addr,
               TMTPU_RPC_LADDR="tcp://" + urlparse(url).netloc,
               TMTPU_P2P_LADDR=f"tcp://127.0.0.1:{_free_port()}")
    env.update({k: str(v) for k, v in cfg["program"]["env"].items()})
    node_env = dict(env, PYTHONPATH=os.path.dirname(poison) + os.pathsep
                    + ctx.root)
    tm = [sys.executable, "-m", "tmtpu.cmd"]
    children = []
    try:
        subprocess.run(tm + ["init", "--home", home], env=node_env,
                       cwd=ctx.root, check=True, timeout=120,
                       stdout=sys.stderr)
        host_argv = [sys.executable, os.path.join(HERE, "sidecar_host.py"),
                     "--home", home, "--addr", addr,
                     "--chips", str(ctx.cell.chips)]
        if not ctx.require_chip:
            host_argv.append("--no-chip-check")
        daemon = Child("daemon", host_argv, env, ctx.root, stdin=True)
        children.append(daemon)
        line = daemon.wait_for("@@ready ", 1100.0)
        if line is None:
            # no chip, or the daemon broke: no result line
            raise SystemExit(daemon.finish(10.0) or 1)
        ready = json.loads(line.split(" ", 1)[1])
        device = dict(ready["device"])
        ctx.check_device(device)
        node = Child("node", node_argv(home, cfg), node_env, ctx.root)
        children.append(node)
        if node.wait_for("Node started", 120.0, anywhere=True) is None:
            raise SystemExit("the node did not start")

        job = {"url": url, "seed": ctx.seed, "seconds": seconds,
               "tx_bytes": int(cfg["tx_bytes"]),
               "senders": int(cfg["assumed"]["senders"])}
        job.update({k: v for k, v in mix.items()
                    if k not in ("driver", "note")})
        job_path = os.path.join(work, "job.json")
        with open(job_path, "w") as f:
            json.dump(job, f)

        snaps = {}
        trace_box = {}

        def snapshot(tag: str) -> None:
            snaps[tag] = {"daemon": _ask(daemon, "snapshot", "snapshot", 60.0),
                          "node": _rpc(url, "metrics")["metrics"]}

        def on_event(line: str) -> None:
            # runs on the generator's output thread, the moment it prints
            if line.startswith("EVENT WINDOW_OPEN"):
                snaps["t_open_seen"] = clock()
                snapshot("open")
                if ctx.trace:
                    _ask(daemon, "trace_start", "trace_started", 60.0)
                    stop_at = threading.Timer(
                        float(mix["trace_seconds"]), lambda: trace_box.update(
                            _ask(daemon, "trace_stop", "trace", 240.0)))
                    stop_at.daemon = True
                    stop_at.start()
                    trace_box["timer"] = stop_at
            elif line.startswith("EVENT WINDOW_CLOSE"):
                snapshot("close")

        gen = Child("loadgen", [sys.executable,
                                os.path.join(HERE, "loadgen.py"), job_path],
                    dict(os.environ, PYTHONUNBUFFERED="1"), ctx.root,
                    on_line=on_event)
        children.append(gen)
        line = gen.wait_for("RESULT ", seconds + float(mix["give_up_s"])
                            + float(mix["drain_s"]) + 200.0)
        if line is None:
            raise SystemExit("the load generator gave no result")
        res = json.loads(line.split(" ", 1)[1])
        gen.finish(30.0)
        if "error" in res or "close" not in snaps:
            raise SystemExit(f"the load generator failed: {res}")
        if ctx.trace:
            trace_box["timer"].join(timeout=300.0)
        trace = trace_box.get("reduced")
        final = _ask(daemon, "snapshot", "snapshot", 60.0)
        device["memory_peak_bytes"] = final["memory_peak_bytes"]
    finally:
        codes = {}
        for child in reversed(children):
            if child.name == "daemon" and child.proc.poll() is None:
                try:
                    child.send("stop")
                    codes[child.name] = child.finish(90.0)
                    continue
                except OSError:
                    pass
            codes[child.name] = child.terminate()
        shutil.rmtree(work, ignore_errors=True)

    window_s = res["t_close"] - res["t_open"]
    d0, d1 = snaps["open"]["daemon"], snaps["close"]["daemon"]
    r = readers.Readings(
        clock={"chip_reach_s": ready["chip_reach_s"],
               "warm_s": ready["warm_s"],
               "txs_per_block": res["txs_per_block"],
               "block_interval_s": res["block_interval_s"],
               "loadgen_cpu_pct": res["loadgen_cpu_pct"]},
        counters={
            "program_counter": readers.registry_delta(d1["registry"],
                                                      d0["registry"]),
            "sidecar_stats": readers.stats_delta(d1["stats"], d0["stats"]),
            "node_metrics": readers.registry_delta(snaps["close"]["node"],
                                                   snaps["open"]["node"])},
        trace=trace, window_s=window_s, device_kind=device["kind"])

    checks = Checks()
    for name in ("acked_not_committed", "committed_twice", "readback_wrong",
                 "tampered_accepted", "tampered_committed", "valid_refused",
                 "foreign_txs_committed", "tx_bytes_off_size"):
        checks.at_most(name, res[name], 0)
    checks.at_most("client_errors", len(res["client_errors"])
                   + bool(res["scanner_error"]), 0)
    checks.at_least("readback_sampled", res["readback_sampled"],
                    min(int(mix["readback"]), 1))
    if mix.get("tamper_every"):
        checks.at_least("tampered_sent", res["tampered_sent"], 1)
    checks.at_most("node_sidecar_client_fallback", readers.term_value(
        {"source": "node_metrics", "name": "sidecar_client_fallback_total",
         "field": "value"}, "", r) or 0, 0)
    checks.at_most("compiles_in_window", d1["compiles"] - d0["compiles"], 0)
    total = gates.device_path(checks, r, ctx.require_chip, 1)
    for name, rc in codes.items():
        checks.at_most(f"exit_code_{name}", abs(rc), 0)

    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    e2e = {"committed_tx_per_s": res["committed_in_window"] / window_s,
           "setup_s": res["t_open"] - ctx.t_start}
    if res["latency_p99_s"] is not None:
        e2e["tx_commit_p99_ms"] = 1000.0 * res["latency_p99_s"]
    print(f"served_tx: window {window_s:.3f}s over {res['blocks_in_window']} "
          f"blocks, {res['committed_in_window']} txs; sent "
          f"{res['sent_total']} (signed {res['signed_sent']}, tampered "
          f"{res['tampered_sent']}), at most {res['peak_outstanding']} "
          f"outstanding; daemon dispatches in window "
          f"{total:.0f}; drain {res['drain_s']:.1f}s, reference check "
          f"{res['check_s']:.1f}s, neither in setup_s",
          file=sys.stderr, flush=True)
    print("served_tx: tx/s and p99 ms by window length " + " ".join(
        f"{due}s={rate:.1f}/{1000 * (p99 or 0):.0f}"
        for due, rate, p99 in res["by_length"]), file=sys.stderr, flush=True)
    return RunResult(checks=checks, attempted=res["attempted"],
                     failed=res["failed"], end_to_end=e2e, device=device,
                     readings=r, breakdown=trace_box.get("breakdown"))

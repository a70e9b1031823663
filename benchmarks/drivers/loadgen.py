#!/usr/bin/env python3
"""The load generator of the ``served_tx`` driver: a process of its own
that imports neither JAX nor the program. A closed loop of keep-alive RPC
connections, each sending its next tx when ``broadcast_tx_sync`` returns
(one loop on one thread serves them all); a scanner thread that sees every
block; and, once the window has closed, the
comparison with the plain reference (reference/kvstore.py).

    python3 benchmarks/drivers/loadgen.py <job.json>

Prints ``EVENT <name> <monotonic seconds>`` lines as the window opens and
closes (both on a block boundary) and one ``RESULT <json>`` line.
"""

from __future__ import annotations

import base64
import gc
import json
import math
import os
import random
import re
import selectors
import socket
import sys
import threading
import time
from urllib.parse import urlparse

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.reference import kvstore as ref  # noqa: E402

clock = time.perf_counter      # CLOCK_MONOTONIC: one base for every process


def say(line: str) -> None:
    print(line, flush=True)


class Rpc:
    """One keep-alive JSON-RPC connection over HTTP/1.1, on a plain
    socket: ``http.client`` costs the generator more CPU per request than
    the tx itself, and 48 of them share one interpreter."""

    _LENGTH = re.compile(rb"content-length:[ \t]*(\d+)", re.I)

    def __init__(self, url: str, timeout: float = 120.0):
        u = urlparse(url)
        self.sock = socket.create_connection((u.hostname, u.port),
                                             timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.head = (f"POST / HTTP/1.1\r\nHost: {u.netloc}\r\n"
                     f"Content-Type: application/json\r\n"
                     f"Content-Length: ").encode()
        self.buf = bytearray()
        self.n = 0

    def send(self, method: str, **params) -> None:
        self.n += 1
        body = json.dumps({"jsonrpc": "2.0", "id": self.n,
                           "method": method, "params": params}).encode()
        self.sock.sendall(self.head + b"%d\r\n\r\n" % len(body) + body)

    def feed(self):
        """Take what the socket holds -> the result once the response is
        whole, else None."""
        self._more()
        return self._parse()

    def _parse(self):
        buf = self.buf
        end = buf.find(b"\r\n\r\n")
        if end < 0:
            return None
        m = self._LENGTH.search(buf, 0, end)
        if not buf.startswith(b"HTTP/1.1 200") or m is None:
            raise RuntimeError(f"bad response: {bytes(buf[:end])!r}")
        total = end + 4 + int(m.group(1))
        if len(buf) < total:
            return None
        out = json.loads(bytes(buf[end + 4:total]))
        del buf[:total]
        if out.get("error"):
            raise RuntimeError(f"rpc error: {out['error']}")
        return out["result"]

    def call(self, method: str, **params):
        self.send(method, **params)
        while (out := self._parse()) is None:
            self._more()
        return out

    def _more(self) -> None:
        chunk = self.sock.recv(1 << 18)
        if not chunk:
            raise ConnectionError("the node closed the connection")
        self.buf += chunk

    def close(self) -> None:
        self.sock.close()


class Mix:
    """Which tx a client sends as its k-th: the seed chooses keys and
    bytes, never sizes, kinds or counts."""

    def __init__(self, job: dict):
        self.seed = job["seed"]
        self.size = job["tx_bytes"]
        self.signed = job["signed"]
        self.tamper_every = job.get("tamper_every", 0)
        keys = [ref.sender_key(self.seed, s) for s in range(job["senders"])]
        self.senders = [(k, k.public_key().public_bytes_raw()) for k in keys]

    def tx(self, client: int, k: int, rng: random.Random, signed: bool,
           n_signed: int):
        """-> (tx bytes, kind) with kind 'plain' | 'signed' | 'tampered'."""
        head = b"b%d-%d-%d=" % (self.seed, client, k)
        room = self.size - len(head) - (ref.HEADER if signed else 0)
        payload = head + rng.randbytes(room)
        if not signed:
            return payload, "plain"
        key, pub = self.senders[(client + k) % len(self.senders)]
        tx = ref.envelope(payload, key, pub)
        if self.tamper_every and \
                n_signed % self.tamper_every == self.tamper_every - 1:
            return ref.tamper(tx), "tampered"
        return tx, "signed"


class Client:
    """One closed-loop connection: its next tx goes out when the answer
    to the last one is in. All of them are served by one loop on one
    thread (``run_clients``): 48 threads sharing an interpreter spent
    more CPU handing it to each other than on the txs."""

    def __init__(self, idx: int, url: str, mix: Mix):
        self.idx, self.mix = idx, mix
        self.rpc = Rpc(url)
        self.rpc.call("health")
        self.rng = random.Random(mix.seed * 1009 + idx)
        self.sent = []          # (tx, kind, t_send, t_ack, code)
        self.k = self.n_signed = self.n_valid = 0
        self.flying = None      # (tx, kind, t_send)

    def send_next(self, burst: bool) -> None:
        signed = self.mix.signed or burst
        tx, kind = self.mix.tx(self.idx, self.k, self.rng, signed,
                               self.n_signed)
        self.n_signed += signed
        self.n_valid += kind != "tampered"
        self.k += 1
        self.flying = (tx, kind, clock())
        self.rpc.send("broadcast_tx_sync", tx=base64.b64encode(tx).decode())

    def on_readable(self) -> bool:
        """-> True when the answer is in (and recorded)."""
        res = self.rpc.feed()
        if res is None:
            return False
        self.sent.append(self.flying + (clock(), int(res.get("code", 0))))
        self.flying = None
        return True


def run_clients(clients, state: dict, burst: dict, cap: int) -> None:
    """The closed loop, until ``state['stop']``; then every answer still
    in flight is waited for.

    ``cap`` bounds the valid txs sent and not yet seen in a block: a loop
    closed on CheckTx alone offers more than consensus commits, blocks
    grow with the backlog, and the mempool (5,000 txs) fills within a
    minute — past that the node refuses txs. A client that would pass the
    cap waits for the next block.

    ``burst`` = {"at_s", "per_client"} or None: ``at_s`` after the window
    opened, each client sends ``per_client`` signed txs, all clients
    together, so that one mempool gather holds enough signed lanes to
    reach the device."""
    sel = selectors.DefaultSelector()
    for c in clients:
        sel.register(c.rpc.sock, selectors.EVENT_READ, c)
        c.send_next(False)
    rounds, idle = 0, []        # idle: no request in flight
    while True:
        for key, _ in sel.select(timeout=0.01):
            if key.data.on_readable():
                idle.append(key.data)
        if state["stop"]:
            for c in idle:
                sel.unregister(c.rpc.sock)
            idle = []
            if not sel.get_map():
                break
        elif burst and state["open"] is not None and \
                clock() >= state["open"] + burst["at_s"]:
            rounds, burst = burst["per_client"], None
        elif rounds:
            if len(idle) == len(clients):
                for c in idle:
                    c.send_next(True)
                idle, rounds = [], rounds - 1
        else:
            room = cap - (sum(c.n_valid for c in clients)
                          - state["committed"])
            state["least_room"] = min(state["least_room"], room)
            for _ in range(min(room, len(idle))):
                idle.pop().send_next(False)
        if clock() > state["deadline"]:
            raise TimeoutError("the clients' loop passed its deadline")
    for c in clients:
        c.rpc.close()


class Scanner(threading.Thread):
    """Sees every block from ``start`` on: (height, t_seen, [txs])."""

    def __init__(self, url: str, poll_s: float):
        super().__init__(daemon=True, name="scanner")
        self.rpc = Rpc(url)
        self.poll_s = poll_s
        self.start_height = int(self.rpc.call("status")["sync_info"][
            "latest_block_height"])
        self.blocks = []
        self.on_block = None
        self.stop = False
        self.error = None

    def run(self) -> None:
        scanned = self.start_height
        try:
            while not self.stop:
                latest = int(self.rpc.call("status")["sync_info"][
                    "latest_block_height"])
                t_seen = clock()
                for h in range(scanned + 1, latest + 1):
                    raw = self.rpc.call("block", height=str(h))[
                        "block"]["data"]["txs"] or []
                    blk = (h, t_seen, [base64.b64decode(t) for t in raw])
                    self.blocks.append(blk)
                    if self.on_block:
                        self.on_block(blk)
                scanned = latest
                time.sleep(self.poll_s)
        except Exception as e:  # noqa: BLE001
            self.error = repr(e)


def p_rank(sorted_vals, q: float) -> float:
    """Nearest-rank percentile."""
    return sorted_vals[max(0, math.ceil(q * len(sorted_vals) / 100) - 1)]


def main(job_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    url = job["url"]
    mix = Mix(job)
    n = job["clients"]
    # connections opened one at a time: the server's listen backlog is small
    clients = [Client(i, url, mix) for i in range(n)]
    scanner = Scanner(url, job["poll_s"])
    seconds, warm_blocks = job["seconds"], job["warm_blocks"]
    win = {"open": None, "close": None, "full": 0, "stop": False,
           "committed": 0, "least_room": int(job["max_outstanding"]),
           "deadline": clock() + job["give_up_s"] + seconds}

    def on_block(blk) -> None:
        _h, t_seen, txs = blk
        win["committed"] += len(txs)
        if win["open"] is None:
            win["full"] += bool(txs)
            if win["full"] > warm_blocks:
                win["open"] = t_seen
                win["cpu0"] = os.times()
                say(f"EVENT WINDOW_OPEN {t_seen}")
        elif win["close"] is None and t_seen - win["open"] >= seconds:
            win["close"] = t_seen
            win["cpu1"] = os.times()
            win["stop"] = True
            say(f"EVENT WINDOW_CLOSE {t_seen}")

    scanner.on_block = on_block
    gc.collect()
    gc.freeze()
    scanner.start()
    client_error = None
    try:
        run_clients(clients, win, job.get("signed_burst"),
                    int(job["max_outstanding"]))
    except Exception as e:  # noqa: BLE001 — reported in the result
        client_error = repr(e)
    if win["close"] is None:
        say("RESULT " + json.dumps({
            "error": f"no window: open={win['open']} clients="
                     f"{client_error} scanner={scanner.error}"}))
        return 1

    # drain: every acknowledged valid tx has to show in a block
    sent = [s for c in clients for s in c.sent]
    acked = {s[0] for s in sent if s[4] == 0 and s[1] != "tampered"}
    t_drain = clock() + job["drain_s"]
    while clock() < t_drain and scanner.error is None:
        seen = {tx for _h, _t, txs in scanner.blocks for tx in txs}
        if acked <= seen:
            break
        time.sleep(0.25)
    scanner.stop = True
    scanner.join(timeout=30.0)
    t_end = clock()

    blocks = scanner.blocks
    committed = [tx for _h, _t, txs in blocks for tx in txs]
    t_of = {}
    for _h, t_seen, txs in blocks:
        for tx in txs:
            t_of.setdefault(tx, t_seen)
    missing, dup = ref.exactly_once(acked, committed)
    ours = {s[0] for s in sent}
    t_open, t_close = win["open"], win["close"]
    in_window = [b for b in blocks if t_open < b[1] <= t_close]
    lat, failed = [], 0
    attempted = 0
    for tx, kind, t_send, _t_ack, code in sent:
        if kind == "tampered" or not t_open <= t_send < t_close:
            continue
        attempted += 1
        if code == 0 and tx in t_of:
            lat.append(t_of[tx] - t_send)
        else:       # refused though valid, or never committed: the worst
            failed += 1
            lat.append(t_end - t_send)
    lat.sort()
    tampered = [s for s in sent if s[1] == "tampered"]
    # what a shorter window of the same run would have read: it closes at
    # the first block seen 10, 20, ... seconds in
    by_length, due = [], 10
    for _h, t_b, _txs in in_window[:-1]:
        if t_b - t_open < due:
            continue
        ls = sorted(t_of[s[0]] - s[2] for s in sent if s[1] != "tampered"
                    and t_open <= s[2] < t_b and s[4] == 0 and s[0] in t_of)
        by_length.append([due, sum(len(b[2]) for b in in_window
                                   if b[1] <= t_b) / (t_b - t_open),
                          p_rank(ls, 99) if ls else None])
        due += 10

    # read a sample back and compare with the reference's final state
    state_ref = ref.final_state(committed)
    rng = random.Random(mix.seed ^ 0xBAC)
    keys = sorted({ref.split(tx)[0] for tx in acked if tx in t_of})
    sample = rng.sample(keys, min(len(keys), job["readback"]))
    rpc = Rpc(url)
    wrong = 0
    for k in sample:
        got = rpc.call("abci_query", path="", data="0x" + k.hex(),
                       height="0", prove=False)["response"]
        wrong += base64.b64decode(got.get("value") or "") != state_ref[k]
    rpc.close()

    cpu0, cpu1 = win["cpu0"], win["cpu1"]
    seen_t = [b[1] for b in blocks if t_open <= b[1] <= t_close]
    say("RESULT " + json.dumps({
        "t_open": t_open, "t_close": t_close,
        "blocks_in_window": len(in_window),
        "committed_in_window": sum(len(b[2]) for b in in_window),
        "txs_per_block": [len(b[2]) for b in in_window],
        "block_interval_s": [b - a for a, b in zip(seen_t, seen_t[1:])],
        "attempted": attempted, "failed": failed,
        "latency_p50_s": p_rank(lat, 50) if lat else None,
        "latency_p99_s": p_rank(lat, 99) if lat else None,
        "latency_max_s": lat[-1] if lat else None,
        "by_length": by_length,
        "sent_total": len(sent), "acked_total": len(acked),
        "acked_not_committed": missing, "committed_twice": dup,
        "foreign_txs_committed": sum(1 for tx in committed if tx not in ours),
        "tampered_sent": len(tampered),
        "tampered_accepted": sum(1 for s in tampered if s[4] == 0),
        "tampered_committed": sum(1 for s in tampered if s[0] in t_of),
        "valid_refused": sum(1 for s in sent
                             if s[1] != "tampered" and s[4] != 0),
        "signed_sent": sum(1 for s in sent if s[1] != "plain"),
        "peak_outstanding": int(job["max_outstanding"]) - win["least_room"],
        "tx_bytes_off_size": sum(1 for s in sent
                                 if len(s[0]) != job["tx_bytes"]),
        "readback_sampled": len(sample), "readback_wrong": wrong,
        "client_errors": [client_error] if client_error else [],
        "scanner_error": scanner.error,
        "drain_s": t_end - t_close, "check_s": clock() - t_end,
        "loadgen_cpu_pct": 100.0 * ((cpu1.user + cpu1.system) -
                                    (cpu0.user + cpu0.system))
        / (t_close - t_open),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

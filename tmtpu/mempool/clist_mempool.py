"""Mempool v0 — FIFO with tx cache (reference: mempool/v0/clist_mempool.go).

CheckTx goes through the mempool ABCI connection; committed txs are removed
and the remainder re-checked on update (:435), exactly the reference's
lifecycle. Storage is the wait-chan concurrent list (``libs/clist.py``), exactly the
reference's core structure: broadcast routines hold a CElement cursor and
block on ``next_wait`` — no rescans, no mempool-lock contention with
CheckTx/reap on the hot path. A hash→element map provides O(1) dedup and
removal.

Throughput tier: admission is BATCHED. Concurrent ``check_tx`` calls
gather for a bounded window on a dedicated worker, signed-tx envelopes
(``mempool/signed_tx.py``) verify as ONE ``crypto/batch.py`` flush
(sigcache-fronted, breaker-protected, sidecar/mesh-capable), and the
surviving ABCI CheckTx round trips are pipelined through
``check_tx_batch_async`` + one flush instead of one synchronous round
trip per tx. ``check_tx_nowait`` is the enqueue-and-return surface the
p2p reactor uses so recv-side admission never blocks on the window.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import OrderedDict
from typing import Callable, List, Optional

from tmtpu.abci import types as abci
from tmtpu.crypto import tmhash
from tmtpu.libs import trace, txlat
from tmtpu.libs.clist import CElement, CList


class TxInMempoolError(Exception):
    pass


class MempoolFullError(Exception):
    pass


class TxCache:
    """LRU of tx hashes (mempool/cache.go)."""

    def __init__(self, size: int):
        self.size = size
        self._map: "OrderedDict[bytes, None]" = OrderedDict()
        self._lock = threading.Lock()

    def push(self, tx: bytes) -> bool:
        key = tmhash.sum(tx)
        with self._lock:
            if key in self._map:
                self._map.move_to_end(key)
                return False
            self._map[key] = None
            if len(self._map) > self.size:
                self._map.popitem(last=False)
            return True

    def remove(self, tx: bytes) -> None:
        with self._lock:
            self._map.pop(tmhash.sum(tx), None)


def pipelined_check_tx(proxy_app, reqs: List[abci.RequestCheckTx]
                       ) -> List[abci.ResponseCheckTx]:
    """N CheckTx round trips as one pipelined burst: enqueue every
    request, flush once, wait. Clients without the batch surface (e.g.
    gRPC) fall back to serial sync calls."""
    if not reqs:
        return []
    batch = getattr(proxy_app, "check_tx_batch_async", None)
    if batch is None:
        return [proxy_app.check_tx_sync(r) for r in reqs]
    reqres = batch(reqs)
    proxy_app.flush_sync()
    out = []
    for rr in reqres:
        res = rr.wait(timeout=60.0).check_tx
        if res is None:
            from tmtpu.abci.client import ClientError

            raise ClientError("CheckTx response missing (app conn failed)")
        out.append(res)
    return out


class AsyncRecheckMixin:
    """Shared async-recheck machinery (clist_mempool.go:435 recheckTxs
    fires async CheckTx requests — a synchronous loop would hold the
    consensus thread for mempool-size ABCI round-trips per commit).
    Subclasses implement ``_recheck_pass()``. The running/dirty flags are
    decided under one mutex so a scheduling racing a worker's exit can't
    be lost."""

    def _init_recheck(self) -> None:
        self._recheck_dirty = False
        self._recheck_running = False
        self._recheck_mtx = threading.Lock()

    def _schedule_recheck(self) -> None:
        with self._recheck_mtx:
            self._recheck_dirty = True
            if self._recheck_running:
                return
            self._recheck_running = True
        threading.Thread(target=self._recheck_worker, daemon=True,
                         name="mempool-recheck").start()

    def _recheck_worker(self) -> None:
        while True:
            with self._recheck_mtx:
                if not self._recheck_dirty:
                    self._recheck_running = False
                    return
                self._recheck_dirty = False
            try:
                self._recheck_pass()
            except Exception:
                with self._recheck_mtx:
                    self._recheck_running = False
                return  # app conn gone (shutdown)
            from tmtpu.libs import metrics as _m

            _m.mempool_size.set(self.size())

    def _recheck_pass(self) -> None:
        raise NotImplementedError


class _AdmitEntry:
    __slots__ = ("tx", "tx_info", "cb", "done", "result", "error",
                 "sig_failed")

    def __init__(self, tx: bytes, tx_info: dict, cb: Optional[Callable]):
        self.tx = tx
        self.tx_info = tx_info
        self.cb = cb
        self.done = threading.Event()
        self.result: Optional[abci.ResponseCheckTx] = None
        self.error: Optional[BaseException] = None
        self.sig_failed = False


class BatchCheckMixin:
    """Gather-window batched admission shared by both mempool versions.

    Subclasses provide ``_precheck_admit(tx)`` (synchronous full/dup/
    pre_check screens — these raise on the caller's thread, exactly the
    legacy contract) and ``_apply_check_tx_result(tx, res, tx_info)``
    (mempool bookkeeping for one resolved CheckTx). The worker is lazy:
    no thread exists until the first batched check_tx, and it retires
    after ~30s idle so short-lived test mempools don't leak pollers."""

    def _init_batch_check(self, batch_check: bool, gather_wait_s: float,
                          max_batch: int, verify_signatures: bool) -> None:
        self.batch_check = bool(batch_check)
        self.verify_signatures = bool(verify_signatures)
        self._gather_wait_s = max(0.0, float(gather_wait_s))
        self._batch_max_txs = max(1, int(max_batch))
        self._admit_q: "queue.Queue[_AdmitEntry]" = queue.Queue()
        self._admit_running = False
        self._admit_mtx = threading.Lock()
        # keys of recently committed txs: an admission that was in flight
        # (gather window, ABCI queue) when its tx committed must NOT be
        # inserted afterwards — the tx is in a block, and resurrecting it
        # gets it proposed (and applied) a second time. The tx cache alone
        # can't tell "seen because admission started" from "seen because
        # committed", so update() records commits here and the insert
        # paths drop late arrivals. Bounded LRU, caller holds self._lock.
        self._committed_keys: "OrderedDict[bytes, None]" = OrderedDict()
        self._committed_cap = 16384

    # -- public admission surface -------------------------------------------

    def check_tx(self, tx: bytes, cb: Optional[Callable] = None,
                 tx_info: Optional[dict] = None) -> None:
        """Admit one tx, blocking until its CheckTx verdict is applied
        (the RPC/broadcast surface). Raises dup/full/pre-check errors
        synchronously, like the reference."""
        tx = bytes(tx)
        self._precheck_admit(tx)
        if not self.batch_check:
            if self.verify_signatures and not self._verify_tx_signature(tx):
                from tmtpu.libs import metrics as _m

                _m.mempool_sig_rejects.inc()
                res = abci.ResponseCheckTx(code=1, log="invalid signature")
                self._apply_check_tx_result(tx, res, tx_info or {})
                if cb is not None:
                    cb(res)
                return
            res = self.proxy_app.check_tx_sync(abci.RequestCheckTx(
                tx=tx, type=abci.CHECK_TX_TYPE_NEW))
            self._apply_check_tx_result(tx, res, tx_info or {})
            if cb is not None:
                cb(res)
            return
        entry = _AdmitEntry(tx, tx_info or {}, cb)
        self._enqueue_admit(entry)
        if not entry.done.wait(timeout=60.0):
            from tmtpu.abci.client import ClientError

            raise ClientError("batched CheckTx timed out")
        if entry.error is not None:
            raise entry.error

    def check_tx_nowait(self, tx: bytes, cb: Optional[Callable] = None,
                        tx_info: Optional[dict] = None) -> None:
        """Enqueue-and-return admission for recv threads: the cheap
        synchronous screens (dup/full/pre-check) still raise here, but
        the ABCI round trip and any signature verification happen on the
        gather worker — the caller NEVER blocks on the gather window or
        the app conn."""
        tx = bytes(tx)
        self._precheck_admit(tx)
        self._enqueue_admit(_AdmitEntry(tx, tx_info or {}, cb))

    def _note_committed(self, key: bytes) -> None:
        self._committed_keys[key] = None
        self._committed_keys.move_to_end(key)
        while len(self._committed_keys) > self._committed_cap:
            self._committed_keys.popitem(last=False)

    def _already_committed(self, key: bytes) -> bool:
        return key in self._committed_keys

    def _verify_tx_signature(self, tx: bytes) -> bool:
        """Per-tx (unbatched) envelope screen for the legacy sync path —
        the signature contract must hold whether or not batching is on;
        only the cost profile may differ (one lane per tx here vs one
        flush per gather on the worker)."""
        from tmtpu.crypto import batch as _crypto_batch
        from tmtpu.mempool import signed_tx as _stx

        if not _stx.is_signed(tx):
            return True
        parsed = _stx.parse(tx)
        if parsed is None:
            return False
        pub, sig, payload = parsed
        return _crypto_batch.verify_one(pub, _stx.sign_bytes(payload), sig)

    # -- gather worker -------------------------------------------------------

    def _enqueue_admit(self, entry: _AdmitEntry) -> None:
        # gather-window wait starts here; the "flush" stamp closes it
        txlat.stamp_tx(entry.tx, "admit_enq")
        self._admit_q.put(entry)
        with self._admit_mtx:
            if not self._admit_running:
                self._admit_running = True
                threading.Thread(target=self._admit_worker, daemon=True,
                                 name="mempool-batch-check").start()

    def _admit_worker(self) -> None:
        idle_deadline = time.monotonic() + 30.0
        while True:
            try:
                first = self._admit_q.get(timeout=0.5)
            except queue.Empty:
                if time.monotonic() >= idle_deadline:
                    with self._admit_mtx:
                        if self._admit_q.empty():
                            self._admit_running = False
                            return
                continue
            idle_deadline = time.monotonic() + 30.0
            batch = [first]
            if self.batch_check:
                self._gather(batch)
            try:
                self._process_admit_batch(batch)
            except Exception as e:  # app conn gone / client error
                for en in batch:
                    if not en.done.is_set():
                        if en.error is None and en.result is None:
                            en.error = e
                        en.done.set()

    def _gather(self, batch: List[_AdmitEntry]) -> None:
        """Linger a bounded few ms so concurrent submitters share one
        signature flush and one pipelined ABCI burst. The adaptive
        crypto scheduler can extend the configured floor when device
        rate×RTT data says fuller flushes amortize better (it reports
        0.0 on CPU-only nodes, keeping the config window exact)."""
        from tmtpu.crypto import batch as _crypto_batch

        wait = max(self._gather_wait_s,
                   _crypto_batch.SCHEDULER.gather_wait_s(len(batch)))
        deadline = time.monotonic() + wait
        while len(batch) < self._batch_max_txs:
            left = deadline - time.monotonic()
            if left <= 0:
                try:
                    batch.append(self._admit_q.get_nowait())
                except queue.Empty:
                    break
                continue
            try:
                batch.append(self._admit_q.get(timeout=left))
            except queue.Empty:
                break

    def _process_admit_batch(self, batch: List[_AdmitEntry]) -> None:
        # 1) signature screen: every signed-tx envelope in the gather
        #    resolves through ONE batch-verifier flush — sigcache hits
        #    cost no lane, duplicates collapse, breakers guard the
        #    device path — and failures never reach the app at all
        if self.verify_signatures:
            from tmtpu.mempool import signed_tx as _stx

            lanes: List[_AdmitEntry] = []
            verifier = None
            with trace.span("mempool.screen", txs=len(batch)):
                for en in batch:
                    if not _stx.is_signed(en.tx):
                        continue
                    parsed = _stx.parse(en.tx)
                    if parsed is None:
                        en.sig_failed = True
                        continue
                    pub, sig, payload = parsed
                    if verifier is None:
                        from tmtpu.crypto import batch as _crypto_batch

                        verifier = _crypto_batch.new_batch_verifier()
                    verifier.add(pub, _stx.sign_bytes(payload), sig)
                    lanes.append(en)
            if lanes:
                # the node's wait on the daemon (or the in-process flush)
                with trace.span("mempool.verify", lanes=len(lanes)):
                    _ok, mask = verifier.verify()
                for en, ok in zip(lanes, mask):
                    if not ok:
                        en.sig_failed = True
        with trace.span("mempool.check_tx", txs=len(batch)):
            self._check_tx_batch(batch)

    def _check_tx_batch(self, batch: List[_AdmitEntry]) -> None:
        from tmtpu.libs import metrics as _m

        survivors: List[_AdmitEntry] = []
        for en in batch:
            if en.sig_failed:
                _m.mempool_sig_rejects.inc()
                self._finish_admit(en, abci.ResponseCheckTx(
                    code=1, log="invalid signature"))
            else:
                survivors.append(en)
        if not survivors:
            return
        # 2) pipelined ABCI: enqueue all CheckTx requests, one flush
        _m.mempool_batch_flushes.inc()
        _m.mempool_batch_txs.inc(len(survivors))
        if txlat.enabled():
            for en in survivors:
                txlat.stamp_tx(en.tx, "flush")
        responses = pipelined_check_tx(self.proxy_app, [
            abci.RequestCheckTx(tx=en.tx, type=abci.CHECK_TX_TYPE_NEW)
            for en in survivors])
        for en, res in zip(survivors, responses):
            self._finish_admit(en, res)

    def _finish_admit(self, en: _AdmitEntry,
                      res: abci.ResponseCheckTx) -> None:
        try:
            self._apply_check_tx_result(en.tx, res, en.tx_info)
        except Exception as e:  # e.g. v1 eviction failure
            en.error = e
            en.done.set()
            return
        en.result = res
        if en.cb is not None:
            try:
                en.cb(res)
            except Exception:
                pass  # a callback error must not poison the batch
        en.done.set()

    # -- subclass hooks ------------------------------------------------------

    def _precheck_admit(self, tx: bytes) -> None:
        raise NotImplementedError

    def _apply_check_tx_result(self, tx: bytes, res: abci.ResponseCheckTx,
                               tx_info: dict) -> None:
        raise NotImplementedError


class CListMempool(BatchCheckMixin, AsyncRecheckMixin):
    def __init__(self, proxy_app, max_txs: int = 5000,
                 max_txs_bytes: int = 1 << 30, cache_size: int = 10000,
                 keep_invalid_txs_in_cache: bool = False,
                 pre_check: Optional[Callable] = None,
                 batch_check: bool = True,
                 batch_gather_wait_s: float = 0.002,
                 batch_max_txs: int = 256,
                 verify_signatures: bool = True):
        self.proxy_app = proxy_app
        self.max_txs = max_txs
        self.max_txs_bytes = max_txs_bytes
        self.keep_invalid_txs_in_cache = keep_invalid_txs_in_cache
        self.pre_check = pre_check
        self.cache = TxCache(cache_size)
        self._list = CList()  # of info dicts, FIFO
        self._txs: "OrderedDict[bytes, CElement]" = OrderedDict()
        self._txs_bytes = 0
        self._init_recheck()
        self._init_batch_check(batch_check, batch_gather_wait_s,
                               batch_max_txs, verify_signatures)
        self._height = 0
        self._lock = threading.RLock()
        self._update_lock = threading.RLock()  # Lock()/Unlock() surface
        self._notify: List[Callable] = []

    # -- Mempool interface (mempool/mempool.go:30) --------------------------
    # check_tx / check_tx_nowait provided by BatchCheckMixin.

    @property
    def height(self) -> int:
        """Last height this mempool was updated against (0 pre-genesis);
        the gossip reactor tags tx batches with height+1's trace."""
        return self._height

    def _precheck_admit(self, tx: bytes) -> None:
        with self._lock:
            if len(self._txs) >= self.max_txs or \
                    self._txs_bytes + len(tx) > self.max_txs_bytes:
                raise MempoolFullError(
                    f"mempool is full: {len(self._txs)} txs")
            if not self.cache.push(tx):
                raise TxInMempoolError("tx already exists in cache")
        if self.pre_check is not None:
            err = self.pre_check(tx)
            if err is not None:
                self.cache.remove(tx)
                raise ValueError(f"pre-check failed: {err}")

    def _apply_check_tx_result(self, tx: bytes, res: abci.ResponseCheckTx,
                               tx_info: dict) -> None:
        key = tmhash.sum(tx)
        added = False
        with self._lock:
            if res.is_ok():
                if key not in self._txs and not self._already_committed(key):
                    info = {
                        "tx": tx, "hash": key, "gas_wanted": res.gas_wanted,
                        "height": self._height,
                        "senders": set(filter(None, [tx_info.get("sender")])),
                    }
                    self._txs[key] = self._list.push_back(info)
                    self._txs_bytes += len(tx)
                    added = True
                    txlat.stamp(key, "admit")
            else:
                if not self.keep_invalid_txs_in_cache:
                    self.cache.remove(tx)
        if added:
            # callbacks run OUTSIDE self._lock: a txs-available listener
            # that re-enters the mempool (or grabs its own lock) must not
            # nest under the admission lock
            for fn in self._notify:
                fn()
        from tmtpu.libs import metrics as _m

        _m.mempool_size.set(self.size())

    def reap_max_bytes_max_gas(self, max_bytes: int, max_gas: int
                               ) -> List[bytes]:
        with self._lock:
            out, total_b, total_g = [], 0, 0
            for info in self._list:
                # amino/proto overhead bound per tx, as the reference reaps
                nb = total_b + len(info["tx"]) + 20
                ng = total_g + max(info["gas_wanted"], 0)
                if max_bytes > -1 and nb > max_bytes:
                    break
                if max_gas > -1 and ng > max_gas:
                    break
                total_b, total_g = nb, ng
                out.append(info["tx"])
            return out

    def reap_max_txs(self, n: int) -> List[bytes]:
        with self._lock:
            txs = [i["tx"] for i in self._list]
            return txs if n < 0 else txs[:n]

    def front(self) -> Optional[CElement]:
        """Front element for cursor-based gossip (TxsFront)."""
        return self._list.front()

    def wait_front(self, timeout: float | None = None) -> Optional[CElement]:
        """Block until the mempool is non-empty (TxsWaitChan)."""
        return self._list.wait_chan(timeout)

    def lock(self) -> None:
        self._update_lock.acquire()

    def unlock(self) -> None:
        self._update_lock.release()

    def update(self, height: int, txs: List[bytes], deliver_tx_responses
               ) -> None:
        """Remove committed txs; recheck the rest (clist_mempool.go:435).
        Caller must hold lock()."""
        with self._lock:
            self._height = height
            for tx, res in zip(txs, deliver_tx_responses):
                key = tmhash.sum(tx)
                if res.is_ok():
                    self.cache.push(tx)  # committed: keep in cache forever-ish
                    self._note_committed(key)
                elif not self.keep_invalid_txs_in_cache:
                    self.cache.remove(tx)
                el = self._txs.pop(key, None)
                if el is not None:
                    self._list.remove(el)
                    self._txs_bytes -= len(el.value["tx"])
        # recheck runs on a background worker (clist_mempool.go:435
        # recheckTxs fires ASYNC CheckTx requests): a synchronous loop here
        # would hold the consensus thread — and the shared app mutex — for
        # mempool-size ABCI round-trips per commit, which under tx load
        # starves vote/proposal processing and livelocks rounds
        self._schedule_recheck()
        from tmtpu.libs import metrics as _m

        _m.mempool_size.set(self.size())

    def _recheck_pass(self) -> None:
        """Re-validate survivors as ONE pipelined async batch (N queued
        requests + one flush) instead of N serial sync round trips — at
        5k txs the serial loop held the shared app mutex for the whole
        sweep and starved CheckTx admission."""
        with self._lock:
            remaining = [i["tx"] for i in self._list]
        if not remaining:
            return
        responses = pipelined_check_tx(self.proxy_app, [
            abci.RequestCheckTx(tx=tx, type=abci.CHECK_TX_TYPE_RECHECK)
            for tx in remaining])
        for tx, res in zip(remaining, responses):
            if not res.is_ok():
                with self._lock:
                    el = self._txs.pop(tmhash.sum(tx), None)
                    if el is not None:
                        self._list.remove(el)
                        self._txs_bytes -= len(el.value["tx"])
                if not self.keep_invalid_txs_in_cache:
                    self.cache.remove(tx)

    def flush(self) -> None:
        with self._lock:
            for el in list(self._txs.values()):
                self._list.remove(el)
            self._txs.clear()
            self._txs_bytes = 0
        from tmtpu.libs import metrics as _m

        _m.mempool_size.set(0)

    def flush_app_conn(self) -> None:
        self.proxy_app.flush_sync()

    def size(self) -> int:
        with self._lock:
            return len(self._txs)

    def size_bytes(self) -> int:
        with self._lock:
            return self._txs_bytes

    def is_empty(self) -> bool:
        return self.size() == 0

    def txs_available(self, fn: Callable) -> None:
        """Register a new-tx notification (EnableTxsAvailable analogue)."""
        self._notify.append(fn)

    def mark_sender(self, tx: bytes, sender) -> None:
        with self._lock:
            el = self._txs.get(tmhash.sum(tx))
            if el is not None:
                el.value["senders"].add(sender)

    def senders(self, tx: bytes) -> set:
        with self._lock:
            el = self._txs.get(tmhash.sum(tx))
            return set(el.value["senders"]) if el else set()

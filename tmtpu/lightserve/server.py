"""The lightserve daemon: commit-proof serving for many light clients.

One daemon process terminates light-client sessions for one chain. It
maintains its own verified spine — a :class:`~tmtpu.light.store.LightStore`
anchored at social-consensus :class:`~tmtpu.light.client.TrustOptions`,
fed by a :class:`~tmtpu.light.provider.Provider` (a full node's RPC in
production) — plus the :class:`~tmtpu.lightserve.cache.VerifiedFactCache`
of everything it has ever proven.

Request path, cheapest first:

1. **Cache hit** — the target height's fact is cached and inside the
   trusting period: answered INLINE on the connection thread (no
   coalescer, no reply pool), hop chain cut from parent pointers.
   This is the path that must hold at 10k+ concurrent sessions.
2. **Joint resolve** — cold target: the session queues in the
   height-keyed :class:`~tmtpu.lightserve.coalescer.SyncCoalescer`;
   one bisection resolve (the verifier's skipping algorithm, every hop
   a batched commit verify) serves every session waiting on that
   height, and each verified pivot becomes a cached fact.
3. **Expired target** — the fact's trusting period lapsed: the cache
   refuses it, and the resolve re-verifies the height by hash-linking
   backwards from the nearest still-fresh header
   (:func:`~tmtpu.light.verifier.verify_backwards`). The re-verified
   fact is NOT re-cached — it is expired by definition and would only
   be refused again — so each request for a lapsed height pays its own
   re-verification.

Trust expiry is judged on the SERVER clock, always. A client's
``SyncRequest.now_ns`` is only checked against the server clock
(rejected ``bad_request`` past ``max_client_skew_ns``) — it is never
used for cache reads/evictions or joint resolves, because the shared
cache and every coalesced peer would otherwise be at the mercy of one
unauthenticated client's clock. Cold sessions are answered by a small
fixed reply pool fed by coalescer completion hooks, not by
per-session threads.

The socket side, ``Ping``/``StatsRequest`` and the health listener are
:class:`tmtpu.libs.framed.server.FramedServer`'s; here ``/healthz`` is
``libs.watchdog.lightserve_check``'s verdict (cache hit-rate floor +
session backlog ceiling).

Run it: ``python -m tmtpu.cmd lightserve --addr tcp://127.0.0.1:26680
--upstream http://127.0.0.1:26657 --chain-id ... --trust-height 1
--trust-hash <hex>``.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from tmtpu.libs import metrics as _m
from tmtpu.libs.framed.gather import failure_status
from tmtpu.libs.framed.server import FramedServer
from tmtpu.light import provider as prov
from tmtpu.light import verifier
from tmtpu.light.client import DEFAULT_MAX_CLOCK_DRIFT_NS, TrustOptions
from tmtpu.light.store import LightStore
from tmtpu.light.verifier import ErrNewValSetCantBeTrusted
from tmtpu.lightserve import protocol as proto
from tmtpu.lightserve.cache import Fact, VerifiedFactCache
from tmtpu.lightserve.coalescer import (
    Overloaded,
    PendingSync,
    SyncCoalescer,
)
from tmtpu.types.light_block import LightBlock

# client.go:40 verifySkipping pivot — mirrored from light/client.py
_PIVOT_NUM, _PIVOT_DEN = 1, 2


class Resolution:
    """Outcome of one joint target-height resolve."""

    __slots__ = ("status", "error", "dispatches", "fact", "now_ns",
                 "cache_hit", "hops_override")

    def __init__(self, status: int, dispatches: int = 0,
                 fact: Optional[Fact] = None, now_ns: int = 0,
                 cache_hit: bool = False, error: str = "",
                 hops_override: Optional[List[Fact]] = None):
        self.status = status
        self.dispatches = dispatches
        self.fact = fact
        self.now_ns = now_ns
        self.cache_hit = cache_hit
        self.error = error
        # backwards re-verification builds its chain outside the fact
        # cache (expired facts are never re-cached)
        self.hops_override = hops_override


class _ReplyPool:
    """Bounded pool of reply senders for cold sessions.

    Cold sessions complete on the coalescer thread, which must never
    block on a slow client socket; per-session reply threads (the old
    shape) explode at high cold-session volume and die in
    ``Thread.start``. Instead the coalescer's ``on_done`` hook enqueues
    the finished session here and a FIXED set of workers drains the
    queue. The queue itself needs no cap: each admitted session
    enqueues at most one job, and admission is already bounded by the
    coalescer's ``max_queue_sessions``."""

    def __init__(self, workers: int):
        self.workers = workers
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._threads: List[threading.Thread] = []

    def start(self) -> None:
        if self._threads:
            return
        for i in range(self.workers):
            t = threading.Thread(target=self._run,
                                 name=f"lightserve-reply-{i}",
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        # sentinels queue BEHIND any leftover failure replies the
        # coalescer enqueued during its own stop, so those still drain
        for _ in self._threads:
            self._q.put(None)
        for t in self._threads:
            t.join(timeout=2.0)
        self._threads = []

    def put(self, job) -> None:
        self._q.put(job)

    def _run(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                return
            try:
                job()
            except Exception:  # noqa: BLE001 — one bad reply must not
                pass           # kill the worker


class LightserveServer(FramedServer):
    proto = proto
    prefix = "lightserve"
    LISTEN_BACKLOG = 128
    connections = _m.lightserve_server_connections
    requests = _m.lightserve_server_requests
    protocol_errors = _m.lightserve_server_protocol_errors

    def __init__(self, addr: str, provider: prov.Provider,
                 trust_options: TrustOptions, chain_id: str, *,
                 backend: Optional[str] = None,
                 trust_level: Tuple[int, int] = verifier.DEFAULT_TRUST_LEVEL,
                 max_clock_drift_ns: int = DEFAULT_MAX_CLOCK_DRIFT_NS,
                 cache_max_facts: int = 200_000,
                 store_max_blocks: int = 10_000,
                 max_queue_sessions: int = 65536,
                 max_frame_bytes: int = proto.DEFAULT_MAX_FRAME_BYTES,
                 request_deadline_s: float = 10.0,
                 backwards_limit: int = 1024,
                 health_laddr: str = "",
                 server_id: str = "",
                 hit_rate_floor: float = 0.5,
                 hit_rate_min_lookups: int = 64,
                 backlog_ceiling: int = 4096,
                 max_client_skew_ns: int = 10_000_000_000,
                 reply_workers: int = 8,
                 clock: Callable[[], int] = time.time_ns):
        from tmtpu.libs.db import MemDB

        trust_options.validate_basic()
        verifier.validate_trust_level(*trust_level)
        super().__init__(addr, max_frame_bytes=max_frame_bytes,
                         health_laddr=health_laddr, server_id=server_id)
        self.provider = provider
        self.trust_options = trust_options
        self.chain_id = chain_id
        self.backend = backend
        self.trust_level = trust_level
        self.max_clock_drift_ns = max_clock_drift_ns
        self._store = LightStore(MemDB())
        self._store_max_blocks = store_max_blocks
        self.cache = VerifiedFactCache(
            chain_id, trust_options.period_ns, max_facts=cache_max_facts)
        self._default_deadline_s = request_deadline_s
        self._backwards_limit = backwards_limit
        self._hit_rate_floor = hit_rate_floor
        self._hit_rate_min_lookups = hit_rate_min_lookups
        self._backlog_ceiling = backlog_ceiling
        # the SERVER clock is the only expiry clock: it drives every
        # cache read/eviction and joint-resolve decision. A client's
        # now_ns is only a skew CHECK (see _handle_sync), never an
        # input — an unauthenticated far-future clock must not be able
        # to evict shared facts or poison coalesced peers. Injectable
        # for tests (the only supported way to pin time).
        self._clock = clock
        self._max_client_skew_ns = max_client_skew_ns
        self._reply_pool = _ReplyPool(max(1, reply_workers))
        self.coalescer = SyncCoalescer(
            self._resolve, self._slice,
            max_queue_sessions=max_queue_sessions)
        self.provider_calls = 0
        self.sessions_served = 0
        self._routes[proto.SyncRequest] = ("sync", self._handle_sync)
        self._resolve_lock = threading.Lock()
        self._health_check = None     # wired in start()
        self._anchor_fact: Optional[Fact] = None

    # --- the verified spine -------------------------------------------------

    def _fetch(self, height: Optional[int]) -> LightBlock:
        self.provider_calls += 1
        lb = self.provider.light_block(height)
        if height is not None and lb.height() != height:
            raise prov.ErrBadLightBlock(
                f"expected height {height}, got {lb.height()}")
        lb.validate_basic(self.chain_id)
        return lb

    def _save(self, lb: LightBlock, fact: Fact, now_ns: int) -> None:
        self._store.save_light_block(lb)
        if self._store.size() > self._store_max_blocks:
            self._store.prune(self._store_max_blocks)
        self.cache.put(fact, now_ns)

    def init_anchor(self, now_ns: Optional[int] = None) -> Fact:
        """Fetch and verify the trust anchor (client.go:362
        initializeWithTrustOptions, server-side)."""
        from tmtpu.types import commit_verify

        now_ns = now_ns if now_ns is not None else self._clock()
        lb = self._fetch(self.trust_options.height)
        if lb.header.hash() != self.trust_options.hash:
            raise verifier.LightError(
                f"anchor hash mismatch at height "
                f"{self.trust_options.height}: expected "
                f"{self.trust_options.hash.hex().upper()}, got "
                f"{lb.header.hash().hex().upper()}")
        commit_verify.verify_commit_light_trusting(
            lb.validator_set, self.chain_id, lb.commit,
            self.trust_level[0], self.trust_level[1],
            backend=self.backend)
        _m.lightserve_server_dispatches_total.inc()
        fact = Fact(lb.height(), lb.header.hash(), lb.header.time,
                    parent_height=0)
        self._save(lb, fact, now_ns)
        self._anchor_fact = fact
        return fact

    def latest_height(self) -> int:
        return self._store.last_light_block_height()

    def update_to_latest(self, now_ns: Optional[int] = None) -> int:
        """Advance the spine to the provider's tip (one joint-style
        resolve, same dispatch accounting). Returns the new tip height."""
        now_ns = now_ns if now_ns is not None else self._clock()
        tip = self._fetch(None)
        if tip.height() > self.latest_height():
            res = self._resolve(tip.height(), now_ns)
            if res.status != proto.STATUS_OK:
                raise verifier.LightError(
                    f"update to {tip.height()} failed: "
                    f"{proto.STATUS_NAMES.get(res.status)} {res.error}")
        return self.latest_height()

    # --- resolve engine (runs on the coalescer thread) ----------------------

    def _anchor_below(self, target: int, now_ns: int
                      ) -> Tuple[Optional[Fact], Optional[LightBlock]]:
        """Highest spine block at-or-below ``target`` whose trust is
        still fresh. A stored block with an evicted fact is still a
        verified anchor — its fact is synthesized (parent unknown)."""
        h = target + 1
        while True:
            lb = self._store.light_block_before(h)
            if lb is None:
                return None, None
            fact = self.cache.peek(lb.height())
            if fact is None:
                fact = Fact(lb.height(), lb.header.hash(),
                            lb.header.time, parent_height=0)
            if not fact.expired(self.cache.trusting_period_ns, now_ns):
                return fact, lb
            h = lb.height()

    def _verify_hop(self, verified: LightBlock, untrusted: LightBlock,
                    now_ns: int) -> int:
        """One skipping hop; returns the device dispatches it cost
        (adjacent = 1 commit verify, non-adjacent = 2, a failed trust
        check = 1). Raises exactly like verifier.verify."""
        period = self.trust_options.period_ns
        if untrusted.height() == verified.height() + 1:
            _m.lightserve_server_dispatches_total.inc()
            verifier.verify_adjacent(
                verified.signed_header, untrusted.signed_header,
                untrusted.validator_set, period, now_ns,
                self.max_clock_drift_ns, backend=self.backend)
            return 1
        try:
            _m.lightserve_server_dispatches_total.inc(2)
            verifier.verify_non_adjacent(
                verified.signed_header, verified.validator_set,
                untrusted.signed_header, untrusted.validator_set,
                period, now_ns, self.max_clock_drift_ns,
                self.trust_level, backend=self.backend)
            return 2
        except ErrNewValSetCantBeTrusted:
            # the second (new-valset) dispatch never ran
            _m.lightserve_server_dispatches_total.inc(-1)
            raise

    def _resolve(self, target: int, now_ns: int) -> Resolution:
        """Joint resolve for one target height. Serialized: concurrent
        resolves would race on the spine (single coalescer thread plus
        update_to_latest callers)."""
        with self._resolve_lock:
            return self._resolve_locked(target, now_ns)

    def _resolve_locked(self, target: int, now_ns: int) -> Resolution:
        fact = self.cache.get(target, now_ns)
        if fact is not None:
            return Resolution(proto.STATUS_OK, 0, fact, now_ns,
                              cache_hit=True)
        anchor_fact, anchor_lb = self._anchor_below(target, now_ns)
        if anchor_fact is None:
            return self._resolve_backwards(target, now_ns)
        if anchor_fact.height == target:
            # stored and fresh, only the fact was evicted: re-cache it
            self.cache.put(anchor_fact, now_ns)
            return Resolution(proto.STATUS_OK, 0, anchor_fact, now_ns,
                              cache_hit=True)
        dispatches = 0
        try:
            target_lb = self._fetch(target)
            # verifier's skipping algorithm (light/client.py
            # _verify_skipping), with dispatch accounting and every
            # verified pivot persisted as a fact
            block_cache = [target_lb]
            depth = 0
            verified = anchor_lb
            while True:
                try:
                    dispatches += self._verify_hop(
                        verified, block_cache[depth], now_ns)
                except ErrNewValSetCantBeTrusted:
                    dispatches += 1
                    if depth == len(block_cache) - 1:
                        pivot = verified.height() + \
                            (block_cache[depth].height() -
                             verified.height()) * _PIVOT_NUM // _PIVOT_DEN
                        block_cache.append(self._fetch(pivot))
                    depth += 1
                    continue
                newly = block_cache[depth]
                new_fact = Fact(newly.height(), newly.header.hash(),
                                newly.header.time, verified.height())
                self._save(newly, new_fact, now_ns)
                if depth == 0:
                    return Resolution(proto.STATUS_OK, dispatches,
                                      new_fact, now_ns)
                verified = newly
                block_cache = block_cache[:depth]
                depth = 0
        except verifier.ErrOldHeaderExpired as exc:
            return Resolution(proto.STATUS_EXPIRED, dispatches,
                              error=str(exc), now_ns=now_ns)
        except (verifier.LightError, prov.ProviderError,
                ValueError) as exc:
            return Resolution(proto.STATUS_UPSTREAM_DOWN, dispatches,
                              error=str(exc), now_ns=now_ns)

    def _resolve_backwards(self, target: int, now_ns: int) -> Resolution:
        """Everything at-or-below the target has lapsed: re-verify via
        the hash-link walk from the nearest still-fresh header above
        (verifier.verify_backwards — zero signature dispatches). The
        result is served but never re-cached."""
        above = self.cache.nearest_above(target, now_ns)
        if above is None:
            return Resolution(
                proto.STATUS_EXPIRED, 0, now_ns=now_ns,
                error=f"no trusted state fresh enough to prove height "
                      f"{target} (trusting period lapsed)")
        if above.height - target > self._backwards_limit:
            return Resolution(
                proto.STATUS_EXPIRED, 0, now_ns=now_ns,
                error=f"height {target} is {above.height - target} below "
                      f"the freshest trusted header (backwards limit "
                      f"{self._backwards_limit})")
        cur = self._store.light_block(above.height)
        if cur is None:
            return Resolution(
                proto.STATUS_UPSTREAM_DOWN, 0, now_ns=now_ns,
                error=f"fresh fact at {above.height} has no spine block")
        try:
            target_lb: Optional[LightBlock] = None
            for h in range(above.height - 1, target - 1, -1):
                interim = self._fetch(h)
                verifier.verify_backwards(interim.signed_header,
                                          cur.signed_header)
                cur = interim
                target_lb = interim
        except (verifier.LightError, prov.ProviderError,
                ValueError) as exc:
            return Resolution(proto.STATUS_UPSTREAM_DOWN, 0,
                              error=str(exc), now_ns=now_ns)
        fact = Fact(target_lb.height(), target_lb.header.hash(),
                    target_lb.header.time, parent_height=0)
        return Resolution(proto.STATUS_OK, 0, fact, now_ns,
                          hops_override=[fact])

    # --- per-session slicing (coalescer + inline fast path) -----------------

    def _slice(self, req: PendingSync, res: Resolution) -> None:
        """Fill one session's result from the joint resolution: ITS hop
        chain, cut from the fact cache's parent pointers at ITS trusted
        height."""
        if res.status != proto.STATUS_OK:
            req.status = res.status
            req.error = res.error
            return
        known = self.cache.peek(req.trusted_height)
        if known is not None and req.trusted_hash and \
                known.header_hash != req.trusted_hash:
            req.status = proto.STATUS_UNTRUSTED
            req.error = (f"trusted hash at height {req.trusted_height} "
                         f"conflicts with the verified spine")
            return
        target = res.fact.height
        if res.hops_override is not None:
            hops = [f for f in res.hops_override
                    if f.height > req.trusted_height
                    or f.height == target]
        elif target <= req.trusted_height:
            hops = [res.fact]
        else:
            hops = self.cache.hop_chain(req.trusted_height, target)
            if hops is None:   # chain broken by LRU eviction mid-walk
                hops = [res.fact]
        req.status = proto.STATUS_OK
        req.hops = hops
        req.dispatches = res.dispatches
        req.cache_hit = res.cache_hit

    # --- lifecycle ----------------------------------------------------------

    def start(self, init_anchor: bool = True) -> None:
        if self._running:
            return
        if init_anchor and self._anchor_fact is None:
            self.init_anchor()
        super().start()

    def _start_engine(self) -> None:
        from tmtpu.libs import watchdog as _wd

        self._reply_pool.start()
        self.coalescer.start()
        self._health_check = _wd.lightserve_check(
            self.health_snapshot,
            hit_rate_floor=self._hit_rate_floor,
            min_lookups=self._hit_rate_min_lookups,
            backlog_ceiling=self._backlog_ceiling)

    def _stop_engine(self) -> None:
        # coalescer first: its stop() finishes leftover sessions, whose
        # on_done hooks enqueue failure replies the pool then drains
        # ahead of its shutdown sentinels
        self.coalescer.stop()
        self._reply_pool.stop()

    def _hello_ack(self, hello: proto.Hello, version: int):
        if hello.chain_id and hello.chain_id != self.chain_id:
            self.protocol_errors.inc(kind="chain-mismatch")
            return proto.ErrorReply(
                code=proto.ERR_PROTOCOL,
                message=f"daemon serves chain {self.chain_id!r}, "
                        f"not {hello.chain_id!r}")
        anchor = self._anchor_fact
        return proto.HelloAck(
            version=version, server_id=self.server_id,
            chain_id=self.chain_id,
            anchor_height=self.trust_options.height,
            anchor_hash=anchor.header_hash if anchor
            else self.trust_options.hash,
            latest_height=max(0, self.latest_height()),
            max_frame_bytes=self._max_frame_bytes)

    def _pong(self, nonce: int, uptime_ms: int) -> proto.Pong:
        return proto.Pong(nonce=nonce,
                          latest_height=max(0, self.latest_height()),
                          uptime_ms=uptime_ms)

    def _health(self) -> Tuple[bool, Dict]:
        healthy, reason, details = self._health_check()
        return healthy, {"reason": reason, "check": details,
                         **self.snapshot()}

    # --- introspection ------------------------------------------------------

    def health_snapshot(self) -> Dict:
        """The compact shape libs.watchdog.lightserve_check judges."""
        cache = self.cache.snapshot()
        return {
            "cache_hits": cache["hits"],
            "cache_misses": cache["misses"],
            "cache_expired": cache["expired"],
            "backlog": self.coalescer.backlog(),
        }

    def snapshot(self) -> Dict:
        return {
            **super().snapshot(),
            "chain_id": self.chain_id,
            "anchor_height": self.trust_options.height,
            "latest_height": self.latest_height(),
            "spine_blocks": self._store.size(),
            "provider_calls": self.provider_calls,
            "sessions_served": self.sessions_served,
            "cache": self.cache.snapshot(),
        }

    # --- the sync handler ---------------------------------------------------

    def _reply_sync(self, send, request_id: int, ps: PendingSync,
                    t0: float) -> None:
        status = ps.status if ps.status is not None else \
            failure_status(ps.failure, proto.STATUS_UPSTREAM_DOWN)
        hops = [proto.Hop(height=f.height, header_hash=f.header_hash,
                          header_time=f.header_time)
                for f in (ps.hops or [])]
        self.sessions_served += 1
        if status == proto.STATUS_OK and ps.dispatches == 0:
            _m.lightserve_server_dispatches_avoided.inc()
        _m.lightserve_server_proof_latency.observe(
            time.perf_counter() - t0)
        send(proto.SyncResponse(
            request_id=request_id, status=status, hops=hops,
            dispatches=ps.dispatches, cache_hit=ps.cache_hit,
            dispatch_id=ps.dispatch_id, coalesced=ps.coalesced,
            error=ps.error))

    def _handle_sync(self, client_id: str, req: proto.SyncRequest,
                     send) -> None:
        t0 = time.perf_counter()

        def reject(status: int, error: str) -> None:
            send(proto.SyncResponse(
                request_id=req.request_id, status=status, error=error))

        if self._draining:
            reject(proto.STATUS_OVERLOADED, "daemon draining for shutdown")
            return
        target = req.target_height
        if target == 0:
            target = self.latest_height()
        if target <= 0:
            reject(proto.STATUS_BAD_REQUEST,
                   "no target height (spine empty and none requested)")
            return
        # THE expiry clock is the server's. The client's now_ns is a
        # skew CHECK only: a clock too far from ours would judge our
        # proofs under a different trusting-period window, so refuse
        # loudly — but never let an unauthenticated value drive cache
        # eviction or the joint resolve (a far-future clock would evict
        # fresh shared facts and expire every coalesced peer; a
        # far-past one would bypass trusting-period safety).
        now_ns = self._clock()
        if req.now_ns:
            skew = req.now_ns - now_ns
            if abs(skew) > self._max_client_skew_ns:
                reject(proto.STATUS_BAD_REQUEST,
                       f"client clock skew {skew}ns exceeds "
                       f"±{self._max_client_skew_ns}ns; fix the client "
                       f"clock (the server clock judges trust expiry)")
                return
        ps = PendingSync(client_id, target, req.trusted_height,
                         bytes(req.trusted_hash), now_ns, None)
        # fast path: fresh cached fact — answered inline on the
        # connection thread, no coalescer, no reply pool. This is the
        # only path that can hold 10k+ concurrent sessions.
        fact = self.cache.get(target, now_ns)
        if fact is not None:
            ps.coalesced = 1
            self._slice(ps, Resolution(proto.STATUS_OK, 0, fact, now_ns,
                                       cache_hit=True))
            self._reply_sync(send, req.request_id, ps, t0)
            return

        # cold path: ride the height-keyed coalescer. The reply is sent
        # by the bounded reply pool when the coalescer finishes the
        # session (its on_done hook fires exactly once for every
        # admitted session — resolve, failure, deadline, or stop — so
        # no per-session thread and no unanswered session). A wedged
        # upstream is bounded by the provider's own timeouts plus the
        # client-side request deadline.
        request_id = req.request_id

        def on_done(pending: PendingSync) -> None:
            self._reply_pool.put(
                lambda: self._reply_sync(send, request_id, pending, t0))

        try:
            self.coalescer.submit(
                client_id, target, req.trusted_height,
                bytes(req.trusted_hash), now_ns,
                deadline_s=self._default_deadline_s,
                on_done=on_done)
        except Overloaded as exc:
            reject(proto.STATUS_OVERLOADED, str(exc))
            return

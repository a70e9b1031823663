"""Span-based tracing for the crypto/consensus hot path.

The batch-verify pipeline spends its time in phases that wall-clock
numbers cannot separate (host prep vs device_put vs compile vs execute vs
readback — BENCH_r05's 35.6 s "compile+warmup" is one opaque number), so
every hot-path stage records a Span into a process-global, thread-safe
ring buffer:

    from tmtpu.libs import trace

    with trace.span("ed25519.prepare", lanes=B):
        ...                      # nested spans record their parent

    @trace.traced("consensus.enter_propose")
    def _enter_propose(self, ...): ...

Spans nest per thread (a thread-local stack carries the current parent),
carry arbitrary JSON-able attrs, and cost a few µs each (3–4 on the
builders' CPU host, 4.7 on the chip's) — cheap enough to leave on
permanently.

What a span records: its wall seconds (``perf_counter`` at both ends,
``duration_s``) and the CPU seconds its own thread burned meanwhile
(``time.thread_time()``, ``cpu_s``; enter and exit run on one thread, a
``resume``d worker's spans on the worker). How to read the two side by
side:

- a span that does not block (a decode, ``vote_set.collect``, ``.apply``,
  the sigcache, a store's encoding): ``wall - cpu`` is time the thread was
  runnable and not running — another thread's turn at the interpreter
  lock, or the OS;
- a span named for what it waits on (``batch.dispatch``,
  ``consensus.idle``, ``ed25519.execute``, ``mempool.verify``, the fsync in
  ``consensus.wal``): ``wall - cpu`` is that wait;
- C code that runs on threads of its own (``native/hostprep.c`` at eight
  threads) is not in the caller's ``cpu_s``; the process's is
  ``tendermint_runtime_process_cpu_seconds``.

A read of a thread's CPU clock is a system call, which a span may not cost:
a thread reads its clock when a span begins or ends and the last read is
``_CPU_CLOCK_INTERVAL_S`` old (four hundred times what a read costs on
the host, so the reading takes a quarter of a per cent of a thread's time
at most: 2.4 ms on the chip's host, whose kernel steps the clock by 10 ms
anyway, 0.1 ms on the builders' sandbox), and
what it burned between two reads goes to the spans open at the second. A
span longer than the interval is exact to within it; of shorter ones the
per-name totals are right where a thread's spans fill its time (a relay's
decodes) and blurred over the interval where short spans of unlike kinds
alternate; a single short span reads 0 or a neighbour's share.

The collector's pauses are recorded here too (``gc.callbacks``, hooked
once when this module is imported): every collection moves
``tendermint_runtime_gc_pause_seconds{generation}``, and one of generation
1 or 2 is also a span ``gc.collect`` (attrs ``generation``, ``collected``)
whose parent is the span open on the thread it interrupted — so a pause
comes off its parent's self time under a name of its own. Generation 0
runs hundreds of times a second on the lane loops: a span each would cost
what it measures.

The ring holds the most recent ``capacity`` spans
(default 8192, env ``TMTPU_TRACE_CAPACITY``); older spans are evicted and
counted, never blocking the hot path. Two things outlive the ring:

- per-name cumulative ``(count, seconds, cpu seconds)`` totals
  (``span_totals()``, ``span_cpu_totals()``), served as
  ``tendermint_trace_span_seconds{name}`` and
  ``tendermint_trace_span_cpu_seconds{name}`` by libs/metrics, so a
  process nobody can profile is still differenced over a window;
- while a ``jax.profiler`` session runs in this process every span is also
  a ``jax.profiler.TraceAnnotation`` of the same name, so the profiler's
  trace holds the program's stages on the device trace's clock. JAX is
  only ever looked up in ``sys.modules``: a process that has not loaded it
  (a sidecar node must not) is never made to.

Export formats:
- ``to_chrome_trace(spans)``: the Chrome trace-event JSON (load in
  chrome://tracing or Perfetto) — complete "X" events, microsecond
  timestamps on the perf_counter clock, ``args.cpu_us`` the CPU time;
- ``to_jsonl(spans)``: one JSON object per line (grep/jq-friendly).

Drained over RPC at ``/debug/traces`` on the pprof server
(tmtpu.rpc.pprof) and summarized in the ``metrics`` JSON-RPC method
(tmtpu.rpc.core); see docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import gc
import hashlib
import itertools
import json
import os
import struct
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

_DEFAULT_CAPACITY = int(os.environ.get("TMTPU_TRACE_CAPACITY", "8192"))

# -- trace context (fleet-joinable causal tracing) ---------------------------
#
# A TraceContext names a causal chain that crosses process boundaries:
# it rides p2p gossip envelopes, the sidecar wire protocol, and the ABCI
# handoff as an optional bytes field (absent ⇒ untraced). Root traces are
# derived deterministically from (chain_id, height), so every node in the
# fleet lands the SAME trace_id for the same height without coordination
# — tools/critical_path.py joins the per-node span buffers on it.

CTX_WIRE_VERSION = 1
CTX_MAX_WIRE_BYTES = 64          # hard cap; anything bigger is garbage
_CTX_ORIGIN_MAX = 40             # node ids are 40 hex chars
FLAG_SAMPLED = 0x01

# Causal-chain mark names. Every name here (and every
# ``tendermint_trace_*`` metric) must have a docs/OBSERVABILITY.md row —
# the obs-docs analysis rule parses this tuple statically.
TRACE_MARKS = (
    "height.proposal",
    "height.prevote_quorum",
    "height.precommit_quorum",
    "height.commit",
    "height.apply",
    "abci.handoff",
    "gossip.proposal_tx",
    "gossip.proposal_rx",
    "gossip.block_part_rx",
    "gossip.vote_tx",
    "gossip.vote_rx",
    "gossip.txs_tx",
    "gossip.txs_rx",
    "sidecar.verify",
    "sidecar.dispatch",
)


class TraceContext:
    """Compact cross-process trace context.

    ``trace_id`` is 16 lowercase hex chars (8 bytes on the wire);
    ``parent_span_id`` is the sender-side span id (0 = root);
    ``origin`` is the node id of whoever minted/forwarded the context;
    ``flags`` bit 0 = sampled.
    """

    __slots__ = ("trace_id", "parent_span_id", "origin", "flags")

    def __init__(self, trace_id: str, parent_span_id: int = 0,
                 origin: str = "", flags: int = FLAG_SAMPLED):
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id
        self.origin = origin
        self.flags = flags

    @property
    def sampled(self) -> bool:
        return bool(self.flags & FLAG_SAMPLED)

    def child(self, parent_span_id: int, origin: str = "") -> "TraceContext":
        """Same trace, re-parented on ``parent_span_id`` (for forwarding
        a context with the local hop recorded as the new parent)."""
        return TraceContext(self.trace_id, parent_span_id,
                            origin or self.origin, self.flags)

    def encode(self) -> bytes:
        """Wire form: version(1) || trace_id(8) || parent_span_id(8, BE)
        || flags(1) || origin_len(1) || origin. Always ≤
        CTX_MAX_WIRE_BYTES; raises nothing (fields are clamped)."""
        try:
            tid = bytes.fromhex(self.trace_id)[:8]
        except ValueError:
            tid = b""
        tid = tid.ljust(8, b"\x00")
        origin = self.origin.encode("ascii", "replace")[:_CTX_ORIGIN_MAX]
        return (bytes([CTX_WIRE_VERSION]) + tid
                + struct.pack(">Q", self.parent_span_id & (2 ** 64 - 1))
                + bytes([self.flags & 0xFF, len(origin)]) + origin)

    @classmethod
    def decode(cls, raw: bytes) -> Optional["TraceContext"]:
        """Strict, total decode: any truncated / oversized / garbage
        input returns None (untraced) — a malformed context must never
        crash a receive path."""
        try:
            if (not raw or not isinstance(raw, (bytes, bytearray))
                    or len(raw) > CTX_MAX_WIRE_BYTES or len(raw) < 19
                    or raw[0] != CTX_WIRE_VERSION):
                return None
            olen = raw[18]
            if olen > _CTX_ORIGIN_MAX or len(raw) != 19 + olen:
                return None
            origin = raw[19:19 + olen].decode("ascii")
            return cls(raw[1:9].hex(), struct.unpack(">Q", raw[9:17])[0],
                       origin, raw[17])
        except Exception:
            return None

    def to_dict(self) -> Dict:
        return {"trace": self.trace_id, "parent": self.parent_span_id,
                "origin": self.origin, "flags": self.flags}

    def __repr__(self):
        return (f"TraceContext({self.trace_id}, parent={self.parent_span_id},"
                f" origin={self.origin!r}, flags={self.flags:#x})")


def height_trace_id(chain_id: str, height: int) -> str:
    """Deterministic root trace id for a committed height: every node
    derives the same id, so fleet joins need no context at all for the
    height milestones — propagation adds the *edges*."""
    h = hashlib.sha256(b"tmtpu.height|%s|%d"
                       % (chain_id.encode("utf-8", "replace"), height))
    return h.hexdigest()[:16]


def _profiler_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name`` when JAX is already
    loaded here and a profiler session is running, else None. Never
    imports: with no session the cost is this lookup and one
    ``is_enabled()`` (≈0.1 µs)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    # a jax still being imported on another thread has no profiler yet
    cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    if cls is None or not cls.is_enabled():
        return None
    return cls(name)


def _cpu_clock_interval() -> float:
    """Seconds a thread lets pass between two reads of its CPU clock:
    four hundred times what a read costs on this host, and at most the
    interpreter's 5 ms switch interval. ``time.thread_time()`` is a real
    system call where ``perf_counter`` is not: 0.3 µs on the builders'
    sandbox, 6 µs under the sandboxed kernel of the chip's host, where two
    reads a span cost live rounds a sixth of a traced height and the
    served cell 2.6% of its rate (PERF.md, PR 36)."""
    cost = 1.0
    for _ in range(5):
        t = time.perf_counter()
        time.thread_time()
        cost = min(cost, time.perf_counter() - t)
    return min(400.0 * cost, 0.005)


_CPU_CLOCK_INTERVAL_S = _cpu_clock_interval()


def _thread_cpu(clock: list, now: float) -> float:
    """The calling thread's CPU seconds as of its last read of the clock,
    read again when ``now`` (``perf_counter``) is an interval past it.
    ``clock`` is the thread's own [when to read next, the value read]."""
    if now >= clock[0]:
        clock[1] = time.thread_time()
        clock[0] = now + _CPU_CLOCK_INTERVAL_S
    return clock[1]


class Span:
    """One completed (or in-flight) timed region. Times are
    ``time.perf_counter()`` seconds — monotonic, comparable across spans
    in-process; ``wall_time`` anchors the trace to the epoch clock.
    ``cpu_s`` is the ``time.thread_time()`` its thread spent inside (0 for
    a mark and for a span still open)."""

    __slots__ = ("name", "span_id", "parent_id", "thread_id", "thread_name",
                 "start_s", "end_s", "cpu_s", "attrs", "trace_id",
                 "ctx_parent", "origin")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 thread_id: int, thread_name: str, start_s: float,
                 attrs: Dict):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.thread_id = thread_id
        self.thread_name = thread_name
        self.start_s = start_s
        self.end_s: Optional[float] = None
        self.cpu_s = 0.0
        self.attrs = attrs
        # cross-process causal identity (None/0/"" ⇒ untraced span)
        self.trace_id: Optional[str] = None
        self.ctx_parent: int = 0
        self.origin: str = ""

    @property
    def duration_s(self) -> float:
        if self.end_s is None:
            return 0.0
        return max(0.0, self.end_s - self.start_s)

    def set(self, **attrs) -> None:
        """Attach attrs mid-span (e.g. a batch size known only later)."""
        self.attrs.update(attrs)

    def set_context(self, ctx: "TraceContext") -> None:
        self.trace_id = ctx.trace_id
        self.ctx_parent = ctx.parent_span_id
        self.origin = ctx.origin

    def to_dict(self) -> Dict:
        d = {
            "name": self.name, "id": self.span_id,
            "parent": self.parent_id, "tid": self.thread_id,
            "thread": self.thread_name,
            "start_s": round(self.start_s, 9),
            "dur_s": round(self.duration_s, 9),
            "cpu_s": round(self.cpu_s, 9),
            "attrs": self.attrs,
        }
        if self.trace_id:
            d["trace"] = self.trace_id
            d["ctx_parent"] = self.ctx_parent
            d["origin"] = self.origin
        return d

    def __repr__(self):
        return (f"Span({self.name!r}, {self.duration_s * 1e3:.3f}ms, "
                f"attrs={self.attrs})")


class Tracer:
    """Thread-safe ring buffer of completed spans with per-thread parent
    nesting. One process-global instance (``DEFAULT``) backs the module-
    level API; tests construct their own."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        self._buf: deque = deque(maxlen=max(1, capacity))
        # re-entrant: a collection can start at an allocation made under
        # it, and the collector's hook then records on the same thread
        self._lock = threading.RLock()
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self._enabled = True
        self._dropped = 0
        # {span name: [count, seconds, cpu seconds]} since process start;
        # names are static strings, so its size is the number of call sites
        self._totals: Dict[str, List] = {}
        # the collector's pauses, [count, seconds] a generation, and the
        # collection under way: [perf_counter, thread CPU | None, annotation]
        self._gc_totals = [[0, 0.0], [0, 0.0], [0, 0.0]]
        self._gc_open: List = [0.0, 0.0, None]
        # fleet identity + sampling for cross-process contexts
        self._node_id = ""
        self._chain_id = ""
        self._sample_rate = 1.0

    # -- control ------------------------------------------------------------

    def set_enabled(self, flag: bool) -> None:
        self._enabled = bool(flag)

    def enabled(self) -> bool:
        return self._enabled

    def configure(self, node_id: Optional[str] = None,
                  chain_id: Optional[str] = None,
                  sample_rate: Optional[float] = None) -> None:
        """Wire the fleet identity (origin node, chain) and the
        ``[instr] trace_sample`` knob. sample_rate 0 ⇒ this node never
        mints nor adopts contexts (fully untraced, spans stay local)."""
        if node_id is not None:
            self._node_id = str(node_id)
        if chain_id is not None:
            self._chain_id = str(chain_id)
        if sample_rate is not None:
            self._sample_rate = max(0.0, min(1.0, float(sample_rate)))

    @property
    def node_id(self) -> str:
        return self._node_id

    @property
    def sample_rate(self) -> float:
        return self._sample_rate

    @property
    def dropped(self) -> int:
        """Spans evicted by the ring since the last drain()."""
        return self._dropped

    # -- recording ----------------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
            self._tls.cpu_clock = [0.0, 0.0]    # see _thread_cpu
        return st

    @contextmanager
    def span(self, name: str, **attrs):
        """Record a timed region; yields the Span so callers can ``.set``
        attrs discovered mid-region. Exceptions propagate (the span still
        records, flagged ``error=True``)."""
        if not self._enabled:
            yield _NULL_SPAN
            return
        tls = self._tls
        stack = getattr(tls, "stack", None) or self._stack()
        clock = tls.cpu_clock
        t = threading.current_thread()
        start_s = time.perf_counter()
        sp = Span(name, next(self._ids),
                  stack[-1].span_id if stack else None,
                  t.ident or 0, t.name, start_s, attrs)
        ctxs = getattr(tls, "ctx", None)
        if ctxs:
            sp.set_context(ctxs[-1])
        stack.append(sp)
        ann = _profiler_annotation(name)
        if ann is not None:
            ann.__enter__()
        # _thread_cpu and _record, written out: a call is a fifth of what
        # a span may cost
        if start_s >= clock[0]:
            clock[1] = time.thread_time()
            clock[0] = start_s + _CPU_CLOCK_INTERVAL_S
        cpu0 = clock[1]
        try:
            yield sp
        except BaseException:
            sp.attrs["error"] = True
            raise
        finally:
            sp.end_s = end_s = time.perf_counter()
            if end_s >= clock[0]:
                clock[1] = time.thread_time()
                clock[0] = end_s + _CPU_CLOCK_INTERVAL_S
            sp.cpu_s = cpu_s = clock[1] - cpu0
            if ann is not None:
                ann.__exit__(None, None, None)
            stack.pop()
            with self._lock:
                if len(self._buf) == self._buf.maxlen:
                    self._dropped += 1
                self._buf.append(sp)
                tot = self._totals.setdefault(name, [0, 0.0, 0.0])
                tot[0] += 1
                tot[1] += end_s - start_s
                tot[2] += cpu_s

    def _record(self, sp: Span) -> None:
        """An ended span into the ring and the totals."""
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self._dropped += 1
            self._buf.append(sp)
            tot = self._totals.setdefault(sp.name, [0, 0.0, 0.0])
            tot[0] += 1
            tot[1] += sp.end_s - sp.start_s
            tot[2] += sp.cpu_s

    # -- the collector ------------------------------------------------------

    def hook_gc(self) -> None:
        """Record every collection of this process from now on (once: the
        module does it for ``DEFAULT``). The ``gc.collect`` series is made
        here so that the hook never adds a key to a table a reader may be
        walking on the same thread."""
        with self._lock:
            self._totals.setdefault("gc.collect", [0, 0.0, 0.0])
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict) -> None:
        """``gc.callbacks`` entry. Runs on the thread whose allocation
        started the collection, one collection at a time; nothing may
        start another inside it, so the slots are fixed."""
        gen = info["generation"]
        slot = self._gc_open
        if phase == "start":
            slot[0] = time.perf_counter()
            slot[1] = None                  # no span: the counter only
            if gen and self._enabled:
                slot[2] = ann = _profiler_annotation("gc.collect")
                if ann is not None:
                    ann.__enter__()
                self._stack()               # makes the thread's clock
                slot[1] = _thread_cpu(self._tls.cpu_clock, slot[0])
            return
        now = time.perf_counter()
        tot = self._gc_totals[gen]
        tot[0] += 1
        tot[1] += now - slot[0]
        cpu0 = slot[1]
        if cpu0 is None:
            return
        ann, slot[2] = slot[2], None
        if ann is not None:
            ann.__exit__(None, None, None)
        stack = self._stack()
        t = threading.current_thread()
        sp = Span("gc.collect", next(self._ids),
                  stack[-1].span_id if stack else None, t.ident or 0, t.name,
                  slot[0], {"generation": gen, "collected": info["collected"]})
        ctx = self.current_context()
        if ctx is not None:
            sp.set_context(ctx)
        sp.end_s = now
        sp.cpu_s = _thread_cpu(self._tls.cpu_clock, now) - cpu0
        self._record(sp)

    def gc_pause_totals(self) -> Dict[str, Tuple[int, float]]:
        """{generation: (collections, seconds)} since ``hook_gc``."""
        return {str(g): (t[0], t[1]) for g, t in enumerate(self._gc_totals)}

    def annotate(self, **attrs) -> None:
        """Attach attrs to the innermost span open on this thread (none
        open: nothing happens) — for a callee that learns something about
        the stage its caller is timing, e.g. which implementation ran."""
        stack = self._stack()
        if stack:
            stack[-1].set(**attrs)

    def traced(self, name: Optional[str] = None):
        """Decorator form: the whole call body becomes one span."""

        def deco(fn):
            import functools

            span_name = name or fn.__qualname__

            @functools.wraps(fn)
            def wrapper(*a, **kw):
                with self.span(span_name):
                    return fn(*a, **kw)

            return wrapper

        return deco

    # -- cross-thread handoff -----------------------------------------------

    def handoff(self):
        """What a worker thread needs to go on under the caller's span:
        (the innermost open span or None, the current context or None).
        Pass it to ``resume`` on the worker."""
        stack = getattr(self._tls, "stack", None)
        return (stack[-1] if stack else None), self.current_context()

    @contextmanager
    def resume(self, token):
        """On a worker thread: spans opened inside record ``token``'s
        span as their parent and carry its trace context, as if the
        caller had run the body itself."""
        parent, ctx = token
        stack = self._stack()
        if parent is not None:
            stack.append(parent)
        try:
            with self.activate(ctx):
                yield
        finally:
            if parent is not None:
                stack.pop()

    # -- cross-process contexts ---------------------------------------------

    def _ctx_stack(self) -> list:
        st = getattr(self._tls, "ctx", None)
        if st is None:
            st = self._tls.ctx = []
        return st

    def current_context(self) -> Optional[TraceContext]:
        st = getattr(self._tls, "ctx", None)
        return st[-1] if st else None

    @contextmanager
    def activate(self, ctx: Optional[TraceContext]):
        """Make ``ctx`` the thread's current context: spans and marks
        recorded inside pick up its trace identity. None is a no-op."""
        if ctx is None:
            yield None
            return
        st = self._ctx_stack()
        st.append(ctx)
        try:
            yield ctx
        finally:
            st.pop()

    def height_context(self, height: int) -> Optional[TraceContext]:
        """Deterministic per-height root context, or None when the height
        is sampled out (or sampling is off). Sampling is derived from the
        trace id, so every node keeps/drops the SAME heights."""
        rate = self._sample_rate
        if rate <= 0.0:
            return None
        tid = height_trace_id(self._chain_id, int(height))
        if rate < 1.0:
            # first 8 hex chars as a uniform draw in [0, 1)
            if int(tid[:8], 16) / float(0x100000000) >= rate:
                return None
        return TraceContext(tid, 0, self._node_id, FLAG_SAMPLED)

    def mark(self, name: str, ctx: Optional[TraceContext] = None,
             **attrs) -> Optional[Span]:
        """Record an instant (zero-duration) span tagged with ``ctx`` (or
        the thread's current context). The causal-chain milestones and
        every gossip/sidecar rx/tx hook use this — ~1 µs, lock-bounded."""
        if not self._enabled:
            return None
        ctx = ctx if ctx is not None else self.current_context()
        t = threading.current_thread()
        now = time.perf_counter()
        sp = Span(name, next(self._ids), None, t.ident or 0, t.name,
                  now, dict(attrs))
        sp.end_s = now
        if ctx is not None:
            sp.set_context(ctx)
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self._dropped += 1
            self._buf.append(sp)
        return sp

    def mark_height(self, height: int, name: str, **attrs) -> Optional[Span]:
        """Milestone mark on the height's deterministic root trace; no-op
        when the height is unsampled."""
        ctx = self.height_context(height)
        if ctx is None:
            return None
        return self.mark(name, ctx=ctx, height=int(height), **attrs)

    def wire_context(self, height: int) -> bytes:
        """Encoded context for outbound wire messages of ``height``
        (b"" ⇒ leave the optional field absent: untraced)."""
        ctx = self.height_context(height)
        return ctx.encode() if ctx is not None else b""

    def adopt(self, raw: bytes) -> Optional[TraceContext]:
        """Decode a received wire context. Returns None — never raises —
        on absent/garbage input, and also when this node samples at 0
        (an untraced node must not be poisoned into tracing by peers)."""
        if not raw or self._sample_rate <= 0.0:
            return None
        return TraceContext.decode(raw)

    def clock_anchor(self) -> Dict:
        """A (wall, perf) clock pair read back-to-back: lets a remote
        reader map this process's perf_counter span times onto the epoch
        clock (refined by RPC round-trip offset estimation)."""
        return {"wall_time": time.time(), "perf_time": time.perf_counter(),
                "node_id": self._node_id, "chain_id": self._chain_id,
                "sample_rate": self._sample_rate}

    # -- reading ------------------------------------------------------------

    def snapshot(self) -> List[Span]:
        """Current ring contents, oldest first, without clearing."""
        with self._lock:
            return list(self._buf)

    def drain(self) -> List[Span]:
        """Return and clear the ring (also resets the dropped counter)."""
        with self._lock:
            out = list(self._buf)
            self._buf.clear()
            self._dropped = 0
            return out

    def span_totals(self) -> Dict[str, Tuple[int, float]]:
        """{name: (count, seconds)} over every span ended since the
        process started: unlike the ring, never evicted or drained."""
        with self._lock:
            return {n: (t[0], t[1]) for n, t in self._totals.items()}

    def span_cpu_totals(self) -> Dict[str, Tuple[int, float]]:
        """{name: (count, CPU seconds of the spans' own threads)}: the
        same spans as ``span_totals``."""
        with self._lock:
            return {n: (t[0], t[2]) for n, t in self._totals.items()}

    def summary(self) -> Dict:
        """Aggregate per span name: {name: {count, total_s, cpu_s,
        max_s}} plus ring bookkeeping — the cheap form served by the
        ``metrics`` JSON-RPC method."""
        spans = self.snapshot()
        agg: Dict[str, Dict] = {}
        for sp in spans:
            a = agg.setdefault(sp.name, {"count": 0, "total_s": 0.0,
                                         "cpu_s": 0.0, "max_s": 0.0})
            a["count"] += 1
            d = sp.duration_s
            a["total_s"] += d
            a["cpu_s"] += sp.cpu_s
            if d > a["max_s"]:
                a["max_s"] = d
        for a in agg.values():
            for k in ("total_s", "cpu_s", "max_s"):
                a[k] = round(a[k], 6)
        return {"spans": agg, "buffered": len(spans),
                "dropped": self._dropped,
                "capacity": self._buf.maxlen, "enabled": self._enabled}


class _NullSpan:
    """Yielded while tracing is disabled: absorbs .set() calls."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()


# -- export formats ---------------------------------------------------------


def to_chrome_trace(spans: List[Span]) -> Dict:
    """Chrome trace-event format (chrome://tracing / Perfetto): complete
    "X" events, µs timestamps on the shared perf_counter clock, one row
    per thread. Span ids/parents ride in args for tooling."""
    events = []
    for sp in spans:
        args = dict(sp.attrs, span_id=sp.span_id, parent_id=sp.parent_id,
                    cpu_us=sp.cpu_s * 1e6)
        if sp.trace_id:
            args["trace"] = sp.trace_id
            args["ctx_parent"] = sp.ctx_parent
            args["origin"] = sp.origin
        events.append({
            "name": sp.name, "ph": "X", "pid": os.getpid(),
            "tid": sp.thread_id, "ts": sp.start_s * 1e6,
            "dur": sp.duration_s * 1e6,
            "args": args,
        })
        # thread name metadata rows render once per tid in the viewer;
        # duplicates are harmless
    seen = set()
    for sp in spans:
        if sp.thread_id not in seen:
            seen.add(sp.thread_id)
            events.append({
                "name": "thread_name", "ph": "M", "pid": os.getpid(),
                "tid": sp.thread_id,
                "args": {"name": sp.thread_name},
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def to_jsonl(spans: List[Span]) -> str:
    """One JSON object per line (jq/grep-friendly); trailing newline when
    non-empty so concatenated drains stay line-delimited."""
    if not spans:
        return ""
    return "\n".join(json.dumps(sp.to_dict()) for sp in spans) + "\n"


# -- process-global tracer + module-level API -------------------------------

DEFAULT = Tracer()
DEFAULT.hook_gc()


def span(name: str, **attrs):
    return DEFAULT.span(name, **attrs)


def annotate(**attrs) -> None:
    DEFAULT.annotate(**attrs)


def traced(name: Optional[str] = None):
    return DEFAULT.traced(name)


def snapshot() -> List[Span]:
    return DEFAULT.snapshot()


def drain() -> List[Span]:
    return DEFAULT.drain()


def summary() -> Dict:
    return DEFAULT.summary()


def span_totals() -> Dict[str, Tuple[int, float]]:
    return DEFAULT.span_totals()


def span_cpu_totals() -> Dict[str, Tuple[int, float]]:
    return DEFAULT.span_cpu_totals()


def gc_pause_totals() -> Dict[str, Tuple[int, float]]:
    return DEFAULT.gc_pause_totals()


def handoff():
    return DEFAULT.handoff()


def resume(token):
    return DEFAULT.resume(token)


def set_enabled(flag: bool) -> None:
    DEFAULT.set_enabled(flag)


def configure(node_id: Optional[str] = None, chain_id: Optional[str] = None,
              sample_rate: Optional[float] = None) -> None:
    DEFAULT.configure(node_id=node_id, chain_id=chain_id,
                      sample_rate=sample_rate)


def current_context() -> Optional[TraceContext]:
    return DEFAULT.current_context()


def activate(ctx: Optional[TraceContext]):
    return DEFAULT.activate(ctx)


def height_context(height: int) -> Optional[TraceContext]:
    return DEFAULT.height_context(height)


def mark(name: str, ctx: Optional[TraceContext] = None, **attrs):
    return DEFAULT.mark(name, ctx=ctx, **attrs)


def mark_height(height: int, name: str, **attrs):
    return DEFAULT.mark_height(height, name, **attrs)


def wire_context(height: int) -> bytes:
    return DEFAULT.wire_context(height)


def adopt(raw: bytes) -> Optional[TraceContext]:
    return DEFAULT.adopt(raw)


def clock_anchor() -> Dict:
    return DEFAULT.clock_anchor()

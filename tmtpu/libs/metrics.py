"""Prometheus-style metrics (reference: libs' go-kit prometheus wiring,
consensus/metrics.go:18, p2p/metrics.go:29).

A process-global registry of counters/gauges/histograms with text
exposition (served at the RPC /metrics endpoint). Lock-light: values are
plain floats guarded by a registry lock only on creation; updates use
per-metric locks.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from tmtpu.libs import trace as _trace

_NAMESPACE = "tendermint"


class _Metric:
    def __init__(self, name: str, help_: str, labels: Tuple[str, ...]):
        self.name = name
        self.help = help_
        self.label_names = labels
        self._values: Dict[Tuple[str, ...], float] = {}
        self._lock = threading.Lock()

    def _key(self, labels: Dict[str, str]) -> Tuple[str, ...]:
        return tuple(str(labels.get(k, "")) for k in self.label_names)

    def render(self, kind: str) -> List[str]:
        out = [f"# HELP {self.name} {_esc_help(self.help)}",
               f"# TYPE {self.name} {kind}"]
        with self._lock:
            items = sorted(self._values.items())
        if not items and not self.label_names:
            out.append(f"{self.name} 0")
        for key, v in items:
            if self.label_names:
                lbl = ",".join(f'{k}="{_esc_label(val)}"' for k, val in
                               zip(self.label_names, key))
                out.append(f"{self.name}{{{lbl}}} {_fmt(v)}")
            else:
                out.append(f"{self.name} {_fmt(v)}")
        return out

    def summary_series(self) -> Dict[str, float]:
        """{"k=v,k=v" (or "" unlabeled): value} — the JSON form served by
        the ``metrics`` JSON-RPC method."""
        with self._lock:
            items = sorted(self._values.items())
        return {_series_key(self.label_names, k): v for k, v in items}


def _series_key(names: Tuple[str, ...], key: Tuple[str, ...]) -> str:
    return ",".join(f"{n}={v}" for n, v in zip(names, key))


def _fmt(v: float) -> str:
    """Prometheus text-format value rendering, including the special
    values the exposition format spells exactly +Inf/-Inf/NaN (repr()
    would emit Python's 'inf'/'nan', which scrapers reject)."""
    v = float(v)
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if math.isnan(v):
        return "NaN"
    return str(int(v)) if v.is_integer() else repr(v)


def _esc_label(v: str) -> str:
    """Label-value escaping per the text format: backslash, double quote,
    and newline must be escaped inside the quoted value."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _esc_help(v: str) -> str:
    """HELP-line escaping: backslash and newline only (quotes are legal)."""
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


def percentile_from_buckets(buckets, counts, q: float) -> float:
    """Estimate the q-quantile (0..1) from cumulative bucket counts:
    ``counts[i]`` observations were <= ``buckets[i]``, ``counts[-1]`` is
    the total (+Inf bucket). Linear interpolation inside the winning
    bucket (lower bound 0 below the first), clamped to the last finite
    bound when the rank lands in +Inf — the histogram_quantile
    convention. Shared by Histogram.percentile and the watchdog's
    windowed-delta SLO math (libs/watchdog.py latency_slo_check)."""
    if not buckets or not counts:
        return 0.0
    total = counts[-1]
    if total <= 0:
        return 0.0
    rank = min(max(q, 0.0), 1.0) * total
    prev_count = 0
    prev_bound = 0.0
    for i, b in enumerate(buckets):
        c = counts[i]
        if c >= rank:
            if c == prev_count:
                return float(b)
            frac = (rank - prev_count) / (c - prev_count)
            return prev_bound + (float(b) - prev_bound) * frac
        prev_count = c
        prev_bound = float(b)
    return float(buckets[-1])


class Counter(_Metric):
    def inc(self, amount: float = 1.0, **labels) -> None:
        k = self._key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + amount

    def bound(self, **labels) -> Callable[..., None]:
        """``inc`` for one label combination, its key made once: for a call
        site that counts every message of a hot loop."""
        k = self._key(labels)
        values, lock = self._values, self._lock

        def inc(amount: float = 1.0) -> None:
            with lock:
                values[k] = values.get(k, 0.0) + amount

        return inc


class Gauge(_Metric):
    def set(self, value: float, **labels) -> None:
        with self._lock:
            self._values[self._key(labels)] = float(value)

    def add(self, amount: float, **labels) -> None:
        k = self._key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0.0) + amount


class Histogram(_Metric):
    """Cumulative-bucket histogram (Prometheus semantics)."""

    def __init__(self, name, help_, labels, buckets):
        super().__init__(name, help_, labels)
        self.buckets = sorted(buckets)
        self._counts: Dict[Tuple[str, ...], List[int]] = {}
        self._sums: Dict[Tuple[str, ...], float] = {}

    def observe(self, value: float, **labels) -> None:
        k = self._key(labels)
        with self._lock:
            counts = self._counts.setdefault(
                k, [0] * (len(self.buckets) + 1))
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
            counts[-1] += 1  # +Inf
            self._sums[k] = self._sums.get(k, 0.0) + value

    def totals(self, **labels) -> Tuple[int, float]:
        """(observation count, sum) for one label combination — the
        public read used by tools/tests instead of poking _counts."""
        k = self._key(labels)
        with self._lock:
            counts = self._counts.get(k)
            return ((counts[-1] if counts else 0),
                    self._sums.get(k, 0.0))

    def bucket_counts(self, **labels) -> Tuple[int, ...]:
        """Cumulative per-bucket counts (ending with the +Inf total) for
        one label combination — the public read backing windowed-delta
        percentile math (watchdog SLO check) and tools."""
        k = self._key(labels)
        with self._lock:
            counts = self._counts.get(k)
            return tuple(counts) if counts else ()

    def percentile(self, q: float, **labels) -> float:
        """Bucket-interpolated q-quantile (0..1) of everything observed
        for one label combination; 0.0 with no observations."""
        k = self._key(labels)
        with self._lock:
            counts = self._counts.get(k)
            if not counts:
                return 0.0
            counts = list(counts)
        return percentile_from_buckets(self.buckets, counts, q)

    def render(self, kind: str) -> List[str]:
        out = [f"# HELP {self.name} {_esc_help(self.help)}",
               f"# TYPE {self.name} histogram"]
        with self._lock:
            for k, counts in sorted(self._counts.items()):
                lbl_base = [(a, _esc_label(v))
                            for a, v in zip(self.label_names, k)]
                for i, b in enumerate(self.buckets):
                    labels = lbl_base + [("le", _fmt(b))]
                    ls = ",".join(f'{a}="{v}"' for a, v in labels)
                    out.append(f"{self.name}_bucket{{{ls}}} {counts[i]}")
                inf = lbl_base + [("le", "+Inf")]
                ls = ",".join(f'{a}="{v}"' for a, v in inf)
                out.append(f"{self.name}_bucket{{{ls}}} {counts[-1]}")
                base = ",".join(f'{a}="{v}"' for a, v in lbl_base)
                suffix = f"{{{base}}}" if base else ""
                out.append(f"{self.name}_sum{suffix} "
                           f"{_fmt(self._sums.get(k, 0.0))}")
                out.append(f"{self.name}_count{suffix} {counts[-1]}")
        return out

    def summary_series(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                _series_key(self.label_names, k):
                    {"count": counts[-1],
                     "sum": round(self._sums.get(k, 0.0), 6)}
                for k, counts in sorted(self._counts.items())
            }


class Summary(_Metric):
    """A Prometheus summary without quantiles: ``_count`` and ``_sum``.
    ``observe`` takes a whole batch at once (its seconds and how many
    observations they are), so a hot loop moves it once."""

    def __init__(self, name: str, help_: str):
        super().__init__(name, help_, ())
        self._count = 0
        self._sum = 0.0

    def observe(self, total: float, count: int = 1) -> None:
        with self._lock:
            self._count += count
            self._sum += total

    def totals(self) -> Tuple[int, float]:
        with self._lock:
            return self._count, self._sum

    def render(self, kind: str) -> List[str]:
        count, total = self.totals()
        return [f"# HELP {self.name} {_esc_help(self.help)}",
                f"# TYPE {self.name} summary",
                f"{self.name}_sum {_fmt(total)}",
                f"{self.name}_count {count}"]

    def summary_series(self) -> Dict[str, Dict[str, float]]:
        count, total = self.totals()
        return {"": {"count": count, "sum": round(total, 6)}}


class CounterView(_Metric):
    """An unlabelled counter whose number something else keeps:
    ``read()`` gives it when the registry is read."""

    def __init__(self, name: str, help_: str, read):
        super().__init__(name, help_, ())
        self._read = read

    def render(self, kind: str) -> List[str]:
        return [f"# HELP {self.name} {_esc_help(self.help)}",
                f"# TYPE {self.name} counter",
                f"{self.name} {_fmt(self._read())}"]

    def summary_series(self) -> Dict[str, float]:
        return {"": round(self._read(), 6)}


class SummaryView(_Metric):
    """A family of Prometheus summaries (``_count`` and ``_sum``, no
    quantiles) whose numbers another module keeps: ``read()`` gives
    {label value: (count, sum)} when the family is rendered. In
    ``summary()`` a series has a histogram's shape, {"count", "sum"}."""

    def __init__(self, name: str, help_: str, label: str, read):
        super().__init__(name, help_, (label,))
        self._read = read

    def render(self, kind: str) -> List[str]:
        out = [f"# HELP {self.name} {_esc_help(self.help)}",
               f"# TYPE {self.name} summary"]
        for value, (count, total) in sorted(self._read().items()):
            lbl = f'{{{self.label_names[0]}="{_esc_label(value)}"}}'
            out.append(f"{self.name}_sum{lbl} {_fmt(total)}")
            out.append(f"{self.name}_count{lbl} {count}")
        return out

    def summary_series(self) -> Dict[str, Dict[str, float]]:
        return {_series_key(self.label_names, (value,)):
                {"count": count, "sum": round(total, 6)}
                for value, (count, total) in sorted(self._read().items())}


class Registry:
    def __init__(self):
        self._metrics: Dict[str, Tuple[str, _Metric]] = {}
        self._lock = threading.Lock()

    def histogram(self, subsystem: str, name: str, help_: str = "",
                  labels: Tuple[str, ...] = (),
                  buckets=(0.1, 0.5, 1, 2, 5, 10, 30)) -> Histogram:
        return self._get(
            subsystem, name, "histogram",
            lambda full: Histogram(full, help_, tuple(labels), buckets))

    def counter(self, subsystem: str, name: str, help_: str = "",
                labels: Tuple[str, ...] = ()) -> Counter:
        return self._get(subsystem, name, "counter",
                         lambda full: Counter(full, help_, tuple(labels)))

    def gauge(self, subsystem: str, name: str, help_: str = "",
              labels: Tuple[str, ...] = ()) -> Gauge:
        return self._get(subsystem, name, "gauge",
                         lambda full: Gauge(full, help_, tuple(labels)))

    def summary_view(self, subsystem: str, name: str, help_: str,
                     label: str, read) -> SummaryView:
        return self._get(
            subsystem, name, "summary",
            lambda full: SummaryView(full, help_, label, read))

    def summary_metric(self, subsystem: str, name: str,
                       help_: str = "") -> Summary:
        return self._get(subsystem, name, "summary",
                         lambda full: Summary(full, help_))

    def counter_view(self, subsystem: str, name: str, help_: str,
                     read) -> CounterView:
        return self._get(subsystem, name, "counter",
                         lambda full: CounterView(full, help_, read))

    def _get(self, subsystem, name, kind, make):
        full = f"{_NAMESPACE}_{subsystem}_{name}"
        with self._lock:
            if full not in self._metrics:
                self._metrics[full] = (kind, make(full))
            return self._metrics[full][1]

    def render(self) -> str:
        with self._lock:
            items = sorted(self._metrics.items())
        lines: List[str] = []
        for _name, (kind, m) in items:
            lines.extend(m.render(kind))
        return "\n".join(lines) + "\n"

    def summary(self) -> Dict[str, Dict]:
        """JSON form of every registered metric (the ``metrics`` JSON-RPC
        method's payload; the text exposition stays on GET /metrics)."""
        with self._lock:
            items = sorted(self._metrics.items())
        return {name: {"kind": kind, "series": m.summary_series()}
                for name, (kind, m) in items}


DEFAULT = Registry()


def render_prometheus() -> str:
    return DEFAULT.render()


def summary() -> Dict[str, Dict]:
    return DEFAULT.summary()


# --- the consensus/p2p/mempool metric set (consensus/metrics.go:18) ---------

consensus_height = DEFAULT.gauge("consensus", "height",
                                 "Height of the chain")
consensus_rounds = DEFAULT.gauge("consensus", "rounds",
                                 "Round of the current height")
consensus_validators = DEFAULT.gauge("consensus", "validators",
                                     "Number of validators")
consensus_validators_power = DEFAULT.gauge(
    "consensus", "validators_power", "Total voting power of validators")
consensus_block_interval = DEFAULT.histogram(
    "consensus", "block_interval_seconds",
    "Time between this and the last block",
    buckets=(0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10))
consensus_num_txs = DEFAULT.gauge("consensus", "num_txs",
                                  "Number of txs in the latest block")
consensus_total_txs = DEFAULT.counter("consensus", "total_txs",
                                      "Total txs committed")
consensus_block_size = DEFAULT.gauge("consensus", "block_size_bytes",
                                     "Size of the latest block")
consensus_invalid_votes = DEFAULT.counter(
    "consensus", "invalid_votes_total",
    "Gossiped votes rejected at signature verification — the admission "
    "filter doing its job under byzantine garbage-signature spam")
# Vote ingestion (consensus/state.py _try_add_votes over
# types/vote_set.py add_votes): what a peer's votes came to, and how wide
# the vote sets' verify flushes were. ``late_precommit`` is a precommit
# of the height just committed, added to LastCommit during the commit wait.
consensus_votes_added = DEFAULT.counter(
    "consensus", "votes_added_total",
    "Votes added to a vote set, signature verified",
    labels=("type",))
consensus_votes_dropped = DEFAULT.counter(
    "consensus", "votes_dropped_total",
    "Votes handed to the state machine and not added: height (neither "
    "this height's nor a late precommit), late (a late precommit after "
    "round 0 began, as state.go drops it), refused (the vote set did not "
    "add it: invalid signature, duplicate, conflict, wrong index)",
    labels=("reason",))
consensus_vote_flush_lanes = DEFAULT.histogram(
    "consensus", "vote_flush_lanes",
    "Votes one VoteSet.add_votes call handed to its batch verifier",
    buckets=(1, 8, 64, 256, 512, 1024, 2048, 4096, 8192, 16384))
# The sign bytes of those flushes' lanes (types/vote_set.py add_votes): each
# from its vote set's template for its block id, built at the first vote
# that brought the block id; moved once a flush by the flush's exact counts.
consensus_vote_sign_templates = DEFAULT.counter(
    "consensus", "vote_sign_templates_total",
    "Vote-flush lanes by where their sign-bytes template came from: hit "
    "(one the vote set already held) or built (made for this lane)",
    labels=("event",))
# The WAL records of the receive loop (consensus/state.py _wal_write_msgs,
# consensus/wal.py): a drain's votes are encoded from a per-group template,
# everything else by the reflective encoder; moved once a drain by the
# drain's exact counts. An append is one file write, of one record or a run.
consensus_wal_records = DEFAULT.counter(
    "consensus", "wal_records_total",
    "WAL records written, by how the payload was encoded: template (a "
    "drain's vote records) or reflective (every other kind)",
    labels=("path",))
consensus_wal_appends = DEFAULT.counter(
    "consensus", "wal_appends_total",
    "File writes the WAL made: one a run of records")
# Every message a peer sends on the vote channel, by how the reactor turned
# its bytes into a Vote (consensus/msgs.py VoteDecoder): by hand against the
# step's shared head, or by the reflective decoder (another shape: a traced
# height's trace_ctx, a peer that orders or repeats fields, a malformed one).
consensus_vote_decode = DEFAULT.counter(
    "consensus", "vote_decode_total",
    "Vote-channel messages received, by how they were decoded: hand (the "
    "canonical vote message, against its step's shared head) or reflective "
    "(every other shape)",
    labels=("path",))
# The one queue of the vote path, between the reactor's threads and the
# consensus thread. Internal messages and timeouts are not in it.
consensus_peer_queue_blocked = DEFAULT.summary_metric(
    "consensus", "peer_queue_blocked_seconds",
    "Puts that found the peers' message queue full, and the seconds their "
    "threads waited for room: the producer outruns the consensus thread")
consensus_peer_queue_wait = DEFAULT.summary_metric(
    "consensus", "peer_queue_wait_seconds",
    "Peers' messages drained, and the seconds they lay in the queue, from "
    "the reactor's put to the drain that took them (moved once a drain)")
# Per-step latency breakdown (consensus/metrics.go StepDurationSeconds
# in later reference releases: ONE histogram with a step label): time
# spent in each round step, observed on every step transition by
# RoundState.step's setter. Fine buckets — steps run ~1-100 ms on a
# localnet.
consensus_step_duration = DEFAULT.histogram(
    "consensus", "step_duration_seconds",
    "Time spent per consensus round step", labels=("step",),
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1.0, 2.5))


# Unknown step ids were silently dropped before; count them so a new
# step constant added without a STEP_NAMES entry is visible in /metrics
# instead of producing a hole in the per-step breakdown.
consensus_step_unknown = DEFAULT.counter(
    "consensus", "step_unknown_total",
    "Step transitions with an unrecognized step id")


# mirror of consensus/types.py STEP_NAMES, used only when that module's
# import chain is unavailable (it pulls the full key-type registry, which
# needs libcrypto) — metric emission must never depend on optional deps
_STEP_NAMES_FALLBACK = {
    1: "NewHeight", 2: "NewRound", 3: "Propose", 4: "Prevote",
    5: "PrevoteWait", 6: "Precommit", 7: "PrecommitWait", 8: "Commit",
}


def observe_step_duration(step: int, seconds: float) -> None:
    try:
        from tmtpu.consensus.types import STEP_NAMES
    except ImportError:
        STEP_NAMES = _STEP_NAMES_FALLBACK

    name = STEP_NAMES.get(step)
    if name is None:
        consensus_step_unknown.inc()
        return
    consensus_step_duration.observe(seconds, step=name)


p2p_peers = DEFAULT.gauge("p2p", "peers", "Number of connected peers")
# A reactor's receive() raised on an inbound message — the peer is
# stopped for error (switch._on_peer_receive). Persistent nonzero growth
# on one channel means a peer is sending frames that channel's decoder
# rejects: version skew or a hostile/corrupting link.
p2p_recv_errors = DEFAULT.counter(
    "p2p", "recv_errors_total",
    "Inbound messages whose reactor receive() raised (peer stopped)",
    labels=("channel",))

# p2p/shaping.py + p2p/fuzz.py link emulation: writes perturbed by the
# shaper — kind=loss counts writes swallowed by sampled WAN loss,
# kind=partition counts writes stalled by a partition (TCP-backpressure
# emulation; the write blocks, it is never silently dropped). Plus the
# artificial latency injected per shaped write. A production scrape
# showing nonzero values means someone left [p2p] shaping on a real node.
p2p_shape_drops = DEFAULT.counter(
    "p2p", "shape_drops_total",
    "Peer-connection writes dropped (loss) or stalled (partition) by "
    "link shaping",
    labels=("kind",))
p2p_shape_delay = DEFAULT.histogram(
    "p2p", "shape_delay_seconds",
    "Artificial latency injected per shaped peer-connection write",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.2, 0.5, 1, 2))
mempool_size = DEFAULT.gauge("mempool", "size",
                             "Number of uncommitted txs")
# throughput tier: batched admission + dedup-aware gossip
mempool_batch_flushes = DEFAULT.counter(
    "mempool", "batch_flushes_total",
    "CheckTx gather windows flushed (one pipelined ABCI burst each)")
mempool_batch_txs = DEFAULT.counter(
    "mempool", "batch_txs_total",
    "Txs admitted through batched CheckTx gather windows")
mempool_sig_rejects = DEFAULT.counter(
    "mempool", "sig_rejects_total",
    "Signed-tx envelopes rejected at admission (malformed or bad "
    "signature) before any ABCI round trip")
mempool_gossip_dedup_skips = DEFAULT.counter(
    "mempool", "gossip_dedup_skips_total",
    "Txs NOT echoed to a peer because its seen-cache (or the sender "
    "set) already covers them")
mempool_gossip_rx_dups = DEFAULT.counter(
    "mempool", "gossip_rx_dups_total",
    "Received gossip txs already resident in the mempool cache "
    "(wasted bandwidth a peer's dedup should have prevented)")
# async ApplyBlock overlap: how much execution time ran concurrently
# with next-height gossip intake instead of blocking the state machine
consensus_async_apply_overlap = DEFAULT.histogram(
    "consensus", "async_apply_overlap_seconds",
    "Wall time ApplyBlock spent on the async executor while the "
    "consensus receive loop kept draining gossip",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
             0.5, 1.0, 2.5))


# --- the blocksync metric set (tmtpu/blocksync/) ----------------------------
#
# A run is the contiguous stretch of fetched blocks whose commits ride
# one fused verify dispatch (common.verify_block_run: v0, v1, v2); in v0
# its length is what the validator set's size leaves of common.RUN_LANES,
# and v0 alone counts the blocks it applies and refuses.

blocksync_blocks_applied = DEFAULT.counter(
    "blocksync", "blocks_applied_total",
    "Blocks verified, saved and applied by the fast-sync loop")
blocksync_run_blocks = DEFAULT.histogram(
    "blocksync", "run_blocks",
    "Blocks whose commits one fused verify dispatch carried",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512))
blocksync_bad_blocks = DEFAULT.counter(
    "blocksync", "bad_blocks_total",
    "Fetched blocks refused (commit verification or validate_block): the "
    "block and its successor are re-requested and their servers punished")


# --- the tx lifecycle latency metric set (libs/txlat.py) --------------------
#
# Written by the per-tx stamp ring: each checkpoint stamp observes the
# transition from the tx's previous stamp into the stage histogram
# (labels like "submit_to_admit_enq"), and the commit stamp observes the
# end-to-end submit→commit span. Per-tx adjacent-transition diffs
# telescope, so one tx's stage observations sum exactly to its
# first-stamp→commit span (stage-decomposition contract, see
# docs/OBSERVABILITY.md).

tx_latency_submit_to_commit = DEFAULT.histogram(
    "tx", "latency_submit_to_commit_seconds",
    "End-to-end tx latency from RPC broadcast_tx entry to block commit "
    "on the node the client submitted to",
    buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5,
             10, 30))
tx_latency_stage = DEFAULT.histogram(
    "tx", "latency_stage_seconds",
    "Per-tx time between adjacent lifecycle checkpoints (stage label "
    "names the transition, e.g. submit_to_admit_enq)",
    labels=("stage",),
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1, 2.5, 5, 10))
tx_latency_tracked = DEFAULT.gauge(
    "tx", "latency_tracked",
    "Tx journeys currently resident in the lifecycle stamp ring")
tx_latency_completed = DEFAULT.counter(
    "tx", "latency_completed_total",
    "Tx journeys that reached the commit checkpoint")
tx_latency_evicted = DEFAULT.counter(
    "tx", "latency_evicted_total",
    "Tx journeys FIFO-evicted from the stamp ring before commit")


# --- the distributed-tracing metric set (libs/trace.py context tier) --------
#
# Written by the gossip reactors, the sidecar client/server, and the
# traces RPC exporter. transport ∈ {gossip, sidecar}; every name needs a
# docs/OBSERVABILITY.md row (obs-docs rule).

trace_spans_exported = DEFAULT.counter(
    "trace", "spans_exported_total",
    "Spans served to remote readers via the traces JSON-RPC method or "
    "GET /debug/traces")
trace_spans_dropped = DEFAULT.counter(
    "trace", "spans_dropped_total",
    "Spans evicted from the ring buffer between exports (observed at "
    "export time; the ring itself never blocks)")
trace_context_tx = DEFAULT.counter(
    "trace", "context_tx_total",
    "Trace contexts attached to outbound messages",
    labels=("transport",))
trace_context_rx = DEFAULT.counter(
    "trace", "context_rx_total",
    "Valid trace contexts decoded from inbound messages",
    labels=("transport",))
trace_context_invalid = DEFAULT.counter(
    "trace", "context_invalid_total",
    "Inbound trace-context fields that failed strict decode (truncated, "
    "oversized, or garbage) and were treated as untraced",
    labels=("transport",))
trace_clock_offset_ms = DEFAULT.gauge(
    "trace", "clock_offset_ms",
    "Last wall-clock offset estimate (reader minus this node, ms) "
    "reported by a traces RPC caller that supplied its own clock")

# Kept by libs/trace under the lock a span takes as it ends, never evicted
# or drained: what a window adds to a stage's seconds in a process nobody
# can profile (the node behind a sidecar). One series a span call site.
trace_span_seconds = DEFAULT.summary_view(
    "trace", "span_seconds",
    "Spans ended since the process started and their total seconds, by "
    "span name (libs/trace.py)", "name", _trace.span_totals)
trace_span_cpu_seconds = DEFAULT.summary_view(
    "trace", "span_cpu_seconds",
    "The same spans and the CPU seconds their own threads burned inside "
    "them (time.thread_time): beside span_seconds, what a span did itself "
    "and what it spent waiting, for the interpreter lock or for what it "
    "is named for", "name", _trace.span_cpu_totals)


# --- the runtime under every layer ------------------------------------------
#
# Read, not written: the collector's pauses are kept by libs/trace's
# gc.callbacks hook (which also makes a collection of generation 1 or 2 a
# gc.collect span), the process's CPU seconds by the OS.

runtime_gc_pause_seconds = DEFAULT.summary_view(
    "runtime", "gc_pause_seconds",
    "Collections of the cyclic garbage collector since the process "
    "started and the seconds they stopped the thread they ran on, by "
    "generation", "generation", _trace.gc_pause_totals)
runtime_process_cpu_seconds = DEFAULT.counter_view(
    "runtime", "process_cpu_seconds",
    "CPU seconds of the whole process, user and system, every thread "
    "(time.process_time): over a window, the share of one core it burned",
    time.process_time)


# --- the node health engine metric set (libs/watchdog.py) -------------------
#
# Written by Watchdog.check_now on every evaluation pass; the per-check
# gauges mirror the /healthz payload so a scraper sees the same verdict
# an operator's curl does.

health_up = DEFAULT.gauge(
    "health", "up",
    "1 when every watchdog check passes, 0 when any is unhealthy")
health_check_up = DEFAULT.gauge(
    "health", "check_up",
    "Per-check watchdog verdict (1 healthy, 0 unhealthy)",
    labels=("check",))
health_stalls = DEFAULT.counter(
    "health", "stalls_total",
    "Watchdog checks that transitioned healthy -> unhealthy",
    labels=("check",))
health_watchdog_ticks = DEFAULT.counter(
    "health", "watchdog_ticks_total", "Watchdog evaluation passes")
health_slow_spans = DEFAULT.counter(
    "health", "slow_spans_total",
    "Trace spans whose duration exceeded the slow-span SLO threshold",
    labels=("span",))
# latency SLO check (watchdog latency_slo_check, gated on
# [instr] latency_slo_ms > 0): rolling-window p99 of submit→commit
# derived from tx_latency_submit_to_commit_seconds bucket deltas
health_latency_p99_ms = DEFAULT.gauge(
    "health", "latency_p99_ms",
    "Rolling-window p99 submit-to-commit tx latency (ms) as seen by "
    "the latency SLO watchdog check")
health_latency_slo_breaches = DEFAULT.counter(
    "health", "latency_slo_breaches_total",
    "Watchdog samples whose rolling p99 submit-to-commit latency "
    "exceeded the configured SLO")

# libs/sync.py deadlock-detection reports (one per acquisition that
# blocked past the watched-lock timeout)
sync_lock_stall = DEFAULT.counter(
    "sync", "lock_stall_total",
    "Lock acquisitions that exceeded the deadlock-detection timeout",
    labels=("lock",))


# --- the validator forensics metric set (libs/valstats.py) ------------------
#
# Written by the per-validator behavior ledger fed from types/vote_set.py
# and consensus/state.py. type ∈ {prevote, precommit}; rank is the
# arrival-rank bucket ("1", "2-4", … ">256") so cardinality stays
# bounded at 10k-validator sets; the scorecard gauge is per validator
# address (bounded by the validator set, like the reference's
# consensus_validator_power). Every name needs a docs/OBSERVABILITY.md
# row (obs-docs rule).

validator_vote_lag = DEFAULT.histogram(
    "validator", "vote_lag_seconds",
    "Per-vote arrival offset from the local prevote/precommit step "
    "start, labeled by vote type and arrival-rank bucket",
    labels=("type", "rank"),
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1, 2.5, 5, 10))
validator_vote_after_quorum = DEFAULT.histogram(
    "validator", "vote_after_quorum_seconds",
    "Straggler lag: how far behind the +2/3 crossing a vote arrived "
    "(only votes landing after quorum observe)",
    labels=("type",),
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1, 2.5, 5, 10))
validator_missed_votes = DEFAULT.counter(
    "validator", "missed_votes_total",
    "Validator seats absent from the decided round's vote set at "
    "finalize (one increment per absent validator per height)",
    labels=("type",))
validator_missed_proposals = DEFAULT.counter(
    "validator", "missed_proposals_total",
    "Propose steps that timed out with no proposal from the scheduled "
    "proposer")
validator_equivocations = DEFAULT.counter(
    "validator", "equivocations_total",
    "Verified conflicting-block vote pairs observed (one per "
    "conflicting vote surfaced by the vote set)")
validator_amnesia = DEFAULT.counter(
    "validator", "amnesia_total",
    "Cross-round lock amnesia flags: a validator precommitted two "
    "different non-nil blocks at the same height in different rounds")
validator_scorecard = DEFAULT.gauge(
    "validator", "scorecard",
    "Decaying per-validator liveness score (1.0 = voted every recent "
    "height, decays toward 0.0 while absent), refreshed per finalized "
    "height",
    labels=("address",))
validator_tracked = DEFAULT.gauge(
    "validator", "tracked",
    "Validators currently resident in the forensics ledger")


# --- the crypto batch-verify pipeline metric set ----------------------------
#
# Observed at every batch call site: the device dispatch
# (tmtpu/tpu/dispatch.py, once a flush) and the CPU batch
# verifier (tmtpu/crypto/batch.py). Labels: curve = ed25519 | sr25519 |
# secp256k1; backend = the jax device platform ("cpu", "tpu", ...) or
# "cpu" for the serial path; impl = pallas | xla | serial | native.

_LANE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048,
                 4096, 8192, 16384, 40960)

crypto_batch_size = DEFAULT.histogram(
    "crypto", "batch_size",
    "Signatures per batch-verify dispatch",
    labels=("curve", "backend"), buckets=_LANE_BUCKETS)
crypto_pad_ratio = DEFAULT.histogram(
    "crypto", "pad_ratio",
    "Padded-over-actual lane ratio per device dispatch "
    "(bucket rounding waste)",
    labels=("curve",),
    buckets=(1.0, 1.05, 1.1, 1.25, 1.5, 2.0, 4.0, 8.0))
crypto_verify_latency = DEFAULT.histogram(
    "crypto", "verify_latency_seconds",
    "End-to-end batch-verify latency (prep through readback)",
    labels=("curve", "backend", "impl"),
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1, 2.5, 5, 10, 30, 60))
crypto_compile_cache_hits = DEFAULT.counter(
    "crypto", "compile_cache_hits_total",
    "Device dispatches that reused a warm jit cache entry",
    labels=("curve",))
crypto_compile_cache_misses = DEFAULT.counter(
    "crypto", "compile_cache_misses_total",
    "Device dispatches whose padded shape forced a fresh XLA compile",
    labels=("curve",))
crypto_cpu_fallback = DEFAULT.counter(
    "crypto", "cpu_fallback_total",
    "Signatures verified on the serial CPU path instead of the device",
    labels=("curve", "reason"))
crypto_flush_curves = DEFAULT.histogram(
    "crypto", "flush_curves",
    "Key types with a device batch in one flush of the device verifier "
    "(each is a dispatch of its own, one after another)",
    buckets=(1, 2, 3))
crypto_sr_python_transcript_lanes = DEFAULT.counter(
    "crypto", "sr_python_transcript_lanes_total",
    "sr25519 lanes whose merlin challenge the host prep walked in pure "
    "Python because the native library is not bound (some 300x slower)")
# --- the verify-once hot path metric set (crypto/sigcache.py) ---------------
#
# Written by the process-wide verified-signature cache and the batch
# dedup/adaptive-flush layer in crypto/batch.py. The ApplyBlock
# "self-committed height" acceptance reads hit/miss straight off these:
# a healthy validator shows hits_total ≈ commit lane count per height.

crypto_sigcache_hits = DEFAULT.counter(
    "crypto", "sigcache_hits_total",
    "Batch-verify lanes answered by the verified-signature cache "
    "(no dispatch, no CPU verify)")
crypto_sigcache_misses = DEFAULT.counter(
    "crypto", "sigcache_misses_total",
    "Batch-verify lanes not found in the verified-signature cache")
crypto_sigcache_inserts = DEFAULT.counter(
    "crypto", "sigcache_inserts_total",
    "Verified signatures inserted into the cache")
crypto_sigcache_evictions = DEFAULT.counter(
    "crypto", "sigcache_evictions_total",
    "Cache entries evicted by the per-shard LRU bound")
crypto_sigcache_entries = DEFAULT.gauge(
    "crypto", "sigcache_entries",
    "Verified-signature cache entries currently resident")
crypto_sigcache_dedup_lanes = DEFAULT.counter(
    "crypto", "sigcache_dedup_lanes_total",
    "Batch lanes collapsed onto an identical in-flight lane in the "
    "same batch (one verify, N results)")
crypto_flush_target_lanes = DEFAULT.gauge(
    "crypto", "flush_target_lanes",
    "Adaptive flush scheduler's current target batch size "
    "(arrival rate x device RTT, clamped)")
crypto_flush_gather_waits = DEFAULT.counter(
    "crypto", "flush_gather_waits_total",
    "Consensus receive-loop waits taken to gather a fuller verify "
    "batch (adaptive flush scheduling)")

# The same idea one layer up (types/validator.py): a ValidatorSet keeps its
# Merkle hash and its protobuf encoding while the content they cover stands.
types_valset_memo_hits = DEFAULT.counter(
    "types", "valset_memo_hits_total",
    "ValidatorSet.hash() / encode() calls answered with kept bytes "
    "(the content they were computed from is unchanged)",
    labels=("what",))
types_valset_memo_misses = DEFAULT.counter(
    "types", "valset_memo_misses_total",
    "ValidatorSet.hash() / encode() calls that computed their bytes",
    labels=("what",))
# A Commit's protobuf encode and decode (types/pb.py Commit): by hand from
# and into plain rows, or by the reflective codec (a Commit built of
# CommitSig objects, or input of another shape than the canonical one);
# moved once a Commit, never a signature.
types_commit_codec = DEFAULT.counter(
    "types", "commit_codec_total",
    "Commit protobuf encodes and decodes by direction (encode, decode) and "
    "path: hand (the signatures as plain rows) or reflective (the generic "
    "codec, for every other shape)",
    labels=("dir", "path"))
# The validator updates an EndBlock returned (state/execution.py
# update_state, over ValidatorSet.update_with_change_set): a power change of
# a member, a join, a leave (power 0); moved once a call by its exact counts.
state_validator_updates = DEFAULT.counter(
    "state", "validator_updates_total",
    "Validator updates applied to the next-next validator set, by kind: "
    "power (a member's power changed), join, leave",
    labels=("kind",))
# BlockExecutor.validate_block calls (state/execution.py): full (the block
# checked against the state) or repeat (the (state, block) that last passed,
# unchanged: the pure checks skipped); moved once a call.
state_validate_block = DEFAULT.counter(
    "state", "validate_block_total",
    "BlockExecutor.validate_block calls by path: full, or repeat (the same "
    "state and block objects that last passed, unchanged)",
    labels=("path",))
# The lanes of a fused verify+tally flush (tpu/dispatch.py _flush) by the
# power limbs they fill: one (the power fits the first 13-bit limb) or more
# (it carries into limbs 1-4); moved once a flush by one count over the limbs.
crypto_tally_power_lanes = DEFAULT.counter(
    "crypto", "tally_power_lanes_total",
    "Lanes of fused verify+tally flushes by the 13-bit power limbs their "
    "power fills: one, or more",
    labels=("limbs",))

crypto_device_probe_attempts = DEFAULT.counter(
    "crypto", "device_probe_attempts_total",
    "jax device-backend probe attempts")
crypto_device_probe_timeouts = DEFAULT.counter(
    "crypto", "device_probe_timeouts_total",
    "jax device-backend probes that hit the hard timeout")
crypto_tpu_backend_up = DEFAULT.gauge(
    "crypto", "tpu_backend_up",
    "1 when a usable jax device backend answered the probe, else 0")

# --- the self-healing crypto backend metric set (libs/breaker.py) -----------
#
# One series per registered breaker ("crypto.tpu" wraps the whole TPU
# batch-verify path in crypto/batch.py; "pallas.<curve>" wraps each
# fused-kernel family's compile/dispatch). State encoding follows
# breaker.STATE_CODES: 0 closed, 1 open, 2 half-open.

crypto_breaker_state = DEFAULT.gauge(
    "crypto", "breaker_state",
    "Circuit-breaker state: 0 closed, 1 open, 2 half-open",
    labels=("breaker",))
crypto_breaker_transitions = DEFAULT.counter(
    "crypto", "breaker_transitions_total",
    "Circuit-breaker state transitions",
    labels=("breaker", "from", "to"))
crypto_breaker_failures = DEFAULT.counter(
    "crypto", "breaker_failures_total",
    "Failures recorded against a circuit breaker (device errors, "
    "deadline hits, probe failures)",
    labels=("breaker",))
crypto_batch_deadline_exceeded = DEFAULT.counter(
    "crypto", "batch_deadline_exceeded_total",
    "Device batch dispatches abandoned at the per-batch deadline "
    "(the batch re-verified on the CPU path)",
    labels=("curve",))

# --- the mesh-dispatch metric set (tpu/mesh_dispatch.py) --------------------
#
# Written when a flush rides the sharded multi-chip path instead of one
# device. fallback_total{reason} is the degradation story: breaker-open
# counts lanes skipped while crypto.mesh is open, device-error counts
# lanes that re-rode the single-device path after a mesh failure.

crypto_mesh_devices = DEFAULT.gauge(
    "crypto", "mesh_devices",
    "Devices in the cached verify mesh (0 until the first sharded "
    "dispatch builds it)")
crypto_mesh_dispatches_total = DEFAULT.counter(
    "crypto", "mesh_dispatches_total",
    "Batch-verify flushes dispatched across the device mesh",
    labels=("curve",))
crypto_mesh_shard_lanes = DEFAULT.histogram(
    "crypto", "mesh_shard_lanes",
    "Padded lanes per device shard in a mesh dispatch",
    labels=("curve",), buckets=_LANE_BUCKETS)
crypto_mesh_pad_ratio = DEFAULT.histogram(
    "crypto", "mesh_pad_ratio",
    "Padded-over-actual lane ratio per mesh dispatch (bucket plus "
    "32 x n_devices quantum rounding)",
    labels=("curve",),
    buckets=(1.0, 1.05, 1.1, 1.25, 1.5, 2.0, 4.0, 8.0))
crypto_mesh_psum_seconds = DEFAULT.histogram(
    "crypto", "mesh_psum_seconds",
    "Host readback time of the psum-reduced vote-power limb sums "
    "after the packed mask is ready",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
             0.025, 0.05, 0.1, 0.25, 1))
crypto_mesh_fallback_total = DEFAULT.counter(
    "crypto", "mesh_fallback_total",
    "Lanes that skipped or fell back off the mesh path",
    labels=("curve", "reason"))

# libs/faultinject.py: one count per scripted fault actually delivered
# (mode = error | latency | flaky | crash) — chaos tests assert on it,
# and a production scrape showing nonzero values means someone left
# TMTPU_FAULTS set on a real node.
fault_injected = DEFAULT.counter(
    "fault", "injected_total",
    "Faults delivered by the libs/faultinject framework",
    labels=("site", "mode"))

# consensus/wal.py crash-hardened recovery
wal_torn_tail_truncated = DEFAULT.counter(
    "wal", "torn_tail_truncated_total",
    "WAL opens that truncated an incomplete (torn) trailing record")
wal_skipped_bytes = DEFAULT.counter(
    "wal", "replay_skipped_bytes_total",
    "Bytes skipped by non-strict WAL iteration after a corrupt or torn "
    "record")

# --- the verification-sidecar metric set (tmtpu/sidecar/) -------------------
#
# Server set: written by the daemon (sidecar/server.py connection loop,
# sidecar/coalescer.py dispatcher). The coalescing acceptance reads
# straight off dispatch_clients: a shared daemon under multi-node load
# shows observations > 1, per-process verify never can.

sidecar_server_connections = DEFAULT.gauge(
    "sidecar", "server_connections",
    "Client connections currently held by the sidecar daemon")
sidecar_server_requests = DEFAULT.counter(
    "sidecar", "server_requests_total",
    "Protocol messages handled by the sidecar daemon",
    labels=("type",))
sidecar_server_dispatches_total = DEFAULT.counter(
    "sidecar", "server_dispatches_total",
    "Joint device dispatches issued by the cross-client coalescer",
    labels=("curve",))
sidecar_server_dispatch_lanes = DEFAULT.histogram(
    "sidecar", "server_dispatch_lanes",
    "Lanes per joint coalesced dispatch",
    labels=("curve",), buckets=_LANE_BUCKETS)
sidecar_server_dispatch_clients = DEFAULT.histogram(
    "sidecar", "server_dispatch_clients",
    "Distinct clients whose lanes shared one coalesced dispatch",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 32))
sidecar_server_queue_lanes = DEFAULT.gauge(
    "sidecar", "server_queue_lanes",
    "Lanes currently queued in the coalescer awaiting dispatch")
sidecar_server_queue_wait = DEFAULT.histogram(
    "sidecar", "server_queue_wait_seconds",
    "Time a verify request waited in the coalescer's queue, from submit "
    "to the cut of the joint batch that took it (expired ones included)",
    labels=("curve",),
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
             0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30))
sidecar_server_overloads_total = DEFAULT.counter(
    "sidecar", "server_overloads_total",
    "Verify requests rejected by admission control (queue full)")
sidecar_server_protocol_errors = DEFAULT.counter(
    "sidecar", "server_protocol_errors_total",
    "Malformed frames / bad sequencing / version mismatches rejected "
    "by the sidecar daemon",
    labels=("kind",))
sidecar_server_mesh_dispatches = DEFAULT.counter(
    "sidecar", "server_mesh_dispatches_total",
    "Joint coalesced dispatches that rode the multi-chip mesh path",
    labels=("curve",))
sidecar_server_mesh_occupancy_lanes = DEFAULT.gauge(
    "sidecar", "server_mesh_occupancy_lanes",
    "Cumulative sharded lanes dispatched to each mesh device by this "
    "daemon",
    labels=("device",))

# Client set: written by crypto/batch.py SidecarBatchVerifier and
# sidecar/client.py. fallback_total{reason} is the degradation story:
# no-addr / breaker-open / overloaded / unavailable each count the
# lanes that rode the in-process path instead of the daemon.

sidecar_client_requests = DEFAULT.counter(
    "sidecar", "client_requests_total",
    "Verify requests sent to the sidecar daemon",
    labels=("curve", "status"))
sidecar_client_request_latency = DEFAULT.histogram(
    "sidecar", "client_request_latency_seconds",
    "Round-trip latency of sidecar verify requests",
    labels=("curve",),
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1, 2.5, 5, 10, 30))
sidecar_client_reconnects = DEFAULT.counter(
    "sidecar", "client_reconnects_total",
    "Sidecar connection (re)establishment attempts")
sidecar_client_fallback = DEFAULT.counter(
    "sidecar", "client_fallback_total",
    "Lanes verified in-process because the sidecar was unusable",
    labels=("reason",))
sidecar_client_up = DEFAULT.gauge(
    "sidecar", "client_up",
    "1 when this process holds a live sidecar connection, else 0")

# --- the light client's metric set (tmtpu/light/client.py) ------------------
#
# A session is one verify_light_block call; in sequential mode a run is
# the stretch of fetched headers whose commits ride one fused verify
# dispatch, sized in lanes as blocksync's is (blocksync/common.run_shape).

light_blocks_verified = DEFAULT.counter(
    "light", "blocks_verified_total",
    "Light blocks verified and saved to the trusted store")
light_sessions = DEFAULT.counter(
    "light", "sessions_total",
    "verify_light_block calls that reached verification (sequential, "
    "skipping or backwards), whatever their outcome")
light_run_blocks = DEFAULT.histogram(
    "light", "run_blocks",
    "Headers whose commits one fused verify dispatch of a sequential "
    "session carried",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512))
light_provider_calls = DEFAULT.counter(
    "light", "provider_calls_total",
    "Light blocks asked of a provider, by its role",
    labels=("role",))

# --- the light-client serving-tier metric set (tmtpu/lightserve/) -----------
#
# Server set: written by the lightserve daemon (lightserve/server.py
# connection loop, lightserve/coalescer.py dispatcher, lightserve/cache.py
# read path). The serving-tier acceptance reads straight off
# dispatches_avoided_total vs sessions served: after warmup nearly every
# session must cost zero device dispatches (cache + coalescer working).

lightserve_server_connections = DEFAULT.gauge(
    "lightserve", "server_connections",
    "Client connections currently held by the lightserve daemon")
lightserve_server_requests = DEFAULT.counter(
    "lightserve", "server_requests_total",
    "Protocol messages handled by the lightserve daemon",
    labels=("type",))
lightserve_server_backlog = DEFAULT.gauge(
    "lightserve", "server_backlog",
    "Sync sessions currently queued in the coalescer awaiting a joint "
    "resolve")
lightserve_server_resolves_total = DEFAULT.counter(
    "lightserve", "server_resolves_total",
    "Joint target-height resolves issued by the session coalescer")
lightserve_server_dispatches_total = DEFAULT.counter(
    "lightserve", "server_dispatches_total",
    "Signature-verification dispatches the daemon's resolves actually "
    "performed (bisection hops x commit verifies)")
lightserve_server_dispatches_avoided = DEFAULT.counter(
    "lightserve", "server_dispatches_avoided_total",
    "Sync sessions answered with ZERO verification dispatches (served "
    "from the verified-height fact cache or a shared joint resolve)")
lightserve_server_cache_hits = DEFAULT.counter(
    "lightserve", "server_cache_hits_total",
    "Verified-height fact cache lookups answered by a fresh fact")
lightserve_server_cache_misses = DEFAULT.counter(
    "lightserve", "server_cache_misses_total",
    "Verified-height fact cache lookups that found no fact")
lightserve_server_cache_expired = DEFAULT.counter(
    "lightserve", "server_cache_expired_total",
    "Cached verified-height facts refused (and evicted) because the "
    "trusting period lapsed")
lightserve_server_coalesced_sessions = DEFAULT.histogram(
    "lightserve", "server_coalesced_sessions",
    "Concurrent sessions that shared one joint target-height resolve",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 32, 64, 128, 256))
lightserve_server_proof_latency = DEFAULT.histogram(
    "lightserve", "server_proof_latency_seconds",
    "Time from sync-request receipt to proof reply on the daemon",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
             0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30))
lightserve_server_overloads_total = DEFAULT.counter(
    "lightserve", "server_overloads_total",
    "Sync sessions rejected by admission control (backlog full)")
lightserve_server_protocol_errors = DEFAULT.counter(
    "lightserve", "server_protocol_errors_total",
    "Malformed frames / bad sequencing / version or chain mismatches "
    "rejected by the lightserve daemon",
    labels=("kind",))

# Client set: written by lightserve/client.py (the flood harness, the
# scenario session driver, and any embedded light client attach through
# it).

lightserve_client_requests = DEFAULT.counter(
    "lightserve", "client_requests_total",
    "Sync requests sent to the lightserve daemon",
    labels=("status",))
lightserve_client_request_latency = DEFAULT.histogram(
    "lightserve", "client_request_latency_seconds",
    "Round-trip latency of lightserve sync requests",
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1, 2.5, 5, 10, 30))
lightserve_client_reconnects = DEFAULT.counter(
    "lightserve", "client_reconnects_total",
    "Lightserve connection (re)establishment attempts")
lightserve_client_up = DEFAULT.gauge(
    "lightserve", "client_up",
    "1 when this process holds a live lightserve connection, else 0")

# (curve, impl, padded-lanes) shapes already dispatched in this process:
# jax.jit keys its cache on input shapes, so a new padded bucket size is
# exactly one fresh XLA compile — tracked here rather than by poking jax
# internals.
_seen_jit_shapes: set = set()
_seen_jit_lock = threading.Lock()


def observe_crypto_batch(curve: str, backend: str, impl: str, lanes: int,
                         padded: int, seconds: float) -> None:
    """One call per batch-verify dispatch; fans out to the whole crypto
    metric set. ``padded`` of 0 means no device padding (serial path)."""
    crypto_batch_size.observe(lanes, curve=curve, backend=backend)
    crypto_verify_latency.observe(seconds, curve=curve, backend=backend,
                                  impl=impl)
    if padded and lanes:
        crypto_pad_ratio.observe(padded / lanes, curve=curve)
        key = (curve, impl, padded)
        with _seen_jit_lock:
            hit = key in _seen_jit_shapes
            _seen_jit_shapes.add(key)
        if hit:
            crypto_compile_cache_hits.inc(curve=curve)
        else:
            crypto_compile_cache_misses.inc(curve=curve)

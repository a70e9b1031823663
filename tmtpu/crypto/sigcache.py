"""Process-wide verified-signature cache — the "verify once" hot path.

Every signature hot path in the tree (VoteSet.add_votes, VerifyCommit*
during ApplyBlock, blocksync v0/v1/v2, the light client) routes through
one crypto.BatchVerifier, but before this module they verified the SAME
signatures repeatedly: a precommit checked at vote ingestion was
re-verified by verify_commit on the very next height's ApplyBlock,
blocksync re-verified commits the node already tallied, and a vote
relayed by N peers burned N padded batch lanes. Dispatch count and lane
occupancy are the cost drivers on the CPU backend and on a device
alike (every dispatch pays host prep, a transfer and a readback), so a
lane that never exists is the cheapest lane there is.

Design:

- Entries are keyed by ``sha256(type ‖ len(pk) ‖ pk ‖ len(msg) ‖ msg ‖
  len(sig) ‖ sig)`` — length-prefixed so no two distinct triples can
  collide by concatenation ambiguity, and curve-typed so identical key
  bytes on two curves stay distinct entries. The SAME ``(pubkey, msg)``
  under two DIFFERENT signatures occupies two distinct entries (the
  equivocation case: both must verify independently).
- **Only successful verifications are cached.** A cached entry asserts
  "this exact (pubkey, msg, sig) triple verified" — a pure statement of
  signature math that no validator-set rotation, peer behavior, or
  restart can invalidate, so a hit can never be a stale false-positive.
  Failures are NOT cached: invalid signatures are rare, attacker-
  controlled (a negative cache is a memory DoS lever), and re-verifying
  them only slows the attacker down.
- Sharded + lock-striped: the key's first bytes pick one of
  ``shards`` independent LRU maps, each with its own lock, so vote
  ingestion, ApplyBlock, and blocksync threads do not serialize on one
  mutex. Per-shard capacity bounds total memory (entries are 32-byte
  keys + OrderedDict overhead; the default 131072 entries is a few MB).
- Explicit invalidation: ``invalidate_all()`` (operator action, tests)
  and ``configure()`` (node wiring from ``[crypto] sigcache_*`` knobs;
  shrinking capacity evicts immediately).

Every hit/miss/insert/evict lands in the
``tendermint_crypto_sigcache_*`` metric set (libs/metrics.py) and batch
verifies with cache activity emit ``crypto.sigcache`` timeline events
(docs/OBSERVABILITY.md runbook).
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Dict, Optional

DEFAULT_MAX_ENTRIES = 131072
DEFAULT_SHARDS = 16


def cache_key(type_value: str, pk_bytes: bytes, msg: bytes,
              sig: bytes) -> bytes:
    """The 32-byte cache key for one (curve, pubkey, msg, sig) triple.
    Length-prefixed fields make the encoding injective; the curve name
    keeps equal byte-strings on different curves apart."""
    h = hashlib.sha256()
    t = type_value.encode()
    for part in (t, pk_bytes, msg, sig):
        h.update(len(part).to_bytes(4, "big"))
        h.update(part)
    return h.digest()


class SigCache:
    """Sharded, lock-striped LRU set of verified-signature keys."""

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES,
                 shards: int = DEFAULT_SHARDS, enabled: bool = True):
        shards = max(1, int(shards))
        # round shards down to a power of two so the key byte masks
        # uniformly (sha256 output is uniform; masking keeps it so)
        while shards & (shards - 1):
            shards -= 1
        self._shard_mask = shards - 1
        self._shards = [OrderedDict() for _ in range(shards)]
        self._locks = [threading.Lock() for _ in range(shards)]
        self._max_entries = max(shards, int(max_entries))
        self._per_shard = max(1, self._max_entries // shards)
        self._enabled = bool(enabled)
        # lifetime counters (metrics carry the cross-restart totals;
        # these back stats() so tools need no metrics scrape)
        self._hits = 0
        self._misses = 0
        self._inserts = 0
        self._evictions = 0
        self._stats_lock = threading.Lock()

    # -- core ---------------------------------------------------------------

    def _shard(self, key: bytes):
        i = key[0] & self._shard_mask
        return self._shards[i], self._locks[i]

    def contains(self, key: bytes) -> bool:
        """True iff ``key`` was inserted as verified. Hits refresh LRU
        recency. Counts a hit/miss in both stats and metrics."""
        if not self._enabled:
            return False
        shard, lock = self._shard(key)
        with lock:
            hit = key in shard
            if hit:
                shard.move_to_end(key)
        self._note(hit)
        return hit

    def add(self, key: bytes) -> None:
        """Record one VERIFIED triple. Evicts LRU entries past the
        per-shard cap; never blocks other shards."""
        if not self._enabled:
            return
        evicted = 0
        shard, lock = self._shard(key)
        with lock:
            already = key in shard
            shard[key] = True
            shard.move_to_end(key)
            while len(shard) > self._per_shard:
                shard.popitem(last=False)
                evicted += 1
        from tmtpu.libs import metrics as _m

        with self._stats_lock:
            if not already:
                self._inserts += 1
            self._evictions += evicted
        if not already:
            _m.crypto_sigcache_inserts.inc()
        if evicted:
            _m.crypto_sigcache_evictions.inc(evicted)
        _m.crypto_sigcache_entries.set(self.size())

    def check(self, type_value: str, pk_bytes: bytes, msg: bytes,
              sig: bytes) -> bool:
        """Convenience: key + contains in one call."""
        return self.contains(cache_key(type_value, pk_bytes, msg, sig))

    def record(self, type_value: str, pk_bytes: bytes, msg: bytes,
               sig: bytes) -> None:
        """Convenience: key + add in one call."""
        self.add(cache_key(type_value, pk_bytes, msg, sig))

    def _note(self, hit: bool) -> None:
        from tmtpu.libs import metrics as _m

        with self._stats_lock:
            if hit:
                self._hits += 1
            else:
                self._misses += 1
        if hit:
            _m.crypto_sigcache_hits.inc()
        else:
            _m.crypto_sigcache_misses.inc()

    # -- control ------------------------------------------------------------

    def set_enabled(self, enabled: bool) -> None:
        self._enabled = bool(enabled)
        if not self._enabled:
            self.invalidate_all()

    def enabled(self) -> bool:
        return self._enabled

    def invalidate_all(self) -> None:
        """Drop every entry (operator hook / tests). Never invalidates
        correctness — entries are context-free signature-math facts —
        but frees memory and forces fresh verifies."""
        for shard, lock in zip(self._shards, self._locks):
            with lock:
                shard.clear()
        from tmtpu.libs import metrics as _m

        _m.crypto_sigcache_entries.set(0)

    def resize(self, max_entries: int, shards: Optional[int] = None) -> None:
        """Apply new capacity (config reload). Changing the shard count
        rebuilds the stripe array (entries are dropped — simpler than
        rehashing, and a reload is rare); shrinking capacity in place
        evicts LRU immediately."""
        if shards is not None and (max(1, int(shards)) !=
                                   self._shard_mask + 1):
            self.__init__(max_entries, shards, self._enabled)
            return
        self._max_entries = max(self._shard_mask + 1, int(max_entries))
        self._per_shard = max(1, self._max_entries //
                              (self._shard_mask + 1))
        evicted = 0
        for shard, lock in zip(self._shards, self._locks):
            with lock:
                while len(shard) > self._per_shard:
                    shard.popitem(last=False)
                    evicted += 1
        if evicted:
            from tmtpu.libs import metrics as _m

            with self._stats_lock:
                self._evictions += evicted
            _m.crypto_sigcache_evictions.inc(evicted)
            _m.crypto_sigcache_entries.set(self.size())

    # -- reading ------------------------------------------------------------

    def size(self) -> int:
        return sum(len(s) for s in self._shards)

    def stats(self) -> Dict:
        with self._stats_lock:
            hits, misses = self._hits, self._misses
            inserts, evictions = self._inserts, self._evictions
        lookups = hits + misses
        return {
            "enabled": self._enabled,
            "entries": self.size(),
            "max_entries": self._max_entries,
            "shards": self._shard_mask + 1,
            "hits": hits,
            "misses": misses,
            "inserts": inserts,
            "evictions": evictions,
            "hit_rate": round(hits / lookups, 4) if lookups else 0.0,
        }


# --- the process-wide instance ----------------------------------------------
#
# One cache per process, like the breaker registry: vote ingestion,
# ApplyBlock, blocksync and the light client must all see each other's
# verifications or the "verify once" property is lost.

DEFAULT = SigCache()


def configure(max_entries: int, shards: int, enabled: bool = True) -> None:
    """Apply the ``[crypto] sigcache_*`` knobs (node wiring / config
    reload)."""
    DEFAULT.set_enabled(enabled)
    if enabled:
        DEFAULT.resize(max_entries, shards)


def check(type_value: str, pk_bytes: bytes, msg: bytes, sig: bytes) -> bool:
    return DEFAULT.check(type_value, pk_bytes, msg, sig)


def record(type_value: str, pk_bytes: bytes, msg: bytes, sig: bytes) -> None:
    DEFAULT.record(type_value, pk_bytes, msg, sig)


def stats() -> Dict:
    return DEFAULT.stats()


def invalidate_all() -> None:
    DEFAULT.invalidate_all()

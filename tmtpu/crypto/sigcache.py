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

- Entries are keyed by ``sha256(len(type) ‖ type ‖ len(pk) ‖ pk ‖
  len(msg) ‖ msg ‖ len(sig) ‖ sig)``, 4-byte big-endian lengths —
  length-prefixed so no two distinct triples can
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

- A flush at a time: a batch resolve (crypto/batch.py) asks for a whole
  flush's keys (``cache_keys``), hits (``contains_many``) and inserts
  (``add_many``) in one call each. Per key the work is what the one-key
  forms do — they ARE the bulk forms with one key — and it runs under
  the key's shard lock, a shard's keys in the order given, so entries,
  recency and evictions come out the same, shard by shard, as a
  sequence of ``contains`` / ``add``.

Every hit/miss/insert/evict lands in the
``tendermint_crypto_sigcache_*`` metric set (libs/metrics.py) by its
exact count, added once a call (so once a flush, not once a lane); the
``entries`` gauge is set once, after a call's last insert. Batch
verifies with cache activity emit ``crypto.sigcache`` timeline events
(docs/OBSERVABILITY.md runbook).
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict, defaultdict
from typing import Dict, Iterable, List, Optional, Sequence

DEFAULT_MAX_ENTRIES = 131072
DEFAULT_SHARDS = 16


def cache_key(type_value: str, pk_bytes: bytes, msg: bytes,
              sig: bytes) -> bytes:
    """The 32-byte cache key for one (curve, pubkey, msg, sig) triple.
    Length-prefixed fields make the encoding injective; the curve name
    keeps equal byte-strings on different curves apart."""
    t = type_value.encode()
    return hashlib.sha256(b"".join(
        (_len4(t), t, _len4(pk_bytes), pk_bytes, _len4(msg), msg,
         _len4(sig), sig))).digest()


def _len4(part: bytes) -> bytes:
    return len(part).to_bytes(4, "big")


class _Len4Table(dict):
    """length -> its 4-byte big-endian prefix, filled as lengths are
    met. Made anew for each ``cache_keys`` call, so it never outgrows a
    flush's distinct field lengths."""

    def __missing__(self, n: int) -> bytes:
        prefix = self[n] = n.to_bytes(4, "big")
        return prefix


def cache_keys(items: Iterable[Sequence]) -> List[bytes]:
    """``cache_key`` for every ``(pub_key, msg, sig, ...)`` item of a
    flush, byte for byte: one hash call a lane, the curve's header
    (``len‖type``) built once a curve and each length prefix once a
    length."""
    sha256 = hashlib.sha256
    join = b"".join
    headers: Dict[str, bytes] = {}
    len4 = _Len4Table()
    out = []
    for item in items:
        pk, msg, sig = item[0], item[1], item[2]
        curve = pk.type_value()
        header = headers.get(curve)
        if header is None:
            t = curve.encode()
            header = headers[curve] = _len4(t) + t
        pkb = pk.bytes()
        out.append(sha256(join(
            (header, len4[len(pkb)], pkb, len4[len(msg)], msg,
             len4[len(sig)], sig))).digest())
    return out


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

    def _by_shard(self, keys: Sequence[bytes]):
        """``(shard, its lock, positions in keys)`` for every shard that
        ``keys`` touch, a shard's positions in the order given: what one
        key after another would have met there."""
        mask = self._shard_mask
        positions: Dict[int, List[int]] = defaultdict(list)
        for i, key in enumerate(keys):
            positions[key[0] & mask].append(i)
        return [(self._shards[s], self._locks[s], at)
                for s, at in positions.items()]

    def contains_many(self, keys: Sequence[bytes]) -> List[bool]:
        """Per key, True iff it was inserted as verified; a hit refreshes
        LRU recency. Each shard's lock is taken once for all of its keys.
        Hits and misses go to stats and metrics once, by their counts."""
        hits = [False] * len(keys)
        if not self._enabled or not keys:
            return hits
        for shard, lock, positions in self._by_shard(keys):
            with lock:
                for i in positions:
                    key = keys[i]
                    if key in shard:
                        shard.move_to_end(key)
                        hits[i] = True
        n_hits = hits.count(True)
        n_misses = len(keys) - n_hits
        from tmtpu.libs import metrics as _m

        with self._stats_lock:
            self._hits += n_hits
            self._misses += n_misses
        if n_hits:
            _m.crypto_sigcache_hits.inc(n_hits)
        if n_misses:
            _m.crypto_sigcache_misses.inc(n_misses)
        return hits

    def add_many(self, keys: Sequence[bytes]) -> None:
        """Record VERIFIED triples. Per key: insert (or refresh), then
        evict LRU entries past the per-shard cap; never blocks other
        shards. Inserts and evictions are counted exactly and added
        once, the entries gauge set once after the last insert."""
        if not self._enabled or not keys:
            return
        inserts = 0
        evictions = 0
        cap = self._per_shard
        for shard, lock, positions in self._by_shard(keys):
            with lock:
                for i in positions:
                    key = keys[i]
                    if key in shard:
                        shard.move_to_end(key)
                        continue
                    shard[key] = True
                    inserts += 1
                    while len(shard) > cap:
                        shard.popitem(last=False)
                        evictions += 1
        from tmtpu.libs import metrics as _m

        with self._stats_lock:
            self._inserts += inserts
            self._evictions += evictions
        if inserts:
            _m.crypto_sigcache_inserts.inc(inserts)
        if evictions:
            _m.crypto_sigcache_evictions.inc(evictions)
        _m.crypto_sigcache_entries.set(self.size())

    def contains(self, key: bytes) -> bool:
        """``contains_many`` of one key."""
        return self.contains_many((key,))[0]

    def add(self, key: bytes) -> None:
        """``add_many`` of one key."""
        self.add_many((key,))

    def check(self, type_value: str, pk_bytes: bytes, msg: bytes,
              sig: bytes) -> bool:
        """Convenience: key + contains in one call."""
        return self.contains(cache_key(type_value, pk_bytes, msg, sig))

    def record(self, type_value: str, pk_bytes: bytes, msg: bytes,
               sig: bytes) -> None:
        """Convenience: key + add in one call."""
        self.add(cache_key(type_value, pk_bytes, msg, sig))

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

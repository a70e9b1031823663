"""crypto/sigcache.py + the verify-once batch path (ISSUE 4).

Covers the correctness corners the cache design leans on:

- key injectivity (length-prefixed fields, curve-typed);
- equivocation: the SAME (pubkey, msg) under two DIFFERENT signatures
  occupies two distinct entries and both verify (randomized-signature
  schemes sign the same bytes differently every time);
- validator-set rotation cannot turn a cache hit into a wrong accept —
  entries are context-free signature-math facts, membership is always
  re-checked by the caller against the CURRENT set;
- eviction under churn never returns a stale false-positive (property
  test over random insert/evict/query interleavings against a
  reference model);
- batch-level dedup: N identical in-flight lanes → one verify, N
  results, powers folded exactly once into the tally;
- the adaptive flush scheduler is inert without device RTT samples and
  bounded when it has them;
- the bulk forms a resolve uses (``cache_keys``, ``contains_many``,
  ``add_many``) against a one-key-at-a-time model: same keys, answers,
  entries in the same LRU order, totals and registry values.
"""

import hashlib
import random
import sys
import threading

import pytest

from tmtpu.crypto import batch as crypto_batch
from tmtpu.crypto import ed25519 as ed
from tmtpu.crypto import keys as _keys
from tmtpu.crypto import sigcache

ED = "ed25519"


def _ed(i, msg=None):
    priv = ed.gen_priv_key_from_secret(b"sigcache-%d" % i)
    m = msg if msg is not None else b"sigcache msg %d" % i
    return priv.pub_key(), m, priv.sign(m)


# --- key construction --------------------------------------------------------


def test_cache_key_injective_across_field_boundaries():
    # concatenation-ambiguous splits must produce different keys
    a = sigcache.cache_key(ED, b"ab", b"c", b"sig")
    b = sigcache.cache_key(ED, b"a", b"bc", b"sig")
    c = sigcache.cache_key(ED, b"abc", b"", b"sig")
    assert len({a, b, c}) == 3
    # identical bytes on different curves stay distinct entries
    assert sigcache.cache_key(ED, b"pk", b"m", b"s") != \
        sigcache.cache_key("sr25519", b"pk", b"m", b"s")
    # and the sig is part of the identity (equivocation prerequisite)
    assert sigcache.cache_key(ED, b"pk", b"m", b"s1") != \
        sigcache.cache_key(ED, b"pk", b"m", b"s2")


def _key_by_definition(type_value, pk_bytes, msg, sig):
    """The key's definition, spelled out field by field."""
    h = hashlib.sha256()
    for part in (type_value.encode(), pk_bytes, msg, sig):
        h.update(len(part).to_bytes(4, "big"))
        h.update(part)
    return h.digest()


@pytest.mark.parametrize("msg", [b"", b"m", b"long " * 14000],
                         ids=["empty", "one-byte", "70kB"])
def test_bulk_keys_equal_cache_key_on_every_curve(msg):
    from tmtpu.crypto import secp256k1, sr25519

    pks = [ed.gen_priv_key_from_secret(b"bulk-ed").pub_key(),
           sr25519.gen_priv_key_from_secret(b"bulk-sr").pub_key(),
           secp256k1.gen_priv_key().pub_key(),
           _TwoSigPubKey(b"odd-length-ident", msg, b"a", b"b")]
    assert {pk.type_value() for pk in pks} == {
        "ed25519", "sr25519", "secp256k1", "equivtest"}
    # the curves interleaved, so a header is looked up again after another
    items = [(pk, msg, b"sig-%d" % i * (i + 1), 7)
             for i, pk in enumerate(pks * 2)]
    ks = sigcache.cache_keys(items)
    assert ks == [sigcache.cache_key(pk.type_value(), pk.bytes(), m, s)
                  for pk, m, s, _p in items]
    assert ks == [_key_by_definition(pk.type_value(), pk.bytes(), m, s)
                  for pk, m, s, _p in items]
    assert all(len(k) == 32 for k in ks) and len(set(ks)) == len(ks)
    assert sigcache.cache_keys([]) == []


# --- basic cache behavior ----------------------------------------------------


def test_hit_miss_insert_and_stats():
    c = sigcache.SigCache(max_entries=64, shards=4)
    pk, msg, sig = b"pk", b"msg", b"sig"
    assert not c.check(ED, pk, msg, sig)
    c.record(ED, pk, msg, sig)
    assert c.check(ED, pk, msg, sig)
    st = c.stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["inserts"] == 1
    assert st["entries"] == 1 and 0 < st["hit_rate"] < 1
    c.invalidate_all()
    assert c.size() == 0
    assert not c.check(ED, pk, msg, sig)


def test_disabled_cache_never_hits():
    c = sigcache.SigCache(max_entries=64, shards=2, enabled=False)
    c.record(ED, b"pk", b"m", b"s")
    assert not c.check(ED, b"pk", b"m", b"s")
    assert c.size() == 0


def test_resize_shrink_evicts_lru():
    c = sigcache.SigCache(max_entries=64, shards=1)
    ks = [sigcache.cache_key(ED, b"pk%d" % i, b"m", b"s") for i in range(32)]
    for k in ks:
        c.add(k)
    # touch the newest half so the oldest half is LRU
    for k in ks[16:]:
        assert c.contains(k)
    c.resize(8)
    assert c.size() <= 8
    # survivors must come from the recently-used tail
    assert all(not c.contains(k) for k in ks[:16])


# --- the bulk forms against one key at a time --------------------------------


class _OneKeyModel:
    """The cache as a plain loop: one shard lookup, one counter step and
    one gauge write a key. What ``contains_many`` / ``add_many`` must
    equal, entry for entry and count for count."""

    def __init__(self, max_entries, shards):
        from collections import OrderedDict

        self.mask = shards - 1
        self.per_shard = max_entries // shards
        self.shards = [OrderedDict() for _ in range(shards)]
        self.totals = {"hits": 0, "misses": 0, "inserts": 0, "evictions": 0}
        self.entries_gauge = None

    def contains(self, key):
        shard = self.shards[key[0] & self.mask]
        hit = key in shard
        if hit:
            shard.move_to_end(key)
        self.totals["hits" if hit else "misses"] += 1
        return hit

    def add(self, key):
        shard = self.shards[key[0] & self.mask]
        if key not in shard:
            self.totals["inserts"] += 1
        shard[key] = True
        shard.move_to_end(key)
        while len(shard) > self.per_shard:
            shard.popitem(last=False)
            self.totals["evictions"] += 1
        self.entries_gauge = sum(len(s) for s in self.shards)


def _registry():
    from tmtpu.libs import metrics as _m

    return {name: getattr(_m, "crypto_sigcache_" + name)
            .summary_series().get("", 0.0)
            for name in ("hits", "misses", "inserts", "evictions",
                         "entries")}


def _state(cache):
    st = cache.stats()
    return ([list(shard) for shard in cache._shards],
            {k: st[k] for k in ("hits", "misses", "inserts", "evictions")})


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bulk_forms_equal_one_key_forms(seed):
    """A seeded mix of hits, misses, in-call duplicates and inserts past
    the per-shard cap, as flushes through the bulk forms, key by key
    through the one-key forms, and through the plain model: the same
    answers, the same entries in the same LRU order shard by shard, the
    same stats() and the same five registry values after every flush."""
    rng = random.Random(seed)
    universe = [sigcache.cache_key(ED, b"pk%d" % i, b"m", b"s")
                for i in range(160)]
    bulk = sigcache.SigCache(max_entries=32, shards=4)
    single = sigcache.SigCache(max_entries=32, shards=4)
    model = _OneKeyModel(max_entries=32, shards=4)
    for _flush in range(60):
        keys = [rng.choice(universe) for _ in range(rng.randrange(0, 48))]
        verified = [k for k in keys if rng.random() < 0.7]

        r0 = _registry()
        hits = bulk.contains_many(keys)
        bulk.add_many(verified)
        r1 = _registry()
        assert hits == [single.contains(k) for k in keys]
        for k in verified:
            single.add(k)
        r2 = _registry()
        assert hits == [model.contains(k) for k in keys]
        for k in verified:
            model.add(k)

        assert _state(bulk) == _state(single)
        assert _state(bulk) == ([list(s) for s in model.shards],
                                model.totals)
        assert bulk.stats() == single.stats()
        counters = ("hits", "misses", "inserts", "evictions")
        assert [r1[c] - r0[c] for c in counters] == \
            [r2[c] - r1[c] for c in counters]
        if verified:
            assert r1["entries"] == r2["entries"] == bulk.size() \
                == model.entries_gauge
    assert model.totals["evictions"] > 0 and model.totals["hits"] > 0
    assert sum(r2[c] for c in counters) > 0


def test_bulk_forms_on_a_disabled_cache():
    c = sigcache.SigCache(max_entries=64, shards=2, enabled=False)
    ks = [sigcache.cache_key(ED, b"pk", b"m", b"s%d" % i) for i in range(5)]
    r0 = _registry()
    c.add_many(ks)
    assert c.contains_many(ks) == [False] * 5
    assert c.size() == 0 and _registry() == r0
    st = c.stats()
    assert st["hits"] == st["misses"] == st["inserts"] == 0


class _TrustingVerifier(crypto_batch.BatchVerifier):
    """Accepts every lane: the resolve's bookkeeping with no crypto."""

    def _verify_pending(self, items, tally):
        return [True] * len(items), sum(it[3] for it in items)


def test_two_threads_resolving_leave_the_serial_totals(monkeypatch):
    """Two threads resolve interleaved flushes through one small cache:
    the once-a-call totals lose no update. Lookups, inserts and
    evictions (every triple is fresh, so they do not depend on the
    interleaving) equal the serial sums, in stats() and in the registry."""
    cache = sigcache.SigCache(max_entries=256, shards=4)
    monkeypatch.setattr(sigcache, "DEFAULT", cache)
    pk = ed.gen_priv_key_from_secret(b"threads").pub_key()
    flushes, lanes = 40, 64
    errors = []

    def resolve_all(tag):
        try:
            for f in range(flushes):
                bv = _TrustingVerifier()
                for i in range(lanes):
                    bv.add(pk, b"%s-%d-%d" % (tag, f, i), b"s" * 64, 1)
                _ok, mask, tallied = bv.verify_tally()
                assert mask == [True] * lanes and tallied == lanes
        except Exception as e:  # noqa: BLE001 — reported by the test
            errors.append(e)

    r0 = _registry()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=resolve_all, args=(tag,))
                   for tag in (b"a", b"b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    r1 = _registry()
    total = 2 * flushes * lanes
    st = cache.stats()
    assert st["hits"] + st["misses"] == total and st["hits"] == 0
    assert st["inserts"] == total
    assert st["evictions"] == total - cache.size() > 0
    assert r1["hits"] + r1["misses"] - r0["hits"] - r0["misses"] == total
    assert r1["inserts"] - r0["inserts"] == total
    assert r1["evictions"] - r0["evictions"] == st["evictions"]


# --- equivocation ------------------------------------------------------------


class _TwoSigPubKey(_keys.PubKey):
    """Models a randomized-signature scheme (sr25519/ECDSA): the same
    message admits many valid signatures. Accepts exactly two."""

    def __init__(self, ident, msg, sig_a, sig_b):
        self._ident = ident
        self._msg = msg
        self._valid = {sig_a, sig_b}

    def address(self):
        return self._ident[:20].ljust(20, b"\x00")

    def bytes(self):
        return self._ident

    def verify_signature(self, msg, sig):
        return msg == self._msg and sig in self._valid

    def equals(self, other):
        return isinstance(other, _TwoSigPubKey) and \
            other._ident == self._ident

    def type_value(self):
        return "equivtest"


def test_equivocation_same_msg_two_sigs_distinct_entries():
    """Same (pubkey, msg), two different sigs: BOTH must verify through
    the cache-aware batch path, occupy distinct entries, and both hit
    on re-verify. A (pk, msg)-keyed cache would conflate them."""
    pk = _TwoSigPubKey(b"equiv-pk", b"the vote bytes", b"sig-A" * 13,
                       b"sig-B" * 13)
    msg, sig_a, sig_b = b"the vote bytes", b"sig-A" * 13, b"sig-B" * 13
    bv = crypto_batch.CPUBatchVerifier()
    bv.add(pk, msg, sig_a, power=3)
    bv.add(pk, msg, sig_b, power=3)
    all_ok, mask, tallied = bv.verify_tally()
    assert all_ok and mask == [True, True] and tallied == 6
    # distinct entries — NOT one entry deduped
    assert bv.cache_stats["dedup"] == 0
    assert bv.cache_stats["dispatched"] == 2
    assert sigcache.DEFAULT.check("equivtest", pk.bytes(), msg, sig_a)
    assert sigcache.DEFAULT.check("equivtest", pk.bytes(), msg, sig_b)
    # both ride the cache on the second pass
    bv2 = crypto_batch.CPUBatchVerifier()
    bv2.add(pk, msg, sig_a)
    bv2.add(pk, msg, sig_b)
    all_ok, mask = bv2.verify()
    assert all_ok and bv2.cache_stats["hits"] == 2
    assert bv2.cache_stats["dispatched"] == 0


def test_equivocating_votes_real_ed25519():
    """Tendermint equivocation: one validator signs two CONFLICTING
    messages. Both verify, both cache, and neither entry shadows the
    other."""
    priv = ed.gen_priv_key_from_secret(b"equivocator")
    pk = priv.pub_key()
    m1, m2 = b"vote for block A", b"vote for block B"
    s1, s2 = priv.sign(m1), priv.sign(m2)
    bv = crypto_batch.CPUBatchVerifier()
    bv.add(pk, m1, s1)
    bv.add(pk, m2, s2)
    all_ok, mask = bv.verify()
    assert all_ok and mask == [True, True]
    assert sigcache.DEFAULT.check(ED, pk.bytes(), m1, s1)
    assert sigcache.DEFAULT.check(ED, pk.bytes(), m2, s2)
    # cross-pairing must MISS (and would fail verify): the cache cannot
    # be used to transplant a signature onto a different message
    assert not sigcache.DEFAULT.check(ED, pk.bytes(), m1, s2)
    assert not sigcache.DEFAULT.check(ED, pk.bytes(), m2, s1)


# --- batch dedup + tally exactness -------------------------------------------


def test_dedup_one_lane_n_results_tally_exact():
    pk, msg, sig = _ed(1)
    bv = crypto_batch.CPUBatchVerifier()
    for _ in range(5):
        bv.add(pk, msg, sig, power=7)
    all_ok, mask, tallied = bv.verify_tally()
    assert all_ok and mask == [True] * 5
    # every member's power counted exactly once, through ONE verify
    assert tallied == 35
    assert bv.cache_stats == {"lanes": 5, "hits": 0, "dedup": 4,
                              "dispatched": 1}


def test_mixed_hits_misses_dups_and_invalid():
    pk1, m1, s1 = _ed(10)
    pk2, m2, s2 = _ed(11)
    pk3, m3, s3 = _ed(12)
    bad = bytes([s3[0] ^ 0xFF]) + s3[1:]
    # warm pk1 into the cache
    assert crypto_batch.verify_one(pk1, m1, s1)
    bv = crypto_batch.CPUBatchVerifier()
    bv.add(pk1, m1, s1, power=1)    # hit
    bv.add(pk2, m2, s2, power=2)    # miss
    bv.add(pk2, m2, s2, power=2)    # dup of the miss
    bv.add(pk3, m3, bad, power=4)   # invalid — must not cache
    all_ok, mask, tallied = bv.verify_tally()
    assert not all_ok and mask == [True, True, True, False]
    assert tallied == 1 + 2 + 2
    assert bv.cache_stats["hits"] == 1 and bv.cache_stats["dedup"] == 1
    assert bv.cache_stats["dispatched"] == 2
    assert not sigcache.DEFAULT.check(ED, pk3.bytes(), m3, bad)
    # the invalid triple stays invalid on re-verify (never cached)
    bv2 = crypto_batch.CPUBatchVerifier()
    bv2.add(pk3, m3, bad)
    all_ok, mask = bv2.verify()
    assert not all_ok and mask == [False]


def test_dup_groups_of_unequal_powers_and_an_invalid_group():
    """Two duplicate groups in one flush, one valid with unequal powers
    and one invalid: the backend sees one lane a group carrying the
    group's folded power, every member gets its group's verdict, only
    the valid group's power is tallied and only its triple is cached."""
    pk1, m1, s1 = _ed(40)
    pk2, m2, s2 = _ed(41)
    pk3, m3, s3 = _ed(42)
    pk4, m4, s4 = _ed(43)
    bad = bytes([s3[0] ^ 0xFF]) + s3[1:]
    assert crypto_batch.verify_one(pk4, m4, s4)
    seen = []

    class Recording(crypto_batch.CPUBatchVerifier):
        def _verify_pending(self, items, tally):
            seen.extend((pk.bytes(), power) for pk, _m, _s, power in items)
            return super()._verify_pending(items, tally)

    bv = Recording()
    bv.add(pk2, m2, s2, power=2)     # group A, first seen
    bv.add(pk3, m3, bad, power=16)   # group B (invalid), first seen
    bv.add(pk1, m1, s1, power=1)     # alone
    bv.add(pk2, m2, s2, power=5)     # group A
    bv.add(pk4, m4, s4, power=64)    # hit
    bv.add(pk3, m3, bad, power=32)   # group B
    bv.add(pk2, m2, s2, power=11)    # group A
    st0 = sigcache.stats()
    all_ok, mask, tallied = bv.verify_tally()
    st1 = sigcache.stats()
    assert not all_ok
    assert mask == [True, False, True, True, True, False, True]
    assert tallied == (2 + 5 + 11) + 1 + 64
    assert seen == [(pk2.bytes(), 18), (pk3.bytes(), 48), (pk1.bytes(), 1)]
    assert bv.cache_stats == {"lanes": 7, "hits": 1, "dedup": 3,
                              "dispatched": 3}
    assert st1["hits"] - st0["hits"] == 1
    assert st1["misses"] - st0["misses"] == 6
    assert st1["inserts"] - st0["inserts"] == 2
    assert sigcache.DEFAULT.check(ED, pk2.bytes(), m2, s2)
    assert not sigcache.DEFAULT.check(ED, pk3.bytes(), m3, bad)


def test_verify_one_caches_and_rejects():
    pk, msg, sig = _ed(20)
    assert crypto_batch.verify_one(pk, msg, sig)
    assert sigcache.DEFAULT.check(ED, pk.bytes(), msg, sig)
    bad = bytes([sig[0] ^ 0x01]) + sig[1:]
    assert not crypto_batch.verify_one(pk, msg, bad)
    assert not sigcache.DEFAULT.check(ED, pk.bytes(), msg, bad)


# --- validator-set rotation --------------------------------------------------


def test_rotation_cache_cannot_substitute_membership():
    """Rotation safety: entries assert signature math, never membership.
    After the validator set rotates, the OLD validator's cached entries
    still hit (the math is still true) — but a verifier checking the
    NEW set looks up the NEW validator's pubkey, whose triple was never
    cached, so nothing short-circuits to a wrong accept."""
    old_pk, msg, old_sig = _ed(30, msg=b"commit sign bytes h=5")
    assert crypto_batch.verify_one(old_pk, msg, old_sig)  # pre-rotation
    # rotate: a fresh key takes over the slot
    new_priv = ed.gen_priv_key_from_secret(b"sigcache-rotated")
    new_pk = new_priv.pub_key()
    # the old signature does NOT verify under the new validator's key,
    # cache warm or not — different pubkey → different cache key → miss
    bv = crypto_batch.CPUBatchVerifier()
    bv.add(new_pk, msg, old_sig)
    all_ok, mask = bv.verify()
    assert not all_ok and mask == [False]
    # and the old entry is still there, still TRUE, still harmless
    assert sigcache.DEFAULT.check(ED, old_pk.bytes(), msg, old_sig)


# --- eviction property test --------------------------------------------------


def test_eviction_churn_never_false_positive():
    """Random insert/evict/query interleavings against a reference
    model: ``contains`` may forget (eviction) but must NEVER report a
    key that was not previously inserted as verified — a stale
    false-positive would let an unverified signature through."""
    rng = random.Random(0xC0FFEE)
    cache = sigcache.SigCache(max_entries=32, shards=4)
    inserted = set()     # every key EVER added as verified
    universe = [sigcache.cache_key(ED, b"pk%d" % i, b"m%d" % (i % 7),
                                   b"s%d" % i) for i in range(256)]
    for step in range(5000):
        op = rng.random()
        k = universe[rng.randrange(len(universe))]
        if op < 0.45:
            cache.add(k)
            inserted.add(k)
        elif op < 0.5:
            cache.invalidate_all()   # operator churn
        else:
            if cache.contains(k):
                assert k in inserted, \
                    f"false positive for never-inserted key at step {step}"
    # capacity is bounded no matter the interleaving
    assert cache.size() <= 32
    st = cache.stats()
    assert st["evictions"] > 0, "churn test never evicted — not churning"


# --- adaptive flush scheduler ------------------------------------------------


class _FakeTime:
    def __init__(self):
        self.t = 100.0

    def monotonic(self):
        return self.t


def test_scheduler_inert_without_rtt_samples(monkeypatch):
    s = crypto_batch.AdaptiveFlushScheduler()
    assert s.target_lanes() == s.min_lanes
    assert s.gather_wait_s(1) == 0.0
    # arrivals alone (no device RTT) keep it inert: CPU-only nodes and
    # fresh processes keep the legacy flush-now behavior
    ft = _FakeTime()
    monkeypatch.setattr(crypto_batch._time_mod, "monotonic", ft.monotonic)
    for _ in range(100):
        ft.t += 0.001
        s.note_arrivals(1)
    assert s.gather_wait_s(1) == 0.0


def test_scheduler_targets_rate_times_rtt(monkeypatch):
    ft = _FakeTime()
    monkeypatch.setattr(crypto_batch._time_mod, "monotonic", ft.monotonic)
    s = crypto_batch.AdaptiveFlushScheduler()
    s.min_lanes, s.max_lanes, s.max_wait_s = 8, 4096, 0.008
    for _ in range(200):
        ft.t += 0.001          # 1000 lanes/s steady state
        s.note_arrivals(1)
    for _ in range(50):
        s.note_dispatch(64, 0.05)   # 50 ms round-trips
    snap = s.snapshot()
    assert 900 <= snap["rate_lanes_per_s"] <= 1100
    assert 0.04 <= snap["rtt_s"] <= 0.06
    # target ≈ rate × rtt ≈ 50 lanes, inside [min, max]
    assert 40 <= s.target_lanes() <= 60
    # below target → bounded positive wait; at/above target → 0
    w = s.gather_wait_s(10)
    assert 0.0 < w <= s.max_wait_s
    assert s.gather_wait_s(4096) == 0.0
    # compile outliers are clamped, not believed
    s.note_dispatch(64, 500.0)
    assert s.snapshot()["rtt_s"] <= 2.0
    # disabling returns it to flush-now
    s.enabled = False
    assert s.gather_wait_s(1) == 0.0
    assert s.target_lanes() == s.min_lanes


def test_scheduler_idle_gaps_do_not_poison_rate(monkeypatch):
    ft = _FakeTime()
    monkeypatch.setattr(crypto_batch._time_mod, "monotonic", ft.monotonic)
    s = crypto_batch.AdaptiveFlushScheduler()
    for _ in range(50):
        ft.t += 0.001
        s.note_arrivals(1)
    rate_before = s.snapshot()["rate_lanes_per_s"]
    ft.t += 600.0              # ten quiet minutes
    s.note_arrivals(1)
    assert s.snapshot()["rate_lanes_per_s"] == rate_before


# --- configuration plumbing --------------------------------------------------


def test_configure_applies_sigcache_and_scheduler_knobs():
    from tmtpu.config.config import CryptoConfig

    cfg = CryptoConfig(sigcache_enable=True, sigcache_max_entries=512,
                       sigcache_shards=4, adaptive_flush=False,
                       flush_max_wait_ns=3_000_000, flush_max_lanes=99)
    try:
        crypto_batch.configure(cfg)
        st = sigcache.stats()
        assert st["max_entries"] == 512 and st["shards"] == 4
        assert crypto_batch.SCHEDULER.enabled is False
        assert crypto_batch.SCHEDULER.max_wait_s == pytest.approx(0.003)
        assert crypto_batch.SCHEDULER.max_lanes == 99
        cfg_off = CryptoConfig(sigcache_enable=False)
        crypto_batch.configure(cfg_off)
        assert not sigcache.DEFAULT.enabled()
    finally:
        crypto_batch.configure(CryptoConfig())
        crypto_batch.SCHEDULER.enabled = True


# --- verify-once across vote ingestion -> ApplyBlock ------------------------


def test_self_committed_applyblock_hit_rate():
    """ISSUE 4 acceptance: signatures verified at vote ingestion must be
    cache hits when verify_commit re-proves them during the self-committed
    height's ApplyBlock — >= 95% hit rate, ~zero backend dispatches."""
    import time as _t

    from tmtpu.types import commit_verify  # noqa: F401 — attaches
    # ValidatorSet.verify_commit
    from tmtpu.types.block import BlockID
    from tmtpu.types.priv_validator import MockPV
    from tmtpu.types.validator import Validator, ValidatorSet
    from tmtpu.types.vote import PRECOMMIT, Vote
    from tmtpu.types.vote_set import VoteSet

    chain_id = "sigcache-apply-chain"
    n = 20
    pvs = [MockPV() for _ in range(n)]
    vals = ValidatorSet([Validator(pv.get_pub_key(), 10) for pv in pvs])
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    bid = BlockID(b"\x07" * 32, 1, b"\x08" * 32)

    # vote ingestion: VoteSet.add_vote verifies each signature once and
    # the verify-once path records it
    vs = VoteSet(chain_id, 1, 0, PRECOMMIT, vals)
    for i, val in enumerate(vals.validators):
        v = Vote(type=PRECOMMIT, height=1, round=0, block_id=bid,
                 timestamp=_t.time_ns(), validator_address=val.address,
                 validator_index=i)
        by_addr[val.address].sign_vote(chain_id, v)
        vs.add_vote(v)
    commit = vs.make_commit()

    # ApplyBlock re-proof: count what actually reaches the backend
    lanes = [0]
    real = crypto_batch.CPUBatchVerifier._verify_pending

    def counting(self, items, tally):
        lanes[0] += len(items)
        return real(self, items, tally)

    st0 = sigcache.stats()
    crypto_batch.CPUBatchVerifier._verify_pending = counting
    try:
        vals.verify_commit(chain_id, bid, 1, commit)
    finally:
        crypto_batch.CPUBatchVerifier._verify_pending = real
    st1 = sigcache.stats()

    hits = st1["hits"] - st0["hits"]
    misses = st1["misses"] - st0["misses"]
    assert hits + misses == n
    assert hits / (hits + misses) >= 0.95, (hits, misses)
    assert lanes[0] == 0, f"{lanes[0]} lanes dispatched for a cached commit"

"""Light client (reference: light/client.go, light/detector.go).

Trusted store + primary/witness providers. VerifyLightBlockAtHeight
(client.go:474) runs sequential (:613) or skipping/bisection (:706)
verification; the detector (detector.go:28) cross-checks the verified
header against witnesses and builds LightClientAttackEvidence on
divergence.

TPU-first deviation: sequential verification uses
verifier.verify_adjacent_run — the whole fetched run's commits verify in
ONE fused batch dispatch instead of the reference's per-hop loop. A run
is sized in lanes as blocksync's is (blocksync/common.run_shape: as many
headers as the validator set's size leaves of RUN_LANES), every run's
dispatch is padded to that one shape, and a sequential client compiles it
when it is built: no run, first, short or last, meets a shape the process
has not compiled.

Stricter than the reference, never weaker: a header is trusted only after
the adjacent checks of light/verifier.go:93, more than 2/3 of its own
set's power signed it and EVERY for-block signature of its commit verifies
(VerifyCommitLight stops at 2/3); a session that fails stores nothing.
"""

from __future__ import annotations

import os
import time
from typing import List, Optional, Tuple

from tmtpu.blocksync.common import run_shape, warm_run
from tmtpu.libs import metrics, trace
from tmtpu.light import provider as prov
from tmtpu.light import verifier
from tmtpu.light.store import LightStore
from tmtpu.light.verifier import (
    DEFAULT_TRUST_LEVEL, ErrNewValSetCantBeTrusted, ErrVerificationFailed,
    LightError,
)
from tmtpu.types.evidence import LightClientAttackEvidence
from tmtpu.types.light_block import LightBlock

SEQUENTIAL = "sequential"
SKIPPING = "skipping"

DEFAULT_MAX_CLOCK_DRIFT_NS = 10 * 1_000_000_000  # client.go defaultMaxClockDrift
DEFAULT_PRUNING_SIZE = 1000

# client.go:40 verifySkipping pivot = 1/2 between trusted and target
_PIVOT_NUM, _PIVOT_DEN = 1, 2


class ErrNoWitnesses(LightError):
    pass


class ErrLightClientAttack(LightError):
    """Divergence between primary and a witness was confirmed — evidence
    has been formed and reported (detector.go ErrLightClientAttackDetected)."""

    def __init__(self, evidence: List[LightClientAttackEvidence]):
        super().__init__("light client attack detected")
        self.evidence = evidence


class TrustOptions:
    """client.go TrustOptions — period + (height, hash) from a trusted
    social-consensus source."""

    def __init__(self, period_ns: int, height: int, hash: bytes):
        self.period_ns = int(period_ns)
        self.height = int(height)
        self.hash = bytes(hash)

    def validate_basic(self) -> None:
        if self.period_ns <= 0:
            raise LightError("trusting period must be > 0")
        if self.height <= 0:
            raise LightError("trust height must be > 0")
        if len(self.hash) != 32:
            raise LightError("trust hash must be 32 bytes")


class Client:
    def __init__(self, chain_id: str, trust_options: TrustOptions,
                 primary: prov.Provider,
                 witnesses: Optional[List[prov.Provider]] = None,
                 store: Optional[LightStore] = None,
                 mode: str = SKIPPING,
                 trust_level: Tuple[int, int] = DEFAULT_TRUST_LEVEL,
                 max_clock_drift_ns: int = DEFAULT_MAX_CLOCK_DRIFT_NS,
                 pruning_size: int = DEFAULT_PRUNING_SIZE,
                 backend: Optional[str] = None):
        from tmtpu.libs.db import MemDB

        trust_options.validate_basic()
        verifier.validate_trust_level(*trust_level)
        self.chain_id = chain_id
        self.trust_options = trust_options
        self.primary = primary
        self.witnesses = list(witnesses or [])
        self.store = store or LightStore(MemDB())
        self.mode = mode
        self.trust_level = trust_level
        self.max_clock_drift_ns = max_clock_drift_ns
        self.pruning_size = pruning_size
        self.backend = backend
        self._latest_trusted: Optional[LightBlock] = None
        self._restore_trusted()
        if self._latest_trusted is None:
            self._initialize()
        if mode == SEQUENTIAL:
            # the one shape every run of this set flushes, before the
            # first run's fetch (a no-op off the device backend)
            warm_run(self._latest_trusted.validator_set, backend)

    # -- setup --------------------------------------------------------------

    def _restore_trusted(self) -> None:
        h = self.store.last_light_block_height()
        if h > 0:
            self._latest_trusted = self.store.light_block(h)

    def _initialize(self) -> None:
        """client.go:362 initializeWithTrustOptions."""
        lb = self._from_primary(self.trust_options.height)
        if lb.header.hash() != self.trust_options.hash:
            raise LightError(
                f"expected header's hash "
                f"{self.trust_options.hash.hex().upper()}, got "
                f"{lb.header.hash().hex().upper()}")
        lb.validate_basic(self.chain_id)
        # one correct validator in the trusted set must have signed
        from tmtpu.types import commit_verify

        commit_verify.verify_commit_light_trusting(
            lb.validator_set, self.chain_id, lb.commit,
            self.trust_level[0], self.trust_level[1], backend=self.backend)
        self._compare_first_header_with_witnesses(lb)
        self._update_trusted(lb)

    def _compare_first_header_with_witnesses(self, lb: LightBlock) -> None:
        """client.go:1131 — all witnesses must agree on the first header."""
        for w in self.witnesses:
            try:
                wb = self._fetch(w, lb.height())
            except prov.ProviderError:
                continue
            if wb.header.hash() != lb.header.hash():
                raise LightError(
                    f"witness {w.id()} has a different header at trusted "
                    f"height {lb.height()}")

    # -- public API ---------------------------------------------------------

    def trusted_light_block(self, height: int) -> Optional[LightBlock]:
        return self.store.light_block(height)

    def last_trusted_height(self) -> int:
        return self.store.last_light_block_height()

    def first_trusted_height(self) -> int:
        return self.store.first_light_block_height()

    def update(self, now_ns: Optional[int] = None) -> Optional[LightBlock]:
        """client.go:436 Update — fetch and verify the primary's latest."""
        now_ns = now_ns if now_ns is not None else time.time_ns()
        latest = self._from_primary(None)
        if self._latest_trusted is not None and \
                latest.height() <= self._latest_trusted.height():
            return None
        return self.verify_light_block(latest, now_ns)

    def verify_light_block_at_height(self, height: int,
                                     now_ns: Optional[int] = None
                                     ) -> LightBlock:
        """client.go:474 VerifyLightBlockAtHeight."""
        if height <= 0:
            raise LightError("height must be positive")
        now_ns = now_ns if now_ns is not None else time.time_ns()
        existing = self.store.light_block(height)
        if existing is not None:
            return existing
        lb = self._from_primary(height)
        return self.verify_light_block(lb, now_ns)

    def verify_light_block(self, lb: LightBlock, now_ns: int) -> LightBlock:
        """client.go:558 verifyLightBlock — route to sequential, skipping,
        or backwards verification."""
        metrics.light_sessions.inc()
        with trace.span("light.session", height=lb.height()):
            return self._verify_light_block(lb, now_ns)

    def _verify_light_block(self, lb: LightBlock, now_ns: int) -> LightBlock:
        lb.validate_basic(self.chain_id)
        if self._latest_trusted is None:
            raise LightError("no trusted state")
        height = lb.height()
        first = self.store.first_light_block_height()
        if height < first:
            # target below everything trusted: hash-link backwards
            return self._backwards(self.store.light_block(first), lb, now_ns)
        # closest trusted block at-or-below target (client.go:576-599)
        base = self.store.light_block_before(height + 1)
        if base is None:
            raise LightError("no trusted block below target")
        if base.height() == height:
            return base
        if verifier.header_expired(base.signed_header,
                                   self.trust_options.period_ns, now_ns):
            raise verifier.ErrOldHeaderExpired(
                base.header.time + self.trust_options.period_ns, now_ns)
        if self.mode == SEQUENTIAL:
            verified = self._verify_sequential(base, lb, now_ns)
        else:
            verified = self._verify_skipping_against_primary(base, lb,
                                                             now_ns)
        with trace.span("light.detect", witnesses=len(self.witnesses)):
            self._detect_divergence(verified, now_ns)
        for b in verified[1:]:
            self._update_trusted(b)
        return lb

    # -- sequential (client.go:613), fused ----------------------------------

    def _verify_sequential(self, trusted: LightBlock, target: LightBlock,
                           now_ns: int) -> List[LightBlock]:
        verified = [trusted]
        cur = trusted
        while cur.height() < target.height():
            n_blocks, lanes = run_shape(cur.validator_set)
            hi = min(cur.height() + n_blocks, target.height())
            run = [target if h == target.height() else self._from_primary(h)
                   for h in range(cur.height() + 1, hi + 1)]
            metrics.light_run_blocks.observe(len(run))
            n_ok = verifier.verify_adjacent_run(
                cur, run, self.trust_options.period_ns, now_ns,
                self.max_clock_drift_ns, backend=self.backend,
                min_lanes=lanes)
            if n_ok < len(run):
                # pinpoint the failing hop for a precise error
                bad = run[n_ok]
                prev = run[n_ok - 1] if n_ok > 0 else cur
                try:
                    verifier.verify_adjacent(
                        prev.signed_header, bad.signed_header,
                        bad.validator_set, self.trust_options.period_ns,
                        now_ns, self.max_clock_drift_ns,
                        backend=self.backend)
                except LightError as e:
                    raise ErrVerificationFailed(prev.height(), bad.height(),
                                                e) from e
                raise LightError(   # fused and precise paths disagree
                    f"run verification failed at height {bad.height()}")
            verified.extend(run)
            cur = run[-1]
        return verified

    # -- skipping / bisection (client.go:706) --------------------------------

    def _verify_skipping_against_primary(self, trusted: LightBlock,
                                         target: LightBlock,
                                         now_ns: int) -> List[LightBlock]:
        return self._verify_skipping(self.primary, trusted, target, now_ns)

    def _verify_skipping(self, source: prov.Provider, trusted: LightBlock,
                         target: LightBlock, now_ns: int
                         ) -> List[LightBlock]:
        block_cache = [target]
        depth = 0
        verified = trusted
        trace = [trusted]
        while True:
            try:
                verifier.verify(
                    verified.signed_header, verified.validator_set,
                    block_cache[depth].signed_header,
                    block_cache[depth].validator_set,
                    self.trust_options.period_ns, now_ns,
                    self.max_clock_drift_ns, self.trust_level,
                    backend=self.backend)
            except ErrNewValSetCantBeTrusted:
                # hop too far: bisect towards the trusted block
                if depth == len(block_cache) - 1:
                    pivot = verified.height() + \
                        (block_cache[depth].height() - verified.height()) * \
                        _PIVOT_NUM // _PIVOT_DEN
                    block_cache.append(self._fetch(source, pivot))
                depth += 1
                continue
            # verified this hop
            if depth == 0:
                trace.append(target)
                return trace
            verified = block_cache[depth]
            block_cache = block_cache[:depth]
            depth = 0
            trace.append(verified)

    # -- backwards (client.go:933) -------------------------------------------

    def _backwards(self, trusted: LightBlock, target: LightBlock,
                   now_ns: int) -> LightBlock:
        cur = trusted
        for h in range(trusted.height() - 1, target.height() - 1, -1):
            interim = target if h == target.height() \
                else self._from_primary(h)
            verifier.verify_backwards(interim.signed_header,
                                      cur.signed_header)
            self._update_trusted(interim, prune=False)
            cur = interim
        return target

    # -- detector (light/detector.go) ----------------------------------------

    def _detect_divergence(self, trace: List[LightBlock],
                           now_ns: int) -> None:
        """detector.go:28 detectDivergence — compare the last verified
        header against every witness; confirmed conflicts produce
        LightClientAttackEvidence, reported to the other providers."""
        if not self.witnesses or len(trace) < 2:
            return
        last = trace[-1]
        evidence: List[LightClientAttackEvidence] = []
        for wi, w in enumerate(self.witnesses):
            try:
                wb = self._fetch(w, last.height())
            except prov.ProviderError:
                continue
            if wb.header.hash() == last.header.hash():
                continue
            # conflicting headers: verify the witness's chain from the
            # common trusted root, then find the bifurcation point
            evs = self._handle_conflicting_block(trace, w, wb, now_ns)
            if evs:
                evidence.extend(evs)
        if evidence:
            raise ErrLightClientAttack(evidence)

    def _handle_conflicting_block(self, primary_trace: List[LightBlock],
                                  witness: prov.Provider,
                                  witness_block: LightBlock,
                                  now_ns: int
                                  ) -> List[LightClientAttackEvidence]:
        """detector.go:217 handleConflictingHeaders + :290
        examineConflictingHeaderAgainstTrace."""
        common = primary_trace[0]
        try:
            witness_trace = self._verify_skipping(
                witness, common, witness_block, now_ns)
        except (LightError, prov.ProviderError):
            return []  # witness can't prove its chain: drop it as bad
        # bifurcation: walk the primary trace to the last height where both
        # chains agree
        agreed = common
        for b in primary_trace[1:]:
            try:
                other = self._fetch(witness, b.height())
            except prov.ProviderError:
                break
            if other.header.hash() != b.header.hash():
                break
            agreed = b
        # evidence against the primary (witness's view conflicts) and
        # against the witness (primary's view conflicts): send each to the
        # other side (detector.go:256-276)
        ev_vs_primary = _new_attack_evidence(
            conflicted=primary_trace[-1], trusted=witness_trace[-1],
            common=agreed)
        ev_vs_witness = _new_attack_evidence(
            conflicted=witness_trace[-1], trusted=primary_trace[-1],
            common=agreed)
        for p, ev in ((witness, ev_vs_primary), (self.primary, ev_vs_witness)):
            try:
                p.report_evidence(ev)
            except (prov.ProviderError, NotImplementedError):
                pass
        return [ev_vs_primary, ev_vs_witness]

    # -- internals -----------------------------------------------------------

    def _update_trusted(self, lb: LightBlock, prune: bool = True) -> None:
        with trace.span("light.store", height=lb.height()):
            self.store.save_light_block(lb)
            if self._latest_trusted is None or \
                    lb.height() > self._latest_trusted.height():
                self._latest_trusted = lb
            if prune and self.pruning_size and \
                    self.store.size() > self.pruning_size:
                self.store.prune(self.pruning_size)
        metrics.light_blocks_verified.inc()

    def _from_primary(self, height: Optional[int]) -> LightBlock:
        return self._fetch(self.primary, height)

    def _fetch(self, source: prov.Provider,
               height: Optional[int]) -> LightBlock:
        metrics.light_provider_calls.inc(
            role="primary" if source is self.primary else "witness")
        with trace.span("light.fetch"):
            lb = source.light_block(height)
        if height is not None and lb.height() != height:
            raise prov.ErrBadLightBlock(
                f"expected height {height}, got {lb.height()}")
        return lb


def open_client(home: str, chain_id: str, trust_options: TrustOptions,
                primary: prov.Provider, witnesses: List[prov.Provider],
                sequential: bool = False) -> Client:
    """The client ``tmtpu light`` runs (commands/light.go): its trusted
    store on SQLite under ``<home>/data/light.sqlite``, sequential
    verification when the operator asked for it and skipping otherwise,
    everything else at the defaults. The providers are the caller's."""
    from tmtpu.libs.db import SQLiteDB

    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    store = LightStore(SQLiteDB(os.path.join(home, "data", "light.sqlite")))
    return Client(chain_id, trust_options, primary, witnesses=witnesses,
                  store=store, mode=SEQUENTIAL if sequential else SKIPPING)


def _new_attack_evidence(conflicted: LightBlock, trusted: LightBlock,
                         common: LightBlock) -> LightClientAttackEvidence:
    """detector.go:408 newLightClientAttackEvidence — lunatic attacks
    (different valsets) anchor at the common height; equivocation/amnesia
    at the conflicting height."""
    lunatic = conflicted.header.validators_hash != \
        trusted.header.validators_hash
    if lunatic:
        anchor = common
    else:
        anchor = trusted
    return LightClientAttackEvidence(
        conflicting_block=conflicted,
        common_height=anchor.height(),
        total_voting_power=anchor.validator_set.total_voting_power(),
        timestamp=anchor.header.time,
    )

"""kvstore example app (reference: abci/example/kvstore/kvstore.go and
persistent_kvstore.go) — the benchmark application.

- ``DeliverTx``: ``k=v`` sets key k; a bare tx sets tx=tx.
- AppHash = 8-byte big-endian count of txs ever applied (kvstore.go:123's
  size-based hash, byte-for-byte trivial but deterministic).
- Validator updates via ``val:<hex pubkey>!<power>`` txs (persistent
  variant's ValUpdates flow), returned from EndBlock.
- Query paths: raw key lookup or "/val/<addr-hex>".
"""

from __future__ import annotations

import struct
from typing import Dict, List, Optional

from tmtpu.abci import types as abci
from tmtpu.types import pb

VALIDATOR_TX_PREFIX = b"val:"


SNAPSHOT_CHUNK_SIZE = 64 * 1024
SNAPSHOT_FORMAT = 1


class KVStoreApplication(abci.Application):
    def __init__(self, db=None, snapshot_interval: int = 0,
                 snapshot_keep: int = 5):
        self.db = db  # optional tmtpu.libs.db KV store for persistence
        self.state: Dict[bytes, bytes] = {}
        self.size = 0
        self.height = 0
        self.app_hash = b"\x00" * 8
        self.val_updates: List[abci.ValidatorUpdate] = []
        self.validators: Dict[bytes, abci.ValidatorUpdate] = {}
        # snapshots for statesync (the reference kvstore doesn't snapshot;
        # its e2e app does — abci semantics per abci/types/application.go)
        self.snapshot_interval = snapshot_interval
        self.snapshot_keep = snapshot_keep
        self.snapshots: Dict[int, tuple] = {}  # height -> (Snapshot, chunks)
        self._restore_chunks: Optional[list] = None
        self._restore_snapshot = None
        if db is not None:
            self._load()

    # -- persistence --------------------------------------------------------

    def _load(self) -> None:
        raw = self.db.get(b"kvstore:meta")
        if raw:
            self.height, self.size = struct.unpack(">qq", raw[:16])
            self.app_hash = raw[16:24]
        for k, v in self.db.iter_prefix(b"kvstore:data:"):
            self.state[k[len(b"kvstore:data:"):]] = v
        for k, v in self.db.iter_prefix(b"kvstore:val:"):
            self.validators[k[len(b"kvstore:val:"):]] = \
                abci.ValidatorUpdate.decode(v)

    def _persist(self) -> None:
        if self.db is None:
            return
        self.db.set(b"kvstore:meta",
                    struct.pack(">qq", self.height, self.size) + self.app_hash)

    # -- abci ---------------------------------------------------------------

    def info(self, req: abci.RequestInfo) -> abci.ResponseInfo:
        return abci.ResponseInfo(
            data=f"{{\"size\":{self.size}}}", version="0.17.0", app_version=1,
            last_block_height=self.height,
            last_block_app_hash=self.app_hash if self.height else b"",
        )

    def init_chain(self, req: abci.RequestInitChain) -> abci.ResponseInitChain:
        for vu in req.validators:
            self._set_validator(vu)
        return abci.ResponseInitChain()

    def begin_block(self, req: abci.RequestBeginBlock) -> abci.ResponseBeginBlock:
        self.val_updates = []
        return abci.ResponseBeginBlock()

    def check_tx(self, req: abci.RequestCheckTx) -> abci.ResponseCheckTx:
        if req.tx.startswith(VALIDATOR_TX_PREFIX) and \
                not self._parse_val_tx(req.tx):
            return abci.ResponseCheckTx(code=1, log="invalid validator tx")
        return abci.ResponseCheckTx(code=abci.CODE_TYPE_OK, gas_wanted=1)

    def deliver_tx(self, req: abci.RequestDeliverTx) -> abci.ResponseDeliverTx:
        tx = bytes(req.tx)
        if tx.startswith(VALIDATOR_TX_PREFIX):
            vu = self._parse_val_tx(tx)
            if vu is None:
                return abci.ResponseDeliverTx(code=1, log="invalid validator tx")
            if vu.power == 0 and vu.pub_key.encode() not in self.validators:
                # persistent_kvstore.go updateValidator: no update for it,
                # which would fail UpdateWithChangeSet and halt the chain
                return abci.ResponseDeliverTx(
                    code=1, log="cannot remove non-existent validator")
            self.val_updates.append(vu)
            self._set_validator(vu)
        else:
            if b"=" in tx:
                k, _, v = tx.partition(b"=")
            else:
                k, v = tx, tx
            self.state[k] = v
            if self.db is not None:
                self.db.set(b"kvstore:data:" + k, v)
        self.size += 1
        events = [abci.Event(type="app", attributes=[
            abci.EventAttribute(key=b"key", value=tx.partition(b"=")[0],
                                index=True),
        ])]
        return abci.ResponseDeliverTx(code=abci.CODE_TYPE_OK, events=events)

    def end_block(self, req: abci.RequestEndBlock) -> abci.ResponseEndBlock:
        self.height = req.height
        return abci.ResponseEndBlock(validator_updates=self.val_updates)

    def commit(self) -> abci.ResponseCommit:
        self.app_hash = struct.pack(">q", self.size)
        self._persist()
        if self.snapshot_interval and self.height and \
                self.height % self.snapshot_interval == 0:
            self._take_snapshot()
        return abci.ResponseCommit(data=self.app_hash)

    # -- snapshots (statesync serving + restore) ---------------------------

    def _take_snapshot(self) -> None:
        import hashlib
        import json

        payload = json.dumps({
            "height": self.height, "size": self.size,
            "app_hash": self.app_hash.hex(),
            "state": {k.hex(): v.hex() for k, v in self.state.items()},
            "validators": {k.hex(): v.hex()
                           for k, v in ((key, vu.encode())
                                        for key, vu in self.validators.items())},
        }, sort_keys=True).encode()
        # chunks are always non-empty (the JSON payload is never empty):
        # zero-length chunks are indistinguishable from 'missing' on the
        # statesync wire (proto3 empty bytes)
        chunks = [payload[i:i + SNAPSHOT_CHUNK_SIZE]
                  for i in range(0, len(payload), SNAPSHOT_CHUNK_SIZE)]
        snap = abci.Snapshot(
            height=self.height, format=SNAPSHOT_FORMAT, chunks=len(chunks),
            hash=hashlib.sha256(payload).digest(), metadata=b"")
        self.snapshots[self.height] = (snap, chunks)
        # keep only the newest snapshot_keep snapshots
        keep = max(1, self.snapshot_keep)
        for h in sorted(self.snapshots)[:-keep]:
            del self.snapshots[h]

    def list_snapshots(self, req: abci.RequestListSnapshots
                       ) -> abci.ResponseListSnapshots:
        return abci.ResponseListSnapshots(
            snapshots=[s for s, _ in self.snapshots.values()])

    def load_snapshot_chunk(self, req: abci.RequestLoadSnapshotChunk
                            ) -> abci.ResponseLoadSnapshotChunk:
        entry = self.snapshots.get(req.height)
        if entry is None or req.format != SNAPSHOT_FORMAT or \
                not 0 <= req.chunk < len(entry[1]):
            return abci.ResponseLoadSnapshotChunk()
        return abci.ResponseLoadSnapshotChunk(chunk=entry[1][req.chunk])

    def offer_snapshot(self, req: abci.RequestOfferSnapshot
                       ) -> abci.ResponseOfferSnapshot:
        snap = req.snapshot
        if snap is None or snap.format != SNAPSHOT_FORMAT or \
                snap.chunks <= 0:
            return abci.ResponseOfferSnapshot(
                result=abci.OFFER_SNAPSHOT_REJECT_FORMAT)
        self._restore_snapshot = snap
        self._restore_chunks = []
        return abci.ResponseOfferSnapshot(result=abci.OFFER_SNAPSHOT_ACCEPT)

    def apply_snapshot_chunk(self, req: abci.RequestApplySnapshotChunk
                             ) -> abci.ResponseApplySnapshotChunk:
        import hashlib
        import json

        if self._restore_chunks is None or self._restore_snapshot is None:
            return abci.ResponseApplySnapshotChunk(
                result=abci.APPLY_CHUNK_ABORT)
        if req.index != len(self._restore_chunks):
            return abci.ResponseApplySnapshotChunk(
                result=abci.APPLY_CHUNK_RETRY)
        self._restore_chunks.append(bytes(req.chunk))
        if len(self._restore_chunks) < self._restore_snapshot.chunks:
            return abci.ResponseApplySnapshotChunk(
                result=abci.APPLY_CHUNK_ACCEPT)
        payload = b"".join(self._restore_chunks)
        if hashlib.sha256(payload).digest() != self._restore_snapshot.hash:
            self._restore_chunks = None
            return abci.ResponseApplySnapshotChunk(
                result=abci.APPLY_CHUNK_RETRY_SNAPSHOT)
        d = json.loads(payload)
        self.height = int(d["height"])
        self.size = int(d["size"])
        self.app_hash = bytes.fromhex(d["app_hash"])
        self.state = {bytes.fromhex(k): bytes.fromhex(v)
                      for k, v in d["state"].items()}
        self.validators = {
            bytes.fromhex(k): abci.ValidatorUpdate.decode(bytes.fromhex(v))
            for k, v in d["validators"].items()}
        if self.db is not None:
            for k, v in self.state.items():
                self.db.set(b"kvstore:data:" + k, v)
            for k, vu in self.validators.items():
                self.db.set(b"kvstore:val:" + k, vu.encode())
            self._persist()
        self._restore_chunks = None
        self._restore_snapshot = None
        return abci.ResponseApplySnapshotChunk(
            result=abci.APPLY_CHUNK_ACCEPT)

    def query(self, req: abci.RequestQuery) -> abci.ResponseQuery:
        if req.path == "/val":
            vu = self.validators.get(req.data)
            return abci.ResponseQuery(
                code=abci.CODE_TYPE_OK, key=req.data,
                value=vu.encode() if vu else b"", height=self.height,
            )
        value = self.state.get(bytes(req.data), b"")
        return abci.ResponseQuery(
            code=abci.CODE_TYPE_OK, key=bytes(req.data), value=value,
            log="exists" if value else "does not exist", height=self.height,
        )

    # -- validator tx helpers ----------------------------------------------

    def _parse_val_tx(self, tx: bytes) -> Optional[abci.ValidatorUpdate]:
        try:
            body = tx[len(VALIDATOR_TX_PREFIX):].decode()
            pk_hex, _, power = body.partition("!")
            return abci.ValidatorUpdate(
                pub_key=pb.PublicKey(ed25519=bytes.fromhex(pk_hex)),
                power=int(power),
            )
        except (ValueError, UnicodeDecodeError):
            return None

    def _set_validator(self, vu: abci.ValidatorUpdate) -> None:
        key = vu.pub_key.encode()
        if vu.power == 0:
            self.validators.pop(key, None)
            if self.db is not None:
                self.db.delete(b"kvstore:val:" + key)
        else:
            self.validators[key] = vu
            if self.db is not None:
                self.db.set(b"kvstore:val:" + key, vu.encode())


def make_validator_tx(pubkey_bytes: bytes, power: int) -> bytes:
    return VALIDATOR_TX_PREFIX + f"{pubkey_bytes.hex()}!{power}".encode()

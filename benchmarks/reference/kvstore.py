"""The plain reference of the served path: how a signed-tx envelope is
made (mempool admission's wire layout), what the kvstore app holds after a
sequence of committed txs, and what "committed exactly once" means.
Nothing here imports ``tmtpu``.

Envelope (the layout a chain with signed-tx admission documents)::

    MAGIC(4) = d4 'T' 'X' '1' | curve(1) = 01 | pubkey(32) | sig(64) | payload

with the ed25519 signature over ``"tmtpu/signed-tx/v1\\0" + payload``. A
tx that does not start with MAGIC is a plain tx and is not verified.
The kvstore app (abci/example/kvstore) splits the FULL tx at its first
``=`` into key and value (no ``=``: key = value = tx) and keeps the last
value written to a key.
"""

from __future__ import annotations

import hashlib
from typing import Dict, Iterable, List, Tuple

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

MAGIC = b"\xd4TX1"
CURVE_ED25519 = 0x01
DOMAIN = b"tmtpu/signed-tx/v1\x00"
HEADER = len(MAGIC) + 1 + 32 + 64


def sender_key(seed: int, s: int) -> Ed25519PrivateKey:
    return Ed25519PrivateKey.from_private_bytes(
        hashlib.sha256(b"bench-sender-%d-%d" % (seed, s)).digest())


def envelope(payload: bytes, key: Ed25519PrivateKey, pub: bytes) -> bytes:
    return MAGIC + bytes([CURVE_ED25519]) + pub + \
        key.sign(DOMAIN + payload) + payload


def tamper(tx: bytes) -> bytes:
    """The envelope with the last payload bit flipped: the signature no
    longer covers it, and admission has to refuse it."""
    return tx[:-1] + bytes([tx[-1] ^ 1])


def split(tx: bytes) -> Tuple[bytes, bytes]:
    k, eq, v = tx.partition(b"=")
    return (k, v) if eq else (tx, tx)


def final_state(committed: Iterable[bytes]) -> Dict[bytes, bytes]:
    """The app's state after the txs in commit order."""
    state: Dict[bytes, bytes] = {}
    for tx in committed:
        k, v = split(tx)
        state[k] = v
    return state


def exactly_once(acked: Iterable[bytes], committed: List[bytes]
                 ) -> Tuple[int, int]:
    """-> (acknowledged txs in no block, acknowledged txs in more than
    one), by tx hash."""
    seen: Dict[bytes, int] = {}
    for tx in committed:
        seen[tx] = seen.get(tx, 0) + 1
    missing = dup = 0
    for tx in acked:
        n = seen.get(tx, 0)
        missing += n == 0
        dup += n > 1
    return missing, dup

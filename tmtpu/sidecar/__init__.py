"""Verification sidecar — a standalone batch-verify daemon.

The BASELINE.json north-star ships signature batches to "a JAX/Pallas
sidecar"; this package is that daemon. One process owns the jax device
(paying the ~35 s kernel compile exactly once per daemon lifetime) and
serves batched ed25519/sr25519/secp256k1 verification plus fused
verify+tally to any number of node processes over a length-prefixed
unix-socket/TCP protocol (tmtpu/libs/framed, tmtpu/sidecar/protocol.py).

Why a daemon instead of per-process device access: committee-based
consensus work (arXiv:2302.00418) shows batch amplitude is the dominant
throughput lever for ed25519, and on a multi-validator host the only way
to reach large batches is to COALESCE lanes across processes — four
localnet nodes each verifying ~100 lanes/block become one daemon
dispatching ~400-lane joint batches. The server-side coalescer
(coalescer.py) gathers lanes from concurrent clients under the adaptive
flush EWMAs from crypto/batch.py and returns exact per-lane masks to
each submitter.

Layers (the socket, handshake, frame loop, client connection and the
gather queue under them are :mod:`tmtpu.libs.framed`'s):

- ``protocol.py`` — the sidecar's wire messages (VerifyRequest /
  VerifyResponse, its Hello/HelloAck/Pong), versions and frame cap.
- ``coalescer.py`` — the per-curve joint dispatch and mask slicing.
- ``server.py`` — the daemon: the verify engine (crypto/batch verifiers
  — so the sidecar gets the sigcache, the per-curve breakers and the
  serial fallback for free), warm-start compilation, ``/debug/profile``.
- ``client.py`` — ``SidecarClient.verify`` over the framed client.

Node processes select the daemon with ``crypto.backend=sidecar``
(config) — ``crypto/batch.py SidecarBatchVerifier`` slots UNDER the
sigcache→dedup→breaker stack and falls back to in-process verify (then
serial CPU) when the daemon is down or slow, so killing the daemon
mid-run degrades throughput but never correctness.
"""

from tmtpu.sidecar.protocol import PROTOCOL_VERSION  # noqa: F401

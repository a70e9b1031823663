"""Native (C) host-side helpers for the TPU crypto pipeline.

``hostprep`` — the host half of ed25519 batch verification (the device
half is tmtpu/tpu/kernel.py) in one C call a flush: SHA-512 challenge
hashing + mod-L reduction + the canonical-s check over the flush's keys,
signatures and messages as three contiguous buffers, every lane written
straight into the byte planes of the padded device operand
(``prep_ed25519``). Reference semantics: crypto/ed25519/ed25519.go:148-155
(h = SHA-512(R||A||M)) and scMinimal (s < L); spec oracle
tmtpu/crypto/ed25519_ref.py. The SHA-512 is the system libcrypto's where
the library finds one at run time (about twice the portable C, which
stays as the path without it: ``sha_impls``).

The library is built lazily with the system C compiler (cc -O2 -shared
-pthread) into this directory and loaded over ctypes; when no toolchain is
available, callers fall back to the vectorized numpy/hashlib path in
tmtpu/tpu/verify.py — same results, more host CPU. The start-up log of a
node or sidecar says which of the two it got (crypto/batch.py
``start_backend``).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "hostprep.c")
_SO = os.path.join(_DIR, "_hostprep.so")

_lock = threading.Lock()
_lib = None
_tried = False


def _build() -> bool:
    # Compile to a temp name and rename into place: rewriting _SO in place
    # keeps its inode, and glibc dlopen caches by dev/ino — a process that
    # already loaded a stale .so would get the cached stale handle back on
    # the post-rebuild CDLL instead of the fresh code.
    tmp = _SO + ".build"
    for cc in ("cc", "gcc", "g++", "clang"):
        try:
            r = subprocess.run(
                [cc, "-O2", "-shared", "-fPIC", "-pthread", "-o", tmp, _SRC],
                capture_output=True, timeout=120,
            )
        except (OSError, subprocess.TimeoutExpired):
            continue
        if r.returncode == 0:
            os.replace(tmp, _SO)
            return True
    try:
        os.unlink(tmp)  # partial output from a failed/timed-out compile
    except OSError:
        pass
    return False


def load():
    """ctypes handle to the hostprep library, or None when unavailable.

    A pre-existing .so that fails to load or lacks the expected symbols
    (stale artifact from an older hostprep.c) triggers ONE rebuild from
    source before giving up — callers always get either a fully-bound
    library or None (pure-Python fallback), never a partial binding.
    """
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.path.exists(_SO) and (
            os.path.getmtime(_SO) >= os.path.getmtime(_SRC)
        ):
            lib = _load_and_bind()
            if lib is not None:
                _lib = lib
                return _lib
        if not _build():
            return None
        _lib = _load_and_bind()
        return _lib


def rebuild():
    """Build the library from ``hostprep.c`` NOW, whatever binary is on
    disk, and bind it: the handle, or None when the toolchain or the
    binding fails. ``load()`` trusts any ``_hostprep.so`` newer than the
    source; a run that must prove what git would commit (chip_smoke.py)
    cannot."""
    global _lib, _tried
    with _lock:
        _tried = True
        _lib = _load_and_bind() if _build() else None
        return _lib


def _load_and_bind():
    """CDLL + full symbol binding, or None on any load/symbol failure."""
    try:
        lib = ctypes.CDLL(_SO)
        lib.tmtpu_prep_ed25519.argtypes = [
            ctypes.c_size_t,
            ctypes.c_void_p,  # pks  n*32
            ctypes.c_void_p,  # sigs n*64
            ctypes.c_void_p,  # msgs concatenated
            ctypes.c_void_p,  # moff n+1 uint64
            ctypes.c_void_p,  # plane 128*stride
            ctypes.c_size_t,  # stride
            ctypes.c_void_p,  # s_ok  n
            ctypes.c_int,     # nthreads
            ctypes.c_int,     # portable_sha
        ]
        lib.tmtpu_prep_ed25519.restype = ctypes.c_int
        lib.tmtpu_sha512_libcrypto.argtypes = []
        lib.tmtpu_sha512_libcrypto.restype = ctypes.c_int
        lib.tmtpu_sr_challenges.argtypes = [
            ctypes.c_size_t,
            ctypes.c_void_p,  # pks  n*32
            ctypes.c_void_p,  # rs   n*32
            ctypes.c_void_p,  # msgs concatenated
            ctypes.c_void_p,  # moff n+1 uint64
            ctypes.c_void_p,  # k_out n*32
            ctypes.c_int,     # nthreads
        ]
        lib.tmtpu_sr_challenges.restype = None
        lib.tmtpu_ed25519_verify_batch.argtypes = [
            ctypes.c_size_t,
            ctypes.c_void_p,  # pks  n*32
            ctypes.c_void_p,  # sigs n*64
            ctypes.c_void_p,  # msgs concatenated
            ctypes.c_void_p,  # moff n+1 uint64
            ctypes.c_void_p,  # ok_out n uint8
            ctypes.c_int,     # nthreads
        ]
        lib.tmtpu_ed25519_verify_batch.restype = ctypes.c_int
        return lib
    except AttributeError:
        # stale library missing symbols: dlclose it, else glibc's pathname
        # cache would hand the same stale handle back after a rebuild
        try:
            libc = ctypes.CDLL(None)
            libc.dlclose.argtypes = [ctypes.c_void_p]
            libc.dlclose.restype = ctypes.c_int
            libc.dlclose(ctypes.c_void_p(lib._handle))
        except (OSError, AttributeError):
            pass
        return None
    except OSError:
        return None


def _pack_msgs(msgs, B):
    """(offsets [B+1] uint64, concatenated uint8 buffer) for a message list
    — the shared wire layout both batch entry points hand to C. One
    ``len`` pass and one join, whatever bytes-like type the lanes are."""
    moff = np.zeros(B + 1, dtype=np.uint64)
    np.cumsum(np.fromiter(map(len, msgs), dtype=np.uint64, count=B),
              out=moff[1:])
    blob = b"".join(msgs)
    if len(blob) != int(moff[B]):  # C indexes the blob by these offsets
        raise ValueError("a message's len() is not its size in bytes")
    msgs_buf = np.frombuffer(blob, dtype=np.uint8) if blob else \
        np.zeros(1, dtype=np.uint8)
    return moff, msgs_buf


SHA_IMPLS = ("libcrypto", "portable")


def sha_impls():
    """The SHA-512 implementations ``prep_ed25519`` can run on this host,
    the one it takes by default first; () without the library."""
    lib = load()
    if lib is None:
        return ()
    return SHA_IMPLS if lib.tmtpu_sha512_libcrypto() else SHA_IMPLS[1:]


def prep_ed25519(pk_arr: np.ndarray, sig_arr: np.ndarray, msgs,
                 plane: np.ndarray, nthreads: int | None = None,
                 sha: str | None = None):
    """One flush's lanes into its device operand: for lane i, column i of
    ``plane`` gets pk (rows 0-31), R (32-63), s (64-95) and
    h = SHA-512(R||A||M) mod L (96-127); a lane whose s >= L gets s = 0.

    pk_arr [B, 32], sig_arr [B, 64] (R||s) uint8 C-contiguous; msgs: B
    bytes-like objects; plane: uint8 [128, W >= B] C-contiguous, columns
    B.. are left alone. ``sha`` names one of ``sha_impls()`` (tests; the
    default is the first). Returns (s_ok bool [B], the SHA-512 that ran),
    or None when the native library is unavailable.
    """
    lib = load()
    if lib is None:
        return None
    B = pk_arr.shape[0]
    for arr, shape in ((pk_arr, (B, 32)), (sig_arr, (B, 64))):
        if arr.shape != shape or arr.dtype != np.uint8 \
                or not arr.flags.c_contiguous:
            raise ValueError(f"want C-contiguous uint8 {shape}")
    if plane.ndim != 2 or plane.shape[0] != 128 or plane.shape[1] < B \
            or plane.dtype != np.uint8 or not plane.flags.c_contiguous \
            or not plane.flags.writeable:
        raise ValueError("want a writeable C-contiguous uint8 [128, >=B]")
    if sha is not None and sha not in sha_impls():
        raise ValueError(f"no {sha!r} SHA-512 on this host")
    if nthreads is None:
        nthreads = min(8, os.cpu_count() or 1)
    moff, msgs_buf = _pack_msgs(msgs, B)
    s_ok = np.empty(B, dtype=np.uint8)
    used = lib.tmtpu_prep_ed25519(
        B, pk_arr.ctypes.data, sig_arr.ctypes.data,
        msgs_buf.ctypes.data, moff.ctypes.data,
        plane.ctypes.data, plane.shape[1], s_ok.ctypes.data,
        int(nthreads), int(sha == "portable"),
    )
    return s_ok.view(bool), SHA_IMPLS[0 if used else 1]


def sr_challenges(pk_arr: np.ndarray, r_arr: np.ndarray, msgs,
                  nthreads: int | None = None):
    """Batched sr25519 verify challenges: the merlin transcript walk of
    PubKeySr25519.verify_signature producing k = challenge mod L per lane
    (32 bytes LE). pk_arr/r_arr: [B, 32] uint8 C-contiguous; msgs: list of
    bytes. Returns k_arr [B, 32] uint8, or None when the native library is
    unavailable. ~50x the pure-Python merlin (tmtpu/crypto/merlin.py)."""
    lib = load()
    if lib is None:
        return None
    B = pk_arr.shape[0]
    if nthreads is None:
        nthreads = min(8, os.cpu_count() or 1)
    moff, msgs_buf = _pack_msgs(msgs, B)
    k_out = np.empty((B, 32), dtype=np.uint8)
    lib.tmtpu_sr_challenges(
        B, pk_arr.ctypes.data, r_arr.ctypes.data,
        msgs_buf.ctypes.data, moff.ctypes.data, k_out.ctypes.data,
        int(nthreads),
    )
    return k_out


def ed25519_verify_batch(pks, msgs, sigs, nthreads: int | None = None):
    """Batched ed25519 verification through ONE C call over the system
    libcrypto (EVP_DigestVerify), threaded across lanes. On this 1-core
    box it matches python-cryptography's serial rate (OpenSSL's verify
    itself is the cost, ~125 us/sig); on multi-core hosts the consensus
    CPU backend scales linearly with cores, which a GIL-bound Python
    loop cannot guarantee. Inputs are parallel lists of 32-byte pubkeys,
    message bytes, and 64-byte signatures. Returns list[bool], or None
    when the native library or libcrypto is unavailable (callers fall
    back to per-item Python verify). Reference semantics:
    crypto/ed25519/ed25519.go:70 Verify."""
    lib = load()
    if lib is None:
        return None
    B = len(pks)
    if B == 0:
        return []
    if nthreads is None:
        nthreads = min(8, os.cpu_count() or 1)
    pk_arr = np.frombuffer(b"".join(pks), dtype=np.uint8).reshape(B, 32)
    sig_arr = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(B, 64)
    moff, msgs_buf = _pack_msgs(msgs, B)
    ok = np.zeros(B, dtype=np.uint8)
    rc = lib.tmtpu_ed25519_verify_batch(
        B, pk_arr.ctypes.data, sig_arr.ctypes.data,
        msgs_buf.ctypes.data, moff.ctypes.data, ok.ctypes.data,
        int(nthreads))
    if rc != 0:
        return None  # libcrypto missing at runtime
    return [bool(v) for v in ok]

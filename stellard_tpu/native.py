"""Loader for the native C++ components (native/libstellard_native.so).

The reference's performance-critical host components are C++ (NodeStore
backends, OpenSSL hashing — SURVEY §2 [native-perf]); this module builds
and binds their equivalents. The library is compiled on first use with
`make` (toolchain is in the image) and cached; every consumer degrades
gracefully to the pure-Python path when the toolchain or build is
unavailable, mirroring the pluggable-backend seam. The degrade is for
installs without a toolchain, so it is never silent: a failed build
logs make's stderr once and keeps it in ``build_errors``.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

log = logging.getLogger("stellard.native")

__all__ = [
    "build_errors",
    "load_native",
    "load_stser",
    "native_available",
    "Sha512Native",
    "Ed25519HostPrep",
    "Ed25519NativeVerify",
    "CppLogLib",
    "SegIdxNative",
    "scan_segment_records",
]

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libstellard_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

# library file name -> why its build failed (make's stderr, or the
# OSError when there is no make at all). Empty when both built.
build_errors: dict[str, str] = {}


def _make(target_args: list[str], what: str) -> bool:
    """Run make in native/; on failure keep and log its stderr."""
    try:
        subprocess.run(
            ["make", "-s", *target_args],
            cwd=_NATIVE_DIR,
            check=True,
            capture_output=True,
            text=True,
            timeout=300,
        )
        return True
    except subprocess.CalledProcessError as exc:
        err = (exc.stderr or exc.stdout or "").strip() or repr(exc)
    except (OSError, subprocess.SubprocessError) as exc:
        err = repr(exc)
    build_errors[what] = err
    log.warning(
        "native build of %s FAILED — consumers fall back to %s:\n%s",
        what,
        "a stale prebuilt copy" if os.path.exists(
            os.path.join(_NATIVE_DIR, what)) else "pure Python",
        err[-4000:],
    )
    return False


def load_native() -> Optional[ctypes.CDLL]:
    """Build (once) and dlopen the native library; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.isdir(_NATIVE_DIR) and not os.path.exists(_LIB_PATH):
            return None
        # always let make run its (cheap) up-to-date check: a prebuilt .so
        # from an older source tree must be refreshed, or newly added
        # symbols would be missing from the dlopened library
        if os.path.isdir(_NATIVE_DIR):
            if not _make([], os.path.basename(_LIB_PATH)) and (
                not os.path.exists(_LIB_PATH)
            ):
                return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            return None
        try:
            _bind(lib)
        except AttributeError:
            # stale library missing newer symbols and unrebuildable:
            # degrade to the pure-Python paths rather than crash consumers
            return None
        _lib = lib
        return _lib


def native_available() -> bool:
    return load_native() is not None


_stser_mod = None
_stser_tried = False


def load_stser():
    """Build (once) and import the _stser CPython extension (the
    STObject serializer fast path); None when the toolchain or build is
    unavailable — callers keep the pure-Python encode loop."""
    global _stser_mod, _stser_tried
    with _lock:
        if _stser_mod is not None or _stser_tried:
            return _stser_mod
        _stser_tried = True
        path = os.path.join(_NATIVE_DIR, "_stser.so")
        if os.path.isdir(_NATIVE_DIR):
            # build against the RUNNING interpreter's headers — the
            # Makefile's `python3` may be a different installation,
            # and a version-mismatched extension dlopens anyway
            # (inline object-layout macros would then misread)
            import sysconfig

            _make(
                ["_stser.so", f"PY_INC={sysconfig.get_paths()['include']}"],
                "_stser.so",
            )
        if not os.path.exists(path):
            return None
        try:
            import importlib.machinery
            import importlib.util

            loader = importlib.machinery.ExtensionFileLoader("_stser", path)
            spec = importlib.util.spec_from_loader("_stser", loader)
            mod = importlib.util.module_from_spec(spec)
            loader.exec_module(mod)
        except (ImportError, OSError):
            return None
        _stser_mod = mod
        return _stser_mod


def _bind(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.sha512h_batch.argtypes = [
        ctypes.c_char_p,  # packed data
        ctypes.POINTER(ctypes.c_uint64),  # offsets[n+1]
        ctypes.POINTER(ctypes.c_uint32),  # prefixes[n]
        u8p,  # out
        ctypes.c_uint64,  # n
        ctypes.c_uint64,  # out_len
    ]
    lib.sha512h_batch.restype = None

    # newer symbols bind leniently: a stale prebuilt .so on a box where
    # `make` can't run keeps its older components (sha512/cpplog) usable
    try:
        lib.ed25519_h_batch.argtypes = [
            ctypes.c_char_p,  # packed 32B R values
            ctypes.c_char_p,  # packed 32B A (public key) values
            ctypes.c_char_p,  # packed messages
            ctypes.POINTER(ctypes.c_uint64),  # offsets[n+1]
            u8p,  # out: packed 32B h-scalars (LE, already mod l)
            ctypes.c_uint64,  # n
        ]
        lib.ed25519_h_batch.restype = None
        lib.sc_reduce_batch.argtypes = [ctypes.c_char_p, u8p, ctypes.c_uint64]
        lib.sc_reduce_batch.restype = None
        lib.has_ed25519_prep = True
    except AttributeError:
        lib.has_ed25519_prep = False

    try:
        lib.ed25519_verify_batch.argtypes = [
            ctypes.c_char_p,  # packed 32B public keys
            ctypes.c_char_p,  # packed messages
            ctypes.POINTER(ctypes.c_uint64),  # offsets[n+1]
            ctypes.c_char_p,  # packed 64B signatures
            u8p,  # out: n bytes, 1 = valid
            ctypes.c_uint64,  # n
        ]
        lib.ed25519_verify_batch.restype = None
        lib.has_ed25519_verify = True
    except AttributeError:
        lib.has_ed25519_verify = False

    # segstore primitives (segmented log-structured NodeStore) — newer
    # symbols, bound leniently like the ed25519 batch kernels
    try:
        lib.segidx_new.argtypes = [ctypes.c_uint64]
        lib.segidx_new.restype = ctypes.c_void_p
        lib.segidx_free.argtypes = [ctypes.c_void_p]
        lib.segidx_free.restype = None
        lib.segidx_count.argtypes = [ctypes.c_void_p]
        lib.segidx_count.restype = ctypes.c_uint64
        lib.segidx_put_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p,
            ctypes.c_void_p,  # locs: n uint64 (a numpy array's address)
        ]
        lib.segidx_put_batch.restype = ctypes.c_int
        lib.segidx_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.segidx_get.restype = ctypes.c_int64
        lib.segidx_remove.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.segidx_remove.restype = ctypes.c_int
        lib.segidx_filter_new.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p, u8p,
        ]
        lib.segidx_filter_new.restype = None
        lib.segidx_dump.argtypes = [ctypes.c_void_p, u8p, ctypes.c_uint64]
        lib.segidx_dump.restype = ctypes.c_uint64
        lib.segidx_load.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.segidx_load.restype = ctypes.c_int
        lib.segstore_pack.argtypes = [
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_void_p,  # offsets: n+1 uint64 (a numpy array's address)
            u8p, ctypes.c_uint64,
        ]
        lib.segstore_pack.restype = ctypes.c_int64
        lib.segstore_replay.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
            ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.segstore_replay.restype = ctypes.c_int64
        lib.has_segstore = True
    except AttributeError:
        lib.has_segstore = False

    # record-range scanner (out-of-core history shards): one C pass
    # indexes a whole file of segment-format records by key/type/offset
    try:
        lib.segrecs_scan.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
            u8p, u8p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.segrecs_scan.restype = ctypes.c_int64
        lib.has_segrecs_scan = True
    except AttributeError:
        lib.has_segrecs_scan = False

    try:
        lib.CPPLOG_ITER_CB = ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_void_p, u8p, ctypes.c_uint8, u8p,
            ctypes.c_uint32,
        )
        lib.cpplog_iterate.argtypes = [
            ctypes.c_void_p, lib.CPPLOG_ITER_CB, ctypes.c_void_p,
        ]
        lib.cpplog_iterate.restype = ctypes.c_int64
        lib.has_cpplog_iterate = True
    except AttributeError:
        lib.has_cpplog_iterate = False

    lib.cpplog_open.argtypes = [ctypes.c_char_p]
    lib.cpplog_open.restype = ctypes.c_void_p
    lib.cpplog_put.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint8,
        ctypes.c_char_p, ctypes.c_uint32,
    ]
    lib.cpplog_put.restype = ctypes.c_int
    lib.cpplog_get.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, u8p, ctypes.c_uint64,
    ]
    lib.cpplog_get.restype = ctypes.c_int64
    lib.cpplog_count.argtypes = [ctypes.c_void_p]
    lib.cpplog_count.restype = ctypes.c_uint64
    lib.cpplog_sync.argtypes = [ctypes.c_void_p]
    lib.cpplog_sync.restype = ctypes.c_int
    lib.cpplog_close.argtypes = [ctypes.c_void_p]
    lib.cpplog_close.restype = None


class Sha512Native:
    """Batched prefixed SHA-512-half over the C kernel."""

    def __init__(self):
        self.lib = load_native()
        if self.lib is None:
            raise RuntimeError("native library unavailable")

    def prefix_hash_batch(self, prefixes, payloads, out_len: int = 32) -> list[bytes]:
        n = len(payloads)
        if n == 0:
            return []
        data = b"".join(payloads)
        offsets = (ctypes.c_uint64 * (n + 1))()
        pos = 0
        for i, p in enumerate(payloads):
            offsets[i] = pos
            pos += len(p)
        offsets[n] = pos
        pfx = (ctypes.c_uint32 * n)(*[int(p) & 0xFFFFFFFF for p in prefixes])
        out = (ctypes.c_uint8 * (n * out_len))()
        self.lib.sha512h_batch(
            data, offsets, pfx, out, n, out_len
        )
        raw = bytes(out)
        return [raw[i * out_len : (i + 1) * out_len] for i in range(n)]

    def hash_packed(self, buf: bytes, offsets, out_len: int = 32) -> list[bytes]:
        """Batched SHA-512-half over PACKED messages: `buf` holds every
        message back to back (domain prefixes already embedded — the
        SHAMap flat-buffer node encoding), `offsets` is the n+1 boundary
        list. Zero per-message Python objects cross into C: one buffer,
        one offsets array, one call (sha512h_batch with NULL prefixes)."""
        n = len(offsets) - 1
        if n <= 0:
            return []
        arr = (ctypes.c_uint64 * (n + 1))(*offsets)
        out = (ctypes.c_uint8 * (n * out_len))()
        self.lib.sha512h_batch(bytes(buf), arr, None, out, n, out_len)
        raw = bytes(out)
        return [raw[i * out_len : (i + 1) * out_len] for i in range(n)]


class Ed25519HostPrep:
    """Batched h = SHA512(R||A||M) mod l over the C kernel (threaded).

    The per-signature host work feeding ops.ed25519_jax.verify_kernel,
    done in one ctypes call instead of a Python loop."""

    def __init__(self):
        self.lib = load_native()
        if self.lib is None:
            raise RuntimeError("native library unavailable")
        if not getattr(self.lib, "has_ed25519_prep", False):
            raise RuntimeError("native library predates ed25519_h_batch")

    def h_batch(self, rs: bytes, pubs: bytes, messages, n: int) -> "np.ndarray":
        """rs/pubs: packed 32-byte-per-element buffers; messages: sequence
        of bytes. Returns [n, 32] uint8 h-scalars (LE, reduced mod l)."""
        import numpy as np

        messages = list(messages)  # may be a generator; we iterate twice
        if len(messages) != n or len(rs) != 32 * n or len(pubs) != 32 * n:
            raise ValueError(
                f"h_batch: inconsistent batch (n={n}, msgs={len(messages)}, "
                f"rs={len(rs)}, pubs={len(pubs)})"
            )
        offsets = (ctypes.c_uint64 * (n + 1))()
        pos = 0
        for i, m in enumerate(messages):
            offsets[i] = pos
            pos += len(m)
        offsets[n] = pos
        packed = b"".join(messages)
        out = np.empty((n, 32), np.uint8)
        self.lib.ed25519_h_batch(
            rs, pubs, packed, offsets,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), n,
        )
        return out


class Ed25519NativeVerify:
    """Batched full Ed25519 verification over the C++ kernel
    (native/src/ed25519_verify.cc) — the libsodium role of the reference
    (StellarPublicKey::verifySignature) without the per-call interpreter
    and GIL costs of the one-at-a-time host library path."""

    def __init__(self):
        self.lib = load_native()
        if self.lib is None:
            raise RuntimeError("native library unavailable")
        if not getattr(self.lib, "has_ed25519_verify", False):
            raise RuntimeError("native library predates ed25519_verify_batch")

    def verify_batch(self, publics, messages, signatures) -> "np.ndarray":
        """publics/signatures: sequences of 32/64-byte strings; messages:
        sequence of bytes. Returns a bool ndarray of per-item validity.
        Malformed-length items are rejected (False) without touching the
        C layer, mirroring keys.verify_signature's length gates."""
        import numpy as np

        n = len(publics)
        if not (len(messages) == len(signatures) == n):
            raise ValueError("verify_batch: ragged batch")
        ok_shape = [
            len(publics[i]) == 32 and len(signatures[i]) == 64
            for i in range(n)
        ]
        idx = [i for i in range(n) if ok_shape[i]]
        out = np.zeros(n, bool)
        if not idx:
            return out
        offsets = (ctypes.c_uint64 * (len(idx) + 1))()
        pos = 0
        for j, i in enumerate(idx):
            offsets[j] = pos
            pos += len(messages[i])
        offsets[len(idx)] = pos
        raw = (ctypes.c_uint8 * len(idx))()
        self.lib.ed25519_verify_batch(
            b"".join(publics[i] for i in idx),
            b"".join(messages[i] for i in idx),
            offsets,
            b"".join(signatures[i] for i in idx),
            raw,
            len(idx),
        )
        out[idx] = np.frombuffer(bytes(raw), np.uint8).astype(bool)
        return out


def _u64_array(values):
    """`values` as ONE contiguous uint64 numpy array: a packed buffer
    (``array('Q')``, a uint64 array) is viewed in place, a list of ints
    converted in one C pass. The segstore seam's offsets and locations
    cross into C this way, as the array's address (``.ctypes.data``, an
    int: ``data_as`` would leave a reference cycle behind every call),
    never as one ctypes argument per value."""
    import numpy as np

    return np.ascontiguousarray(values, dtype=np.uint64)


class SegIdxNative:
    """Native open-addressed key→loc index for the segstore backend
    (key = 32-byte content hash, loc = (seg_id << 44) | record_offset).
    NOT thread-safe — the owning backend serializes access under its own
    lock. The pure-Python mirror lives in nodestore/segstore.py and is
    differential-tested against this."""

    def __init__(self, cap_hint: int = 0):
        self.lib = load_native()
        if self.lib is None or not getattr(self.lib, "has_segstore", False):
            raise RuntimeError("native segstore primitives unavailable")
        self._h = self.lib.segidx_new(cap_hint)
        if not self._h:
            raise MemoryError("segidx_new failed")

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            self.lib.segidx_free(h)

    def __len__(self) -> int:
        return int(self.lib.segidx_count(self._h))

    def get(self, key: bytes):
        loc = self.lib.segidx_get(self._h, key)
        return None if loc < 0 else int(loc)

    def put_batch(self, packed_keys: bytes, locs) -> None:
        """`locs`: one uint64 a key, as a packed buffer (``array('Q')``,
        a numpy array) or a sequence of ints."""
        arr = _u64_array(locs)
        n = len(arr)
        if len(packed_keys) < 32 * n:
            raise ValueError("put_batch: fewer keys than locations")
        if n and self.lib.segidx_put_batch(
                self._h, n, packed_keys, arr.ctypes.data) != 0:
            raise ValueError("segidx_put_batch: loc out of range")

    def remove(self, key: bytes, expect_loc=None) -> bool:
        exp = (2**64 - 1) if expect_loc is None else int(expect_loc)
        return bool(self.lib.segidx_remove(self._h, key, exp))

    def filter_new(self, packed_keys: bytes, n: int) -> bytes:
        """Byte mask: 1 where keys[i] is absent from the index (in-batch
        duplicates also masked off after their first occurrence)."""
        out = (ctypes.c_uint8 * n)()
        self.lib.segidx_filter_new(self._h, n, packed_keys, out)
        return bytes(out)

    def dump(self) -> bytes:
        """Checkpoint image: live entries as [32B key | u64 loc LE]."""
        n = len(self)
        out = (ctypes.c_uint8 * (n * 40))()
        got = self.lib.segidx_dump(self._h, out, n)
        return bytes(memoryview(out)[: int(got) * 40])

    def load(self, blob: bytes) -> None:
        n = len(blob) // 40
        if self.lib.segidx_load(self._h, blob, n) != 0:
            raise ValueError("segidx_load: corrupt checkpoint entry")

    def pack_records(self, packed_keys: bytes, types: bytes, buf,
                     offsets) -> bytes:
        """One-call append image from the flat-buffer node encoding."""
        n = len(types)
        arr = _u64_array(offsets)
        blobs = bytes(buf)
        # the C loop trusts every range it copies from: n+1 offsets,
        # never decreasing, inside the blob buffer, and 32 bytes of key
        # for each record
        if len(arr) != n + 1 or len(packed_keys) < 32 * n or (
                n and (arr[-1] > len(blobs) or (arr[1:] < arr[:-1]).any())):
            raise ValueError("pack_records: inconsistent batch")
        cap = len(blobs) + n * 38
        out = (ctypes.c_uint8 * cap)()
        got = self.lib.segstore_pack(
            n, packed_keys, types, blobs, arr.ctypes.data, out, cap
        )
        if got < 0:
            raise ValueError("segstore_pack failed")
        return bytes(memoryview(out)[: int(got)])

    def replay(self, path: str, seg_id: int, start: int) -> tuple:
        """Scan one segment file into the index; returns
        (clean_end_offset, records, bytes)."""
        recs = ctypes.c_uint64(0)
        byts = ctypes.c_uint64(0)
        end = self.lib.segstore_replay(
            self._h, path.encode(), seg_id, start,
            ctypes.byref(recs), ctypes.byref(byts),
        )
        if end < 0:
            raise OSError(f"segstore_replay failed: {path}")
        return int(end), int(recs.value), int(byts.value)


def scan_segment_records(path: str, start: int = 0):
    """Index a file of segment-format records in one native pass:
    [(key, type_byte, blob_offset, blob_len)] for every clean record —
    key/type/offset only, blobs stay on disk for decode-on-demand
    (the history-shard open path). Returns None when the native seam is
    unavailable (callers fall back to the Python struct loop)."""
    lib = load_native()
    if lib is None or not getattr(lib, "has_segrecs_scan", False):
        return None
    p = path.encode()
    n = lib.segrecs_scan(p, start, 0, None, None, None, None)
    if n < 0:
        raise OSError(f"segrecs_scan failed: {path}")
    n = int(n)
    if n == 0:
        return []
    keys = (ctypes.c_uint8 * (32 * n))()
    types = (ctypes.c_uint8 * n)()
    offs = (ctypes.c_uint64 * n)()
    lens = (ctypes.c_uint64 * n)()
    got = lib.segrecs_scan(p, start, n, keys, types, offs, lens)
    if got < 0:
        raise OSError(f"segrecs_scan failed: {path}")
    got = min(int(got), n)  # a concurrently-truncated tail fills fewer
    kb = bytes(keys)
    return [
        (kb[32 * i: 32 * i + 32], int(types[i]), int(offs[i]),
         int(lens[i]))
        for i in range(got)
    ]


class CppLogLib:
    """ctypes handle for one cpplog store. Thread-safe via a Python lock
    (the C side shares one FILE* between reads and appends)."""

    def __init__(self, path: str):
        self.lib = load_native()
        if self.lib is None:
            raise RuntimeError("native library unavailable")
        self._handle = self.lib.cpplog_open(path.encode())
        if not self._handle:
            raise OSError(f"cpplog_open failed: {path}")
        self._lock = threading.Lock()
        self._buf = (ctypes.c_uint8 * 65536)()

    def put(self, key: bytes, type_byte: int, blob: bytes) -> None:
        assert len(key) == 32
        with self._lock:
            rc = self.lib.cpplog_put(
                self._handle, key, type_byte, blob, len(blob)
            )
        if rc != 0:
            raise OSError("cpplog_put failed")

    def get(self, key: bytes) -> Optional[tuple[int, bytes]]:
        assert len(key) == 32
        with self._lock:
            n = self.lib.cpplog_get(
                self._handle, key, self._buf, len(self._buf)
            )
            if n <= -2:
                # -2 - needed_length: retry with an exact-size buffer
                # (one-off; the shared buffer keeps its normal size)
                need = int(-2 - n)
                big = (ctypes.c_uint8 * need)()
                n = self.lib.cpplog_get(self._handle, key, big, need)
                if n < 0:
                    raise OSError("cpplog_get failed after resize")
                raw = bytes(big[: int(n)])
                return raw[0], raw[1:]
            if n < 0:
                return None
            raw = bytes(self._buf[: int(n)])
        return raw[0], raw[1:]

    def count(self) -> int:
        with self._lock:
            return int(self.lib.cpplog_count(self._handle))

    def iterate(self):
        """Yield every live (key, type_byte, blob) record. The native
        callback scan snapshots into a Python list under the store lock
        (the C side shares one FILE* with appends), then yields outside
        it so consumers can interleave fetches/puts."""
        if not getattr(self.lib, "has_cpplog_iterate", False):
            raise OSError("native library predates cpplog_iterate")
        out: list[tuple[bytes, int, bytes]] = []

        def cb(_ctx, key, type_byte, blob, length):
            out.append((
                bytes(key[:32]), int(type_byte),
                bytes(blob[:length]) if length else b"",
            ))
            return 0

        cfun = self.lib.CPPLOG_ITER_CB(cb)
        with self._lock:
            n = self.lib.cpplog_iterate(self._handle, cfun, None)
        if n < 0:
            raise OSError("cpplog_iterate failed")
        return iter(out)

    def sync(self) -> None:
        with self._lock:
            rc = self.lib.cpplog_sync(self._handle)
        if rc != 0:
            # the store is failed (earlier torn write) or fsync failed:
            # callers must NOT believe the batch is durable
            raise OSError("cpplog_sync failed")

    def close(self) -> None:
        with self._lock:
            if self._handle:
                self.lib.cpplog_close(self._handle)
                self._handle = None

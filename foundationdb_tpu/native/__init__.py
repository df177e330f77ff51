"""ctypes bindings for the native (C++) CPU conflict set.

Builds `libconflict.so` on first use with g++ (the image has no pybind11;
the C ABI + ctypes is the binding seam — same role as the reference's
fdb_c C ABI, bindings/c/fdb_c.cpp). The native library serves two jobs:

* the measured CPU baseline for bench.py (the stand-in for the
  reference's `fdbserver -r skiplisttest` microbench), and
* an independent C++ parity oracle for the JAX kernel.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "conflict_set.cpp")
_SL_SRC = os.path.join(_DIR, "skiplist.cpp")
_lock = threading.Lock()
_lib = None
_sl_lib = None


class NativeBuildError(RuntimeError):
    pass


def build_shared(src: str, stem: str) -> str:
    """Compile src into a content-hash-named .so and return its path.

    Hash-named outputs mean a library on disk can never be stale relative
    to its source, its build flags OR the host CPU — a fresh clone always
    compiles (no binaries are committed; ADVICE r1: an mtime check let a
    checked-in .so shadow the source it was supposed to be built from),
    and a copy of the tree on another host builds its own `-march=native`
    library instead of loading one that may use ISA extensions its CPU
    lacks (SIGILL).
    """
    from foundationdb_tpu.utils.compile_cache import host_fingerprint

    flags = ["-O3", "-march=native", "-std=c++17", "-shared", "-fPIC"]
    with open(src, "rb") as f:
        hasher = hashlib.sha256(f.read())
    hasher.update(" ".join(flags).encode())
    hasher.update(host_fingerprint().encode())
    digest = hasher.hexdigest()[:16]
    out = os.path.join(_DIR, f"{stem}-{digest}.so")
    if os.path.exists(out):
        return out
    tmp = out + f".tmp{os.getpid()}"
    cmd = ["g++", *flags, "-o", tmp, src]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise NativeBuildError(f"g++ failed:\n{proc.stderr}")
    os.replace(tmp, out)  # atomic: concurrent builders race safely
    return out


def load() -> ctypes.CDLL:
    """Build (if not yet built for this source hash) and load."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(build_shared(_SRC, "libconflict"))
        lib.cs_create.restype = ctypes.c_void_p
        lib.cs_create.argtypes = [ctypes.c_int64]
        lib.cs_destroy.argtypes = [ctypes.c_void_p]
        lib.cs_resolve.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int32, ctypes.c_void_p,
        ]
        lib.cs_history_size.restype = ctypes.c_int64
        lib.cs_history_size.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


def load_skiplist() -> ctypes.CDLL:
    """Build/load the skip-list baseline (skiplist.cpp — the reference
    SkipList.cpp's algorithm class: pyramid max-versions, radix point
    sort, bitset intra-batch sweep; VERDICT r1 task 3's honest CPU
    baseline)."""
    global _sl_lib
    with _lock:
        if _sl_lib is not None:
            return _sl_lib
        lib = ctypes.CDLL(build_shared(_SL_SRC, "libskiplist"))
        lib.slcs_create.restype = ctypes.c_void_p
        lib.slcs_create.argtypes = [ctypes.c_int64]
        lib.slcs_destroy.argtypes = [ctypes.c_void_p]
        lib.slcs_resolve.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int32, ctypes.c_void_p,
        ]
        lib.slcs_history_size.restype = ctypes.c_int64
        lib.slcs_history_size.argtypes = [ctypes.c_void_p]
        _sl_lib = lib
        return lib


def _flatten(ranges_per_txn):
    """[(txn, begin, end)] -> (key blob, offsets[2n+1], txn ids[n])."""
    keys = bytearray()
    offsets = [0]
    txn_ids = []
    for t, b, e in ranges_per_txn:
        keys.extend(b)
        offsets.append(len(keys))
        keys.extend(e)
        offsets.append(len(keys))
        txn_ids.append(t)
    return (
        np.frombuffer(bytes(keys), np.uint8) if keys else np.zeros(0, np.uint8),
        np.asarray(offsets, np.int64),
        np.asarray(txn_ids, np.int32),
    )


class NativeConflictSet:
    """CPU conflict set with the ConflictBatch verdict contract."""

    def __init__(self, window: int = 5_000_000):
        self._lib = load()
        self._create = self._lib.cs_create
        self._destroy = self._lib.cs_destroy
        self._resolve = self._lib.cs_resolve
        self._size = self._lib.cs_history_size
        self._cs = self._create(window)

    def __del__(self):
        if getattr(self, "_cs", None):
            self._destroy(self._cs)
            self._cs = None

    def resolve(self, transactions, version: int) -> np.ndarray:
        """transactions: CommitTransaction-shaped objects. Returns [n] int32
        verdicts (0=conflict, 1=tooOld, 3=committed)."""
        n = len(transactions)
        snapshots = np.asarray(
            [t.read_snapshot for t in transactions], np.int64
        )
        reads = [
            (t, b, e)
            for t, tr in enumerate(transactions)
            for b, e in tr.read_conflict_ranges
        ]
        writes = [
            (t, b, e)
            for t, tr in enumerate(transactions)
            for b, e in tr.write_conflict_ranges
        ]
        rkeys, roff, rtxn = _flatten(reads)
        wkeys, woff, wtxn = _flatten(writes)
        verdict = np.zeros(n, np.int32)
        c = ctypes.c_void_p
        self._resolve(
            self._cs, version, n,
            snapshots.ctypes.data_as(c),
            rkeys.ctypes.data_as(c), roff.ctypes.data_as(c),
            rtxn.ctypes.data_as(c), len(rtxn),
            wkeys.ctypes.data_as(c), woff.ctypes.data_as(c),
            wtxn.ctypes.data_as(c), len(wtxn),
            verdict.ctypes.data_as(c),
        )
        return verdict

    def resolve_raw(
        self,
        version: int,
        snapshots: np.ndarray,   # [n] int64
        rkeys: np.ndarray,       # uint8 blob: begin_i/end_i interleaved
        roff: np.ndarray,        # [2*n_reads+1] int64 offsets into rkeys
        rtxn: np.ndarray,        # [n_reads] int32
        wkeys: np.ndarray,
        woff: np.ndarray,
        wtxn: np.ndarray,
    ) -> np.ndarray:
        """Zero-copy path for pre-flattened batches (bench hot loop)."""
        n = snapshots.shape[0]
        verdict = np.zeros(n, np.int32)
        c = ctypes.c_void_p
        self._resolve(
            self._cs, version, n,
            np.ascontiguousarray(snapshots, np.int64).ctypes.data_as(c),
            np.ascontiguousarray(rkeys, np.uint8).ctypes.data_as(c),
            np.ascontiguousarray(roff, np.int64).ctypes.data_as(c),
            np.ascontiguousarray(rtxn, np.int32).ctypes.data_as(c), len(rtxn),
            np.ascontiguousarray(wkeys, np.uint8).ctypes.data_as(c),
            np.ascontiguousarray(woff, np.int64).ctypes.data_as(c),
            np.ascontiguousarray(wtxn, np.int32).ctypes.data_as(c), len(wtxn),
            verdict.ctypes.data_as(c),
        )
        return verdict

    @property
    def history_size(self) -> int:
        return self._size(self._cs)


class NativeSkipListConflictSet(NativeConflictSet):
    """The skip-list CPU baseline (skiplist.cpp): same wire contract,
    same verdicts, the reference's algorithm class instead of the
    ordered-map semantic model. bench.py reports vs_baseline against the
    faster of the two (VERDICT r1 task 3)."""

    def __init__(self, window: int = 5_000_000):
        self._lib = load_skiplist()
        self._create = self._lib.slcs_create
        self._destroy = self._lib.slcs_destroy
        self._resolve = self._lib.slcs_resolve
        self._size = self._lib.slcs_history_size
        self._cs = self._create(window)


# ---------------------------------------------------------------------------
# DiskQueue (diskqueue.cpp): the TLog's durable log — push/commit(fsync)/
# pop + crash-recovery scan (role of fdbserver/DiskQueue.actor.cpp).

_dq_lib = None


def load_diskqueue() -> ctypes.CDLL:
    global _dq_lib
    with _lock:
        if _dq_lib is not None:
            return _dq_lib
        lib = ctypes.CDLL(
            build_shared(os.path.join(_DIR, "diskqueue.cpp"), "libdiskqueue")
        )
        lib.dq_open.restype = ctypes.c_void_p
        lib.dq_open.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                ctypes.c_uint64]
        lib.dq_close.argtypes = [ctypes.c_void_p]
        lib.dq_push.restype = ctypes.c_uint64
        lib.dq_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint32]
        lib.dq_pop.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.dq_commit.restype = ctypes.c_uint64
        lib.dq_commit.argtypes = [ctypes.c_void_p]
        lib.dq_ok.restype = ctypes.c_int
        lib.dq_ok.argtypes = [ctypes.c_void_p]
        lib.dq_next_seq.restype = ctypes.c_uint64
        lib.dq_next_seq.argtypes = [ctypes.c_void_p]
        lib.dq_pop_floor.restype = ctypes.c_uint64
        lib.dq_pop_floor.argtypes = [ctypes.c_void_p]
        lib.dq_recovered_count.restype = ctypes.c_int64
        lib.dq_recovered_count.argtypes = [ctypes.c_void_p]
        lib.dq_recovered_get.restype = ctypes.c_int64
        lib.dq_recovered_get.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64),
        ]
        _dq_lib = lib
        return lib


class DiskQueue:
    """Durable append log over a file pair with recovery scan.

    Contract (DiskQueue.actor.cpp): push() buffers, commit() makes
    everything pushed durable (fsync) — ack callers only after commit;
    pop(seq) lets the queue discard records below seq; after a crash,
    `recovered` holds exactly the committed, un-popped records in order.
    """

    def __init__(self, path_prefix: str, *, rotate_bytes: int = 64 << 20):
        lib = load_diskqueue()
        self._lib = lib
        self._q = lib.dq_open(
            (path_prefix + "-0.dq").encode(), (path_prefix + "-1.dq").encode(),
            rotate_bytes,
        )
        if not self._q:
            raise NativeBuildError(f"dq_open failed for {path_prefix}")

    def close(self) -> None:
        if self._q:
            self._lib.dq_close(self._q)
            self._q = None

    def __del__(self):
        self.close()

    def push(self, data: bytes) -> int:
        return self._lib.dq_push(self._q, data, len(data))

    def pop(self, up_to_seq: int) -> None:
        self._lib.dq_pop(self._q, up_to_seq)

    def commit(self):
        """fsync everything pushed. Returns the last durable seq, or
        None if the disk write/fsync FAILED — callers must not ack."""
        r = self._lib.dq_commit(self._q)
        if not self._lib.dq_ok(self._q):
            return None
        return r

    @property
    def next_seq(self) -> int:
        return self._lib.dq_next_seq(self._q)

    @property
    def pop_floor(self) -> int:
        return self._lib.dq_pop_floor(self._q)

    @property
    def recovered(self) -> list[tuple[int, bytes]]:
        n = self._lib.dq_recovered_count(self._q)
        out = []
        seq = ctypes.c_uint64()
        for i in range(n):
            ln = self._lib.dq_recovered_get(self._q, i, None, 0,
                                            ctypes.byref(seq))
            buf = ctypes.create_string_buffer(max(ln, 1))
            self._lib.dq_recovered_get(self._q, i, buf, ln,
                                       ctypes.byref(seq))
            out.append((seq.value, buf.raw[:ln]))
        return out


# ---------------------------------------------------------------------------
# VersionedLsm (vlsm.cpp): the persistent storage engine behind StorageRole
# (role of the reference's Redwood/sqlite engines — data > RAM, restart
# cost proportional to the WAL tail, MVCC at-version reads).

_VLSM_SRC = os.path.join(_DIR, "vlsm.cpp")
_vlsm_lib = None


def load_vlsm() -> ctypes.CDLL:
    global _vlsm_lib
    with _lock:
        if _vlsm_lib is not None:
            return _vlsm_lib
        lib = ctypes.CDLL(build_shared(_VLSM_SRC, "libvlsm"))
        lib.vlsm_open.restype = ctypes.c_void_p
        lib.vlsm_open.argtypes = [ctypes.c_char_p, ctypes.c_longlong]
        lib.vlsm_ok.argtypes = [ctypes.c_void_p]
        lib.vlsm_close.argtypes = [ctypes.c_void_p]
        for name in ("vlsm_durable_version", "vlsm_applied_version",
                     "vlsm_mem_bytes", "vlsm_floor"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_longlong
            fn.argtypes = [ctypes.c_void_p]
        lib.vlsm_num_runs.argtypes = [ctypes.c_void_p]
        lib.vlsm_last_error.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
        lib.vlsm_apply.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_char_p,
            ctypes.c_longlong]
        lib.vlsm_get.restype = ctypes.c_longlong
        lib.vlsm_get.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_longlong, ctypes.c_void_p, ctypes.c_longlong]
        lib.vlsm_flush.restype = ctypes.c_longlong
        lib.vlsm_flush.argtypes = [ctypes.c_void_p]
        lib.vlsm_compact.argtypes = [ctypes.c_void_p]
        lib.vlsm_set_floor.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        lib.vlsm_range.restype = ctypes.c_longlong
        lib.vlsm_range.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int, ctypes.c_longlong,
            ctypes.c_longlong, ctypes.c_void_p, ctypes.c_longlong,
            ctypes.POINTER(ctypes.c_longlong)]
        _vlsm_lib = lib
        return lib


class VlsmError(RuntimeError):
    pass


class VersionedLsm:
    """Versioned LSM storage engine (vlsm.cpp).

    apply() buffers into the memtable (NOT durable by itself — pair it
    with a write-ahead log, as StorageRole does); flush() makes every
    applied version durable and returns the durable version; reads are
    at-version within the MVCC window above the GC floor.
    """

    MUT_SET = 0
    MUT_CLEAR_RANGE = 1

    def __init__(self, directory: str, window: int = 5_000_000):
        self._lib = load_vlsm()
        # vlsm.cpp does NO locking, and ctypes calls release the GIL:
        # this lock serializes every native call so the role may run
        # reads in executor threads while applies stay on the event loop
        self._tl = threading.Lock()
        self._h = self._lib.vlsm_open(
            directory.encode(), ctypes.c_longlong(window))
        if not self._lib.vlsm_ok(self._h):
            raise VlsmError(f"vlsm open failed: {self._error()}")

    def _error(self) -> str:
        buf = ctypes.create_string_buffer(1024)
        self._lib.vlsm_last_error(self._h, buf, 1024)
        return buf.value.decode(errors="replace")

    def close(self) -> None:
        with self._tl:
            if self._h:
                self._lib.vlsm_close(self._h)
                self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -- writes ----------------------------------------------------------

    def apply(self, version: int, mutations) -> None:
        """mutations: [(op, key, value_or_end)] with op in
        {MUT_SET, MUT_CLEAR_RANGE}."""
        blob = bytearray(len(mutations).to_bytes(4, "little"))
        for op, key, second in mutations:
            blob.append(op)
            blob += len(key).to_bytes(4, "little")
            blob += key
            blob += len(second).to_bytes(4, "little")
            blob += second
        b = bytes(blob)
        with self._tl:
            rc = self._lib.vlsm_apply(
                self._h, ctypes.c_longlong(version), b, len(b)
            )
        if rc != 0:
            raise VlsmError("malformed mutation blob")

    def flush(self) -> int:
        """Flush the memtable into a durable run; returns the durable
        version (auto-compacts when the run count passes the trigger)."""
        with self._tl:
            v = self._lib.vlsm_flush(self._h)
        if v < 0:
            raise VlsmError(f"flush failed: {self._error()}")
        return v

    def compact(self) -> None:
        with self._tl:
            rc = self._lib.vlsm_compact(self._h)
        if rc != 0:
            raise VlsmError(f"compact failed: {self._error()}")

    def set_floor(self, floor: int) -> None:
        with self._tl:
            self._lib.vlsm_set_floor(self._h, ctypes.c_longlong(floor))

    # -- reads -----------------------------------------------------------

    def get(self, key: bytes, version: int) -> bytes | None:
        cap = 4096
        while True:
            buf = ctypes.create_string_buffer(cap)
            with self._tl:
                n = self._lib.vlsm_get(
                    self._h, key, len(key), ctypes.c_longlong(version),
                    buf, cap)
            if n == -1:
                return None
            if n < -1:
                cap = -(n + 2) + 1
                continue
            return buf.raw[:n]

    def range(
        self, begin: bytes, end: bytes, version: int,
        max_items: int = 1 << 62,
    ) -> list[tuple[bytes, bytes]]:
        """Merged scan of [begin, end) at `version`; end=b"" scans to
        the last key."""
        cap = 1 << 20
        while True:
            buf = ctypes.create_string_buffer(cap)
            nbytes = ctypes.c_longlong()
            with self._tl:
                n = self._lib.vlsm_range(
                    self._h, begin, len(begin), end, len(end),
                    ctypes.c_longlong(version), ctypes.c_longlong(max_items),
                    buf, cap, ctypes.byref(nbytes))
            if n == -1:
                cap = nbytes.value + 1
                continue
            out = []
            raw = memoryview(buf.raw)
            p = 0
            for _ in range(n):
                kl = int.from_bytes(raw[p:p + 4], "little"); p += 4
                k = bytes(raw[p:p + kl]); p += kl
                vl = int.from_bytes(raw[p:p + 4], "little"); p += 4
                v = bytes(raw[p:p + vl]); p += vl
                out.append((k, v))
            return out

    # -- introspection ---------------------------------------------------

    @property
    def durable_version(self) -> int:
        with self._tl:
            return self._lib.vlsm_durable_version(self._h)

    @property
    def mem_bytes(self) -> int:
        with self._tl:
            return self._lib.vlsm_mem_bytes(self._h)

    @property
    def num_runs(self) -> int:
        with self._tl:
            return self._lib.vlsm_num_runs(self._h)

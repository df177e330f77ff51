"""Host-side batch packing: byte-string conflict ranges -> fixed-shape tensors.

This is the boundary where a `ResolveTransactionBatchRequest`'s
variable-length data (reference wire type:
fdbserver/include/fdbserver/ResolverInterface.h:94-129) becomes the packed,
static-shape tensors the TPU kernel consumes. Reads and writes are packed
*flat* (one row per conflict range, with a txn-id column) rather than
[B, R, ...] so that sparse per-txn range counts don't waste device FLOPs.

Versions are rebased to int32 offsets from a host-held base version: the
MVCC window is ~5e6 versions (fdbclient/ServerKnobs.cpp:43) so every live
version fits comfortably in 31 bits; `Resolver` re-bases periodically.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from foundationdb_tpu.config import KernelConfig

# Version offset used for "far in the past" (clamped stale snapshots).
VERSION_NEG = np.int32(-(2**31) + 1)


class KeyTooLongError(ValueError):
    """Kept for API compatibility; the packer no longer raises it."""


def pack_key(key: bytes, max_key_bytes: int, *, round_up: bool = False) -> np.ndarray:
    """bytes -> [W] uint32 (big-endian byte words + length word).

    Keys longer than max_key_bytes degrade CONSERVATIVELY (SURVEY.md §7.3
    names exact long-key order the #1 parity risk): a truncated begin key
    keeps length == max (sorts at-or-before the original), a truncated
    end key gets length max+1 — "just past every key with this prefix" —
    so it sorts after them. Ranges only ever EXPAND, which can add
    spurious conflicts for >max-byte keys but can never miss one.
    """
    if len(key) > max_key_bytes:
        length = max_key_bytes + 1 if round_up else max_key_bytes
        key = key[:max_key_bytes]
    else:
        length = len(key)
    padded = key + b"\x00" * (max_key_bytes - len(key))
    words = np.frombuffer(padded, dtype=">u4").astype(np.uint32)
    return np.concatenate([words, np.array([length], np.uint32)])


def pack_keys(
    keys: list[bytes], max_key_bytes: int, *, round_up: bool = False
) -> np.ndarray:
    """[n, W] uint32; vectorized over a list of byte keys (see pack_key
    for the conservative long-key handling).

    Bulk-numpy formulation (r6): ONE joined byte blob scattered into the
    padded matrix through cumsum offsets, instead of a per-key
    frombuffer loop — the loop dominated host packing at bench batch
    sizes (tests/test_packing.py pins byte-identical output against the
    loop version, _pack_keys_reference). The scatter itself lives in
    pack_keys_from_blob so the columnar wire decode (r12) runs the SAME
    code over the frame's already-joined blob — the two paths cannot
    produce different matrices.
    """
    n = len(keys)
    w = max_key_bytes // 4 + 1
    if n == 0:
        return np.zeros((n, w), np.uint32)
    lens = np.fromiter((len(k) for k in keys), np.int64, count=n)
    cat = np.frombuffer(b"".join(keys), np.uint8)
    return pack_keys_from_blob(
        cat, np.cumsum(lens) - lens, lens, max_key_bytes, round_up=round_up
    )


def pack_keys_from_blob(
    cat: np.ndarray,
    starts: np.ndarray,
    lens: np.ndarray,
    max_key_bytes: int,
    *,
    round_up: bool = False,
) -> np.ndarray:
    """pack_keys over an already-joined key blob: key i occupies
    ``cat[starts[i] : starts[i] + lens[i]]`` (uint8 view, possibly a
    zero-copy view of a wire frame payload).

    This is the columnar resolve frame's decode-to-kernel scatter —
    and the body pack_keys itself delegates to, so the object path and
    the columnar path are byte-identical by construction, not by test
    alone. Long keys (> max_key_bytes) degrade conservatively exactly
    like pack_key: only the first max_key_bytes bytes are taken and the
    length word saturates (max, or max+1 for round_up end keys).
    """
    n = len(lens)
    w = max_key_bytes // 4 + 1
    out = np.zeros((n, w), np.uint32)
    if n == 0:
        return out
    lens = np.asarray(lens, np.int64)
    starts = np.asarray(starts, np.int64)
    over = lens > max_key_bytes
    kept = np.minimum(lens, max_key_bytes)
    out_lens = np.where(
        over, max_key_bytes + 1 if round_up else max_key_bytes, lens
    )
    buf = np.zeros((n, max_key_bytes), np.uint8)
    rows = np.repeat(np.arange(n), kept)
    offs = np.cumsum(kept) - kept
    cols = np.arange(int(kept.sum())) - np.repeat(offs, kept)
    buf[rows, cols] = cat[np.repeat(starts, kept) + cols]
    out[:, :-1] = buf.view(">u4").astype(np.uint32).reshape(n, w - 1)
    out[:, -1] = out_lens.astype(np.uint32)
    return out


def _pack_keys_reference(
    keys: list[bytes], max_key_bytes: int, *, round_up: bool = False
) -> np.ndarray:
    """The pre-r6 per-key loop packer, kept as the byte-identical
    regression oracle for the vectorized pack_keys (tests/test_packing)."""
    n = len(keys)
    w = max_key_bytes // 4 + 1
    out = np.zeros((n, w), np.uint32)
    if n == 0:
        return out
    buf = np.zeros((n, max_key_bytes), np.uint8)
    lens = np.empty((n,), np.uint32)
    for i, k in enumerate(keys):
        if len(k) > max_key_bytes:
            lens[i] = max_key_bytes + 1 if round_up else max_key_bytes
            k = k[:max_key_bytes]
        else:
            lens[i] = len(k)
        buf[i, : len(k)] = np.frombuffer(k, np.uint8)
    out[:, :-1] = buf.view(">u4").astype(np.uint32).reshape(n, w - 1)
    out[:, -1] = lens
    return out


def unpack_key(row: np.ndarray) -> bytes:
    """[W] uint32 -> bytes (inverse of pack_key)."""
    length = int(row[-1])
    raw = row[:-1].astype(">u4").tobytes()
    return raw[:length]


@dataclasses.dataclass
class PackedBatch:
    """One batch of transactions in kernel form (all numpy, host-side).

    Shapes are one rung of `row_rungs(config)` (the KernelConfig caps
    unless the packer was given the ladder); `n_txns`/`n_reads`/`n_writes`
    give the live prefix sizes (rows past them are masked invalid).
    """

    # scalars
    version: np.int32          # commit version offset of this batch
    new_oldest: np.int32       # MVCC-window floor offset (version - window)
    n_txns: int
    n_reads: int
    n_writes: int
    # per-txn [B]
    txn_valid: np.ndarray      # bool
    snapshot: np.ndarray       # int32 version offsets (clamped at VERSION_NEG)
    has_reads: np.ndarray      # bool — blind writes are never "too old"
    # flattened reads [NR]
    read_begin: np.ndarray     # [NR, W] uint32
    read_end: np.ndarray       # [NR, W] uint32
    read_txn: np.ndarray       # int32
    read_index: np.ndarray     # int32 — index of the range within its txn
    read_valid: np.ndarray     # bool
    # flattened writes [NW]
    write_begin: np.ndarray    # [NW, W] uint32
    write_end: np.ndarray      # [NW, W] uint32
    write_txn: np.ndarray      # int32
    write_valid: np.ndarray    # bool

    def device_args(self):
        """The pytree handed to the jitted kernel (drops host-only ints)."""
        return {
            "version": np.int32(self.version),
            "new_oldest": np.int32(self.new_oldest),
            "txn_valid": self.txn_valid,
            "snapshot": self.snapshot,
            "has_reads": self.has_reads,
            "read_begin": self.read_begin,
            "read_end": self.read_end,
            "read_txn": self.read_txn,
            "read_index": self.read_index,
            "read_valid": self.read_valid,
            "write_begin": self.write_begin,
            "write_end": self.write_end,
            "write_txn": self.write_txn,
            "write_valid": self.write_valid,
        }


def row_rungs(config: KernelConfig) -> tuple[tuple[int, int, int], ...]:
    """The row capacities (txns, reads, writes) a batch may be packed
    at, smallest first: (r, r, r) for each `r` of max(max_txns // 64,
    64) and max_txns, capped by the config's own caps, then the
    config's full shape, duplicates dropped. Every rung is a power of
    two in each coordinate (min of powers of two), and the rungs grow
    in every coordinate, so the first that holds a batch is the
    smallest."""
    full = (config.max_txns, config.max_reads, config.max_writes)
    rungs: list[tuple[int, int, int]] = []
    for r in sorted({max(config.max_txns // 64, 64), config.max_txns}):
        rung = tuple(min(cap, r) for cap in full)
        if rung not in rungs:
            rungs.append(rung)
    if full not in rungs:
        rungs.append(full)
    return tuple(rungs)


def _rung(config: KernelConfig, rungs, n: int, nread: int,
          nwrite: int) -> tuple[int, int, int]:
    """The first of `rungs` that holds n txns, nread reads and nwrite
    writes; the config's full shape when `rungs` is None. The caller
    has already refused a batch past the config's caps."""
    for rung in rungs or ():
        if n <= rung[0] and nread <= rung[1] and nwrite <= rung[2]:
            return rung
    return (config.max_txns, config.max_reads, config.max_writes)


def _clamp_version(v: int, base: int) -> np.int32:
    off = v - base
    if off <= int(VERSION_NEG):
        return VERSION_NEG
    if off >= 2**31:
        raise OverflowError(f"version offset {off} overflows int32; rebase")
    return np.int32(off)


def _txn_columns(cfg: KernelConfig, rungs, off, r_counts, nread: int,
                 nwrite: int):
    """What both packers share ahead of the range rows: refuse a
    snapshot offset past int32 or a batch past the config's caps, choose
    the rung (`_rung`), and build the per-txn columns at its txn
    capacity. `off` holds each txn's snapshot offset from the base
    version and `r_counts` its read count. Returns (b, nr, nw,
    txn_valid, snapshot, has_reads)."""
    high = off >= 2**31
    if high.any():
        bad = int(off[high][0])
        raise OverflowError(f"version offset {bad} overflows int32; rebase")
    if nread > cfg.max_reads:
        raise ValueError(f"{nread} read ranges > max_reads {cfg.max_reads}")
    if nwrite > cfg.max_writes:
        raise ValueError(
            f"{nwrite} write ranges > max_writes {cfg.max_writes}"
        )
    n = len(off)
    b, nr, nw = _rung(cfg, rungs, n, nread, nwrite)
    txn_valid = np.zeros((b,), bool)
    txn_valid[:n] = True
    snapshot = np.full((b,), VERSION_NEG, np.int32)
    snapshot[:n] = np.where(
        off <= int(VERSION_NEG), int(VERSION_NEG), off
    ).astype(np.int32)
    has_reads = np.zeros((b,), bool)
    has_reads[:n] = r_counts > 0
    return b, nr, nw, txn_valid, snapshot, has_reads


def pack_batch(
    transactions,
    version: int,
    base_version: int,
    config: KernelConfig,
    rungs=None,
) -> PackedBatch:
    """Pack a list of CommitTransaction into kernel tensors.

    `transactions` is any sequence with `.read_conflict_ranges`,
    `.write_conflict_ranges` (lists of (begin, end) byte pairs) and
    `.read_snapshot` (int) — the shape of the reference's
    CommitTransactionRef (fdbclient/include/fdbclient/CommitTransaction.h).

    Bulk-numpy formulation (r6): per-txn columns come from
    repeat/cumsum over pre-flattened range lists instead of the pre-r6
    append loops, so host packing stops dominating the pipelined stream
    (the pack stage of TpuConflictSet.resolve_stream_pipelined).
    Byte-identical to pack_batch_reference (tests/test_packing.py).

    With `rungs` (`row_rungs(config)`) the tensors are padded to the
    first rung that holds the batch, not to the config's caps: the same
    bytes as the full-size pack cut to the rung, with the padding rows'
    txn id the rung's txn capacity.
    """
    cfg = config
    n = len(transactions)
    if n > cfg.max_txns:
        raise ValueError(f"{n} txns > max_txns {cfg.max_txns}")

    r_lists = [tr.read_conflict_ranges for tr in transactions]
    w_lists = [tr.write_conflict_ranges for tr in transactions]
    if n:
        off = np.fromiter(
            (tr.read_snapshot for tr in transactions), np.int64, count=n
        ) - base_version
        r_counts = np.fromiter((len(x) for x in r_lists), np.int64, count=n)
        w_counts = np.fromiter((len(x) for x in w_lists), np.int64, count=n)
    else:
        off = r_counts = w_counts = np.zeros((0,), np.int64)
    nread = int(r_counts.sum())
    nwrite = int(w_counts.sum())
    b, nr, nw, txn_valid, snapshot, has_reads = _txn_columns(
        cfg, rungs, off, r_counts, nread, nwrite
    )
    w = cfg.key_words

    r_flat = [rg for lst in r_lists for rg in lst]
    w_flat = [rg for lst in w_lists for rg in lst]
    ids = np.arange(n, dtype=np.int32)
    r_txn = np.repeat(ids, r_counts)
    w_txn = np.repeat(ids, w_counts)
    r_starts = np.concatenate([[0], np.cumsum(r_counts)[:-1]]) if n else r_counts
    r_idx = (np.arange(nread) - np.repeat(r_starts, r_counts)).astype(np.int32)

    def _flat_keys(pairs, cap):
        kb = np.zeros((cap, w), np.uint32)
        ke = np.zeros((cap, w), np.uint32)
        m = len(pairs)
        if m:
            kb[:m] = pack_keys([p[0] for p in pairs], cfg.max_key_bytes)
            ke[:m] = pack_keys(
                [p[1] for p in pairs], cfg.max_key_bytes, round_up=True
            )
        return kb, ke

    rb, re = _flat_keys(r_flat, nr)
    wb, we = _flat_keys(w_flat, nw)

    def _col(vals, cap, dtype=np.int32, fill=0):
        out = np.full((cap,), fill, dtype)
        out[: len(vals)] = vals
        return out
    return PackedBatch(
        version=_clamp_version(version, base_version),
        new_oldest=_clamp_version(version - cfg.window_versions, base_version),
        n_txns=len(transactions),
        n_reads=nread,
        n_writes=nwrite,
        txn_valid=txn_valid,
        snapshot=snapshot,
        has_reads=has_reads,
        read_begin=rb,
        read_end=re,
        # KERNEL LAYOUT CONTRACT (ops/group.py per-txn windows): rows are
        # grouped by txn in nondecreasing txn order with ranges in
        # declaration order, and PADDING rows carry txn id == the
        # rung's txn capacity (its B) — the flat (batch, txn) segment
        # id is then monotone, which lets the kernel do per-txn
        # reductions with cumsum windows instead of scatters.
        read_txn=_col(r_txn, nr, fill=b),
        read_index=_col(r_idx, nr),
        read_valid=_col([True] * nread, nr, bool),
        write_begin=wb,
        write_end=we,
        write_txn=_col(w_txn, nw, fill=b),
        write_valid=_col([True] * nwrite, nw, bool),
    )


def pack_batch_reference(
    transactions,
    version: int,
    base_version: int,
    config: KernelConfig,
) -> PackedBatch:
    """The pre-r6 per-txn append-loop packer, kept verbatim as the
    byte-identical regression oracle for the vectorized pack_batch
    (tests/test_packing.py). Not on any hot path."""
    cfg = config
    b, nr, nw, w = cfg.max_txns, cfg.max_reads, cfg.max_writes, cfg.key_words
    if len(transactions) > b:
        raise ValueError(f"{len(transactions)} txns > max_txns {b}")

    txn_valid = np.zeros((b,), bool)
    snapshot = np.full((b,), VERSION_NEG, np.int32)
    has_reads = np.zeros((b,), bool)

    r_begin, r_end, r_txn, r_idx = [], [], [], []
    w_begin, w_end, w_txn = [], [], []
    for t, tr in enumerate(transactions):
        txn_valid[t] = True
        snapshot[t] = _clamp_version(tr.read_snapshot, base_version)
        has_reads[t] = len(tr.read_conflict_ranges) > 0
        for i, (kb, ke) in enumerate(tr.read_conflict_ranges):
            r_begin.append(kb)
            r_end.append(ke)
            r_txn.append(t)
            r_idx.append(i)
        for kb, ke in tr.write_conflict_ranges:
            w_begin.append(kb)
            w_end.append(ke)
            w_txn.append(t)

    if len(r_txn) > nr:
        raise ValueError(f"{len(r_txn)} read ranges > max_reads {nr}")
    if len(w_txn) > nw:
        raise ValueError(f"{len(w_txn)} write ranges > max_writes {nw}")

    def _flat(begins, ends, cap):
        kb = np.zeros((cap, w), np.uint32)
        ke = np.zeros((cap, w), np.uint32)
        n = len(begins)
        if n:
            kb[:n] = _pack_keys_reference(begins, cfg.max_key_bytes)
            ke[:n] = _pack_keys_reference(
                ends, cfg.max_key_bytes, round_up=True
            )
        return kb, ke

    rb, re = _flat(r_begin, r_end, nr)
    wb, we = _flat(w_begin, w_end, nw)

    def _col(vals, cap, dtype=np.int32, fill=0):
        out = np.full((cap,), fill, dtype)
        out[: len(vals)] = vals
        return out

    nread, nwrite = len(r_txn), len(w_txn)
    return PackedBatch(
        version=_clamp_version(version, base_version),
        new_oldest=_clamp_version(version - cfg.window_versions, base_version),
        n_txns=len(transactions),
        n_reads=nread,
        n_writes=nwrite,
        txn_valid=txn_valid,
        snapshot=snapshot,
        has_reads=has_reads,
        read_begin=rb,
        read_end=re,
        read_txn=_col(r_txn, nr, fill=b),
        read_index=_col(r_idx, nr),
        read_valid=_col([True] * nread, nr, bool),
        write_begin=wb,
        write_end=we,
        write_txn=_col(w_txn, nw, fill=b),
        write_valid=_col([True] * nwrite, nw, bool),
    )


# ---------------------------------------------------------------------------
# Columnar resolve batch (r12 — the wire-to-kernel path): one batch's
# conflict metadata as flat columns, packed ONCE at the proxy in the
# layout pack_batch already consumes (per-txn counts + one joined key
# blob + versions), so the resolver decodes wire bytes straight into
# kernel tensors without ever materializing per-transaction objects.

#: The columnar frame's array layout — ONE constant shared by the wire
#: encoder and decoder (wire/codec.py w_/r_resolve_columnar) so dtypes
#: and endianness can never drift: every column is a packed
#: little-endian fixed-width vector with NO padding or alignment (the
#: decoder reads with np.frombuffer at raw byte offsets; numpy handles
#: unaligned access). Array lengths derive from the frame header's
#: (n_txns, n_reads, n_writes) counts. The key blob follows as one
#: u32-length-prefixed contiguous slice.
COLUMNAR_LAYOUT = (
    ("snapshots", "<i8", "n_txns"),
    ("read_counts", "<u4", "n_txns"),
    ("write_counts", "<u4", "n_txns"),
    ("flags", "<u1", "n_txns"),
    ("key_lens", "<u4", "n_keys"),  # n_keys = 2*n_reads + 2*n_writes
)

#: flags bit 0: the txn asked for the conflicting-key-range report
COLUMNAR_FLAG_REPORT = 1

#: canonical key order inside key_lens / key_blob: all read-range begin
#: keys, then read ends, then write begins, then write ends — four
#: contiguous runs so each kernel column packs with ONE vectorized
#: scatter over its slice of the blob
_KEY_ORDER_DOC = ("read_begin", "read_end", "write_begin", "write_end")


@dataclasses.dataclass
class ColumnarBatch:
    """One resolve batch as flat columns (host side of the columnar
    wire frame; see COLUMNAR_LAYOUT for the wire dtypes).

    Versions are ABSOLUTE here (the proxy doesn't know the resolver's
    rebase base); pack_batch_columnar does the same vectorized
    offset/clamp pass pack_batch does. Keys are carried LOSSLESSLY in
    the blob — truncation of over-length keys happens only in the
    kernel packer, so the object-path fallback (native skip list / CPU
    oracle via columnar_to_transactions) sees exact bytes.
    """

    n_txns: int
    n_reads: int               # sum(read_counts) — cross-checked on decode
    n_writes: int              # sum(write_counts)
    snapshots: np.ndarray      # <i8 [n_txns] absolute read_snapshot
    read_counts: np.ndarray    # <u4 [n_txns]
    write_counts: np.ndarray   # <u4 [n_txns]
    flags: np.ndarray          # <u1 [n_txns] (COLUMNAR_FLAG_REPORT)
    key_lens: np.ndarray       # <u4 [2*n_reads + 2*n_writes], canonical order
    key_blob: Any              # bytes | memoryview, sum(key_lens) bytes

    def __eq__(self, other):
        if not isinstance(other, ColumnarBatch):
            return NotImplemented
        return (
            self.n_txns == other.n_txns
            and self.n_reads == other.n_reads
            and self.n_writes == other.n_writes
            and np.array_equal(self.snapshots, other.snapshots)
            and np.array_equal(self.read_counts, other.read_counts)
            and np.array_equal(self.write_counts, other.write_counts)
            and np.array_equal(self.flags, other.flags)
            and np.array_equal(self.key_lens, other.key_lens)
            and bytes(self.key_blob) == bytes(other.key_blob)
        )


def pack_columnar(transactions) -> ColumnarBatch:
    """Proxy-side columnar pack: CommitTransaction list -> flat columns,
    ONCE per batch at batch-build time (the per-key work is one bytes
    join; everything per-txn is bulk numpy). The resolver side never
    re-flattens: pack_batch_columnar scatters the blob straight into
    kernel tensors."""
    n = len(transactions)
    r_lists = [t.read_conflict_ranges for t in transactions]
    w_lists = [t.write_conflict_ranges for t in transactions]
    if n:
        read_counts = np.fromiter(
            (len(x) for x in r_lists), np.uint32, count=n
        )
        write_counts = np.fromiter(
            (len(x) for x in w_lists), np.uint32, count=n
        )
        snapshots = np.fromiter(
            (t.read_snapshot for t in transactions), np.int64, count=n
        )
        flags = np.fromiter(
            (
                COLUMNAR_FLAG_REPORT if t.report_conflicting_keys else 0
                for t in transactions
            ),
            np.uint8,
            count=n,
        )
    else:
        read_counts = write_counts = np.zeros((0,), np.uint32)
        snapshots = np.zeros((0,), np.int64)
        flags = np.zeros((0,), np.uint8)
    # canonical key order (_KEY_ORDER_DOC): four contiguous runs
    keys: list[bytes] = []
    for lists, side in ((r_lists, 0), (r_lists, 1), (w_lists, 0), (w_lists, 1)):
        keys.extend(rg[side] for lst in lists for rg in lst)
    nread, nwrite = int(read_counts.sum()), int(write_counts.sum())
    key_lens = (
        np.fromiter((len(k) for k in keys), np.uint32, count=len(keys))
        if keys
        else np.zeros((0,), np.uint32)
    )
    return ColumnarBatch(
        n_txns=n,
        n_reads=nread,
        n_writes=nwrite,
        snapshots=snapshots,
        read_counts=read_counts,
        write_counts=write_counts,
        flags=flags,
        key_lens=key_lens,
        key_blob=b"".join(keys),
    )


def pack_batch_columnar(
    cols: ColumnarBatch,
    version: int,
    base_version: int,
    config: KernelConfig,
    rungs=None,
) -> PackedBatch:
    """Columnar twin of pack_batch: flat columns -> kernel tensors.

    Byte-identical to ``pack_batch(txns, ...)`` whenever
    ``cols == pack_columnar(txns)`` (pinned in tests/test_packing.py)
    — the per-txn columns come from the same repeat/cumsum formulas and
    the key matrices from the same pack_keys_from_blob scatter, so the
    columnar wire path and the object path cannot diverge in what the
    kernel sees. No per-transaction Python objects are materialized.
    `rungs` pads to the first rung that holds the batch, as in
    pack_batch.
    """
    cfg = config
    n = cols.n_txns
    if n > cfg.max_txns:
        raise ValueError(f"{n} txns > max_txns {cfg.max_txns}")

    if n:
        off = cols.snapshots.astype(np.int64) - base_version
        r_counts = cols.read_counts.astype(np.int64)
        w_counts = cols.write_counts.astype(np.int64)
    else:
        off = r_counts = w_counts = np.zeros((0,), np.int64)
    nread = int(r_counts.sum())
    nwrite = int(w_counts.sum())
    b, nr, nw, txn_valid, snapshot, has_reads = _txn_columns(
        cfg, rungs, off, r_counts, nread, nwrite
    )
    w = cfg.key_words

    ids = np.arange(n, dtype=np.int32)
    r_txn = np.repeat(ids, r_counts)
    w_txn = np.repeat(ids, w_counts)
    r_starts = np.cumsum(r_counts) - r_counts if n else r_counts
    r_idx = (np.arange(nread) - np.repeat(r_starts, r_counts)).astype(np.int32)

    cat = np.frombuffer(cols.key_blob, np.uint8)
    lens = np.asarray(cols.key_lens, np.int64)
    starts = np.cumsum(lens) - lens

    def _col_keys(lo, m, cap, round_up):
        out = np.zeros((cap, w), np.uint32)
        if m:
            out[:m] = pack_keys_from_blob(
                cat, starts[lo : lo + m], lens[lo : lo + m],
                cfg.max_key_bytes, round_up=round_up,
            )
        return out

    rb = _col_keys(0, nread, nr, False)
    re = _col_keys(nread, nread, nr, True)
    wb = _col_keys(2 * nread, nwrite, nw, False)
    we = _col_keys(2 * nread + nwrite, nwrite, nw, True)

    def _col(vals, cap, dtype=np.int32, fill=0):
        out = np.full((cap,), fill, dtype)
        out[: len(vals)] = vals
        return out

    return PackedBatch(
        version=_clamp_version(version, base_version),
        new_oldest=_clamp_version(version - cfg.window_versions, base_version),
        n_txns=n,
        n_reads=nread,
        n_writes=nwrite,
        txn_valid=txn_valid,
        snapshot=snapshot,
        has_reads=has_reads,
        read_begin=rb,
        read_end=re,
        read_txn=_col(r_txn, nr, fill=b),
        read_index=_col(r_idx, nr),
        read_valid=_col([True] * nread, nr, bool),
        write_begin=wb,
        write_end=we,
        write_txn=_col(w_txn, nw, fill=b),
        write_valid=_col([True] * nwrite, nw, bool),
    )


def columnar_key(cols: ColumnarBatch, index: int) -> bytes:
    """Key `index` (canonical order) sliced out of the blob — used by
    the conflicting-key-range report assembly, which only touches the
    (rare) rows the kernel flagged."""
    lens = cols.key_lens
    start = int(np.asarray(lens[:index], np.int64).sum())
    return bytes(
        memoryview(cols.key_blob)[start : start + int(lens[index])]
    )


def columnar_to_transactions(cols: ColumnarBatch) -> list:
    """Columnar frame -> per-txn CommitTransaction objects: the OBJECT
    fallback for conflict backends that consume byte keys directly (the
    native skip list, the CPU oracle). Keys are exact — the blob
    carries full bytes, truncation only ever happens in the kernel
    packer — so decisions match the object wire path bit for bit."""
    from foundationdb_tpu.models.types import CommitTransaction

    lens = np.asarray(cols.key_lens, np.int64)
    ends = np.cumsum(lens)
    starts = ends - lens
    view = memoryview(cols.key_blob)
    keys = [bytes(view[s:e]) for s, e in zip(starts, ends)]
    nread, nwrite = cols.n_reads, cols.n_writes
    rb, re_ = keys[:nread], keys[nread : 2 * nread]
    wb = keys[2 * nread : 2 * nread + nwrite]
    we = keys[2 * nread + nwrite :]
    out = []
    ri = wi = 0
    for t in range(cols.n_txns):
        rc = int(cols.read_counts[t])
        wc = int(cols.write_counts[t])
        out.append(
            CommitTransaction(
                read_conflict_ranges=list(
                    zip(rb[ri : ri + rc], re_[ri : ri + rc])
                ),
                write_conflict_ranges=list(
                    zip(wb[wi : wi + wc], we[wi : wi + wc])
                ),
                read_snapshot=int(cols.snapshots[t]),
                report_conflicting_keys=bool(
                    int(cols.flags[t]) & COLUMNAR_FLAG_REPORT
                ),
            )
        )
        ri += rc
        wi += wc
    return out


def stack_device_args(batches) -> dict:
    """Stack PackedBatch device_args along a new leading axis — the
    input contract of TpuConflictSet.resolve_group_args. Single place so
    a new device_args key can never be silently dropped by callers."""
    import numpy as _np

    args = [b.device_args() for b in batches]
    versions = [int(a["version"]) for a in args]
    if any(b <= a for a, b in zip(versions, versions[1:])):
        # the group kernel's cross-batch visibility masks assume the
        # sequencer's monotone version order
        raise ValueError(f"stacked batch versions must ascend: {versions}")
    return {k: _np.stack([a[k] for a in args]) for k in args[0]}

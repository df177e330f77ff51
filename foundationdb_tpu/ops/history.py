"""Device-resident MVCC write history: the TPU-native ConflictSet state.

The reference keeps committed-write history in a version-annotated skip
list (fdbserver/SkipList.cpp — one mutable pointer structure, O(log n)
finger searches). A pointer structure is the wrong shape for a TPU, so the
same abstract object — a piecewise-constant map keyspace -> last-commit
version, with "overwrite range with version" updates, "max over range"
queries, and windowed GC (SkipList::removeBefore :576-608) — is held as
one sorted boundary array with per-segment versions plus a range-max
table.

Design note (measured on v5e): the structure is single-tier — one
sorted boundary array with per-segment versions; the merge is ONE
4-operand lax.sort + scans, with GC folded in (dead segments collapse
in the same pass). A sort-free merge via cross searchsorteds was built
and benchmarked at 8.7x WORSE: random gathers against loop-carried/
donated state cost ~6-15ns/element on this platform while argument
gathers are ~free, so search-heavy designs lose to the streaming sort.
Queries pay one binary search (begin key) + a bounded geometric probe
for the end key.

All shapes static; all functions pure; state is a NamedTuple pytree that
callers thread through `jax.jit` with donation.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.ops import keys as K
from foundationdb_tpu.ops import rangemax

VERSION_NEG = -(2**31) + 1  # plain int: jnp scalars must not leak into donated pytrees


class VersionHistory(NamedTuple):
    main_keys: jnp.ndarray   # [M, W] uint32 sorted boundaries (tail sentinel)
    main_ver: jnp.ndarray    # [M] int32 — version of [key_i, key_{i+1});
    #                          NEG from the last real boundary onward
    oldest: jnp.ndarray      # [] int32 current oldestVersion offset
    overflow: jnp.ndarray    # [] bool — merge exceeded main capacity
    # NOTE deliberately NOT carried: the [L, M] range-max table. It is
    # derived from main_ver at the start of each group —
    # carrying 66MB of derived data made lax.scan fusion copy it per
    # iteration (measured: fused dispatch SLOWER than sequential) and
    # tripled donation traffic.


def init(config: KernelConfig) -> VersionHistory:
    m = config.history_capacity
    main_ver = jnp.full((m,), VERSION_NEG, jnp.int32)
    return VersionHistory(
        main_keys=K.sentinel_like(m, config.key_words),
        main_ver=main_ver,
        oldest=jnp.int32(VERSION_NEG),
        overflow=jnp.asarray(False),
    )


def query_reads(
    state: VersionHistory,
    rb: jnp.ndarray,    # [Q, W] read-range begins
    re: jnp.ndarray,    # [Q, W] read-range ends
    snap: jnp.ndarray,  # [Q] int32 read snapshots
    main_tab: jnp.ndarray = None,  # [L, M] prebuilt range-max table
) -> jnp.ndarray:
    """conflict[q] = (max version over history segments intersecting
    [rb, re)) > snap — the CheckMax contract (SkipList.cpp:695-759).
    """
    return query_reads_vmax(state, rb, re, main_tab) > snap


def query_reads_vmax(
    state: VersionHistory,
    rb: jnp.ndarray,    # [Q, W] read-range begins
    re: jnp.ndarray,    # [Q, W] read-range ends
    main_tab: jnp.ndarray = None,  # [L, M] prebuilt range-max table
) -> jnp.ndarray:
    """[Q] int32: max version over history segments intersecting
    [rb, re) — the raw CheckMax value, before the snapshot compare (the
    tiered path's dedup probe shares one vmax across duplicate ranges
    whose snapshots differ, ops/delta.py).

    One searchsorted for the begin keys; the end position is found by
    geometric expansion from il (reads usually span few segments, so the
    common case is one bounded row-probe; wide scans fall back to more
    while_loop rounds, still exact).
    """
    m = state.main_keys.shape[0]
    il = K.searchsorted(state.main_keys, rb, side="right") - 1
    # ir = (last boundary < re) = searchsorted_left(re) - 1. Probe the 4
    # boundaries after il directly (reads usually span few segments); only
    # if some read overruns the probe window does the full binary search
    # run — lax.cond on a scalar, so the common case never pays it.
    span = 4
    idx = il[:, None] + jnp.arange(1, span + 1)[None, :]
    rows = state.main_keys[jnp.clip(idx, 0, m - 1)]  # [Q, span, W]
    lt = K.lex_less(rows, re[:, None, :]) & (idx < m)
    cnt = jnp.sum(lt.astype(jnp.int32), axis=1)
    ir = jax.lax.cond(
        jnp.any(cnt == span),
        lambda: K.searchsorted(state.main_keys, re, side="left") - 1,
        lambda: il + cnt,
    )
    if main_tab is None:
        main_tab = rangemax.build(state.main_ver, op="max")
    return rangemax.query(main_tab, jnp.maximum(il, 0), ir + 1, op="max")


def merge_writes(
    state: VersionHistory,
    run_bounds: jnp.ndarray,  # [Mf, W] sorted disjoint interval boundaries
    #                           (b0,e0,b1,e1,... sentinel tail)
    version: jnp.ndarray,     # [] int32 — commit version of the batch
    new_oldest: jnp.ndarray,  # [] int32 — MVCC floor (version - window)
) -> VersionHistory:
    """Overwrite the union of run intervals with `version`, raise the GC
    floor, and compact — one packed 4-operand sort + scans.

    Equivalent of mergeWriteConflictRanges + removeBefore
    (SkipList.cpp:430-441, 576-608) as a single functional pass:
    new_map(k) = version        if k inside the run union
               = old_map(k)     otherwise,
    with segments whose version falls below the floor collapsing to NEG.
    """
    m, w = state.main_keys.shape
    mf = run_bounds.shape[0]

    # A sort-free variant of this merge (cross searchsorteds + gathers,
    # since both inputs are sorted) was built and measured: 469ms vs the
    # sort's 54ms at bench shapes, because gathers from loop-carried/
    # donated buffers run ~100x slower than argument gathers on this
    # platform while lax.sort streams sequentially. The sort stays.
    #
    # Sort-operand packing: the tie-kind (main row before run row at
    # equal keys, so the carry includes the main value at that key) rides
    # the low bit of the length word — (len << 1) | kind preserves
    # (key bytes, len, kind) order exactly, and the parity delta of run
    # rows is re-derived AFTER the sort from their rank among run rows
    # (runs are disjoint strictly-increasing boundaries, so sorted order
    # preserves their begin/end alternation). Net: 4 operands.
    main_packed = (state.main_keys[:, w - 1] << 1) | jnp.uint32(0)
    run_packed = (run_bounds[:, w - 1] << 1) | jnp.uint32(1)
    packed = jnp.concatenate([main_packed, run_packed])
    # main rows carry their segment version; run rows carry NEG so the
    # carry scan yields the background value before the first boundary.
    val = jnp.concatenate(
        [state.main_ver, jnp.full((mf,), VERSION_NEG, jnp.int32)]
    )
    ops = [
        jnp.concatenate([state.main_keys[:, i], run_bounds[:, i]])
        for i in range(w - 1)
    ] + [packed, val]
    s = jax.lax.sort(ops, num_keys=w)
    s_packed, s_val = s[w - 1], s[w]
    is_main = (s_packed & 1) == 0
    s_len = s_packed >> 1
    # Sentinel rows: len word 0xFFFFFFFF packs to >= 0x7FFFFFFF after the
    # shift (no real key's length gets near it). Reconstruct the stored
    # key rows with the original length word.
    sent_len = jnp.uint32(0x7FFFFFFF)
    is_real = s_len < sent_len
    skeys = jnp.stack(
        list(s[: w - 1]) + [jnp.where(is_real, s_len, K.SENTINEL_WORD)],
        axis=-1,
    )

    # Carry scan: the old-map value in force at each sorted row.
    def last_valid(a, b):
        av, am = a
        bv, bm = b
        return jnp.where(bm, bv, av), am | bm

    carry_val, _ = jax.lax.associative_scan(
        last_valid, (s_val, is_main)
    )
    # Parity delta from run-row rank: even run ordinal = interval begin.
    is_run = ~is_main
    run_ord = jnp.cumsum(is_run.astype(jnp.int32))  # 1-based at run rows
    s_delta = jnp.where(
        is_run, 1 - 2 * ((run_ord - 1) & 1), 0
    ).astype(jnp.int32)
    covered = jnp.cumsum(s_delta) > 0
    new_val = jnp.where(covered, jnp.maximum(carry_val, version), carry_val)
    # GC floor: segments that can never conflict again die here.
    new_val = jnp.where(new_val < new_oldest, VERSION_NEG, new_val)

    prev_val = jnp.concatenate(
        [jnp.full((1,), VERSION_NEG, jnp.int32), new_val[:-1]]
    )
    keep = is_real & (new_val != prev_val)

    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    new_count = jnp.sum(keep.astype(jnp.int32))
    overflow = state.overflow | (new_count > m)
    dest = jnp.where(keep & (pos < m), pos, m)  # m = trash row

    new_keys = K.sentinel_like(m + 1, w).at[dest].set(skeys)[:m]
    new_ver = (
        jnp.full((m + 1,), VERSION_NEG, jnp.int32).at[dest].set(new_val)[:m]
    )
    oldest = jnp.maximum(state.oldest, new_oldest)

    return VersionHistory(
        main_keys=new_keys,
        main_ver=new_ver,
        oldest=oldest,
        overflow=overflow,
    )


def boundary_count(state: VersionHistory) -> jnp.ndarray:
    return jnp.sum(
        (~jnp.all(state.main_keys == K.SENTINEL_WORD, axis=-1)).astype(jnp.int32)
    )

"""Group conflict resolution: G batches, one device program, ONE sort.

This is the round-3 restructure of the resolver kernel (the TPU
replacement for ConflictBatch::detectConflicts,
fdbserver/SkipList.cpp:909-956), shaped by the measured v5e cost model:

* `lax.sort` streams at ~0.4ns/row/operand — sorts are nearly free.
* `searchsorted` costs ~100ns/query (20 gather rounds) — binary search
  is the single most expensive primitive and must not be on the hot
  path.
* every dispatch pays a fixed host round trip — batches must be grouped
  into one program.

So the kernel CO-SORTS the persistent history's boundary rows with every
conflict-range endpoint of all G batches in ONE mega-sort; every
position the old design binary-searched for now falls out of cumulative
sums over the sorted order:

  - `il`/`ir` (which history segments a read overlaps) come from a
    running count of history rows, read off at each point's sorted
    position — replacing 2 searchsorteds per read.
  - dense ranks (the intra-batch conflict universe) come from a running
    count of distinct keys (block index).
  - per-batch local ranks come from G lane-cumsums, so each batch's
    intra-batch fixpoint runs on a compact per-batch leaf space exactly
    like the round-2 single-batch kernel.
  - the merge of committed writes into history is a carry scan + dedup
    over the SAME sorted order — the mega-sort IS the merge sort.

Cross-batch semantics: a read in batch i conflicts with batch j<i's
committed writes only if version_j > read_snapshot — snapshots may land
between group commit versions, so visibility is per-(read,
writer-batch). The kernel resolves batches IN ORDER inside one trace
(a lax.scan whose carry is `seg_ver`, the running piecewise map of the
group's committed-write versions over the sorted block space): batch
i's reads first range-max `seg_ver` against their snapshot — exactly
the writes of earlier batches whose version exceeds the snapshot, i.e.
what sequential resolution would find in history — then run the
alternating fixpoint against their OWN batch's writers only. After the
verdicts, the batch's committed writes fold into `seg_ver` via a
parity-delta cumsum. Chains therefore stay within one batch (2-3
fixpoint iterations); cross-batch ordering is exact by construction.

The alternating fixpoint recurrence (see ops/conflict.py's original
derivation) is unchanged, per batch: committed[t] = ok[t] and no
committed earlier writer in the same batch intersects t's reads. F is
antitone and the dependency order is a DAG by txn index, so iteration
from the all-ok start converges to the unique sequential answer in
(max conflict-chain length + 1) rounds.

Decisions are bit-identical to resolving the G batches sequentially
(tests/test_group_parity.py drives both paths plus the Python oracle).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from foundationdb_tpu.ops import history as H
from foundationdb_tpu.ops import keys as K
from foundationdb_tpu.ops import rangemax, segtree
from foundationdb_tpu.ops.rangemax import INT32_POS

VERSION_NEG = H.VERSION_NEG

# Verdict codes — ConflictBatch::TransactionCommitResult
# (fdbserver/include/fdbserver/ConflictSet.h:41-46).
CONFLICT = 0
TOO_OLD = 1
COMMITTED = 3

# G's ceiling is compile cost, not correctness: the batch index rides
# `bits_b` bits of the packed sort key (stealing them from the length
# word) and the scan body compiles once for any G, but the skeleton's
# r_rows = M + 2G(NR+NW) arrays make XLA compile time grow with G
# (G=16 at bench shapes exceeded 35 minutes on this host).
MAX_GROUP = 16


class GroupVerdict(NamedTuple):
    """BatchVerdict with a leading [G] batch axis on every leaf."""

    verdict: jnp.ndarray             # [G, B] int32
    hist_conflict_read: jnp.ndarray  # [G, NR] bool — history OR earlier
    #                                  group batch conflict, per read range
    intra_first_range: jnp.ndarray   # [G, B] int32
    committed_count: jnp.ndarray     # [G] int32
    conflict_count: jnp.ndarray      # [G] int32
    too_old_count: jnp.ndarray       # [G] int32
    overflow: jnp.ndarray            # [G] bool (latched, broadcast)
    unconverged: jnp.ndarray         # [G] bool — fixpoint_latch mode
    #   only: some batch needed more than fixpoint_unroll applications.
    #   The returned STATE is the UNCHANGED input state and the verdicts
    #   are not trustworthy; the host re-dispatches with the exact
    #   (while_loop) kernel. Always False with fixpoint_latch=False.


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _sorted_counts(ids, n_seg: int):
    """off[t] = #{ids < t} for t in [0, n_seg], via two sorts.

    The sort+cumsum replacement for searchsorted/scatter histograms
    (the platform cost model: a sort streams at ~0.45ns/row/operand
    while a scatter pays ~50ns/update): co-sort the ids with the query
    points 0..n_seg (queries FIRST among equal keys), read the running
    id-count at each query row, then compact the query rows back to
    index order with a second sort. Returns [n_seg + 1] int32.
    """
    n = ids.shape[0]
    q = jnp.arange(n_seg + 1, dtype=jnp.int32)
    keys = jnp.concatenate([ids.astype(jnp.int32), q])
    isid = jnp.concatenate(
        [jnp.ones((n,), jnp.int32), jnp.zeros((n_seg + 1,), jnp.int32)]
    )
    sk, si = jax.lax.sort([keys, isid], num_keys=2)
    cnt = jnp.cumsum(si)  # at a query row (si == 0): #ids strictly < t
    _si2, _sk2, out = jax.lax.sort([si, sk, cnt], num_keys=2)
    return out[: n_seg + 1]


def _shift_down(x, fill):
    """x[i-1] with `fill` at i=0 (prev-row view of a sorted column)."""
    return jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])


def resolve_group(state: H.VersionHistory, g: dict, *,
                  short_span_limit: int = 0,
                  fixpoint_unroll: int = 3,
                  fixpoint_latch: bool = False,
                  extra_stale=None,
                  _ablate: frozenset = frozenset()):
    """Resolve G stacked batches in one program.

    `g` is a stacked device_args tree (leaves [G, ...]); versions must be
    strictly increasing across the group (the caller asserts — the
    sequencer hands out monotone batch versions by construction).
    Returns (new_state, GroupVerdict).

    `short_span_limit` (static): 0 compiles the fully general doubling
    structures. A positive S compiles DIRECT S-wide gather/scatter range
    ops instead — the doubling cover + two table builds per fixpoint
    application cost ~40 small latency-bound passes on v5e, while point
    workloads (conflict ranges a few keys wide, e.g. the reference's own
    skipListTest shapes) span only a handful of rank blocks. Exactness
    is preserved by a latch: if any live range spans more than S blocks,
    the overflow flag trips and the host refuses the results (the same
    static-capacity discipline as history overflow) — never a silent
    wrong answer. Leave 0 for arbitrary workloads (range scans).

    `extra_stale` ([G, NR] bool or None): per-read-range conflict hits
    computed OUTSIDE this kernel against history this call's `state`
    does not hold — the tiered path (ops/delta.py) resolves against the
    delta tier here and injects its main-tier probe results through
    this. Hits are OR'd into the phase-1 stale set (masked by
    read_live), so verdicts, reports and the fixpoint treat them
    exactly like segment hits on `state` itself.

    `_ablate` (static, diagnostic only — scripts/profile_group.py):
    stage names whose work is stubbed out to attribute in-kernel cost;
    results are WRONG with any stage ablated.
    """
    gn, b = g["txn_valid"].shape
    nr = g["read_valid"].shape[1]
    nw = g["write_valid"].shape[1]
    m, w = state.main_keys.shape
    if gn > MAX_GROUP:
        raise ValueError(f"group of {gn} > MAX_GROUP {MAX_GROUP}")
    rn, wn = gn * nr, gn * nw
    r_rows = m + 2 * rn + 2 * wn

    versions = g["version"].astype(jnp.int32)          # [G] ascending
    floors = g["new_oldest"].astype(jnp.int32)         # [G]
    final_version = versions[gn - 1]
    final_floor = jnp.max(floors)

    def fl(x):
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

    txn_valid = fl(g["txn_valid"])                     # [G*B]
    snapshot = fl(g["snapshot"])                       # [G*B]
    has_reads = fl(g["has_reads"])

    # ---- tooOld classification (per batch floor; SkipList.cpp:819-828)
    too_old = txn_valid & has_reads & (snapshot < jnp.repeat(floors, b))

    r_batch = jnp.repeat(jnp.arange(gn, dtype=jnp.int32), nr)   # [RN]
    w_batch = jnp.repeat(jnp.arange(gn, dtype=jnp.int32), nw)   # [WN]
    r_txn = fl(g["read_txn"])                          # [RN] within-batch idx
    w_txn = fl(g["write_txn"])
    r_gid = r_batch * b + r_txn                        # [RN] global txn ids
    w_gid = w_batch * b + w_txn

    read_live = fl(g["read_valid"]) & ~too_old[r_gid]
    write_live = fl(g["write_valid"]) & ~too_old[w_gid]
    read_snap = snapshot[r_gid]

    # ---- the mega-sort -------------------------------------------------
    # Rows: [main(M)] ++ [rb(RN)] ++ [re(RN)] ++ [wb(WN)] ++ [we(WN)].
    # Sort key: (byte words..., pk) where pk packs
    #   (len << (bits_b+3)) | (is_point << (bits_b+2)) | (batch << 2) | type
    # so equal full keys group into one block with main rows FIRST (their
    # running count then gives searchsorted-right semantics at begin
    # points for free) and point rows batch-contiguous (local ranks).
    bits_b = max(1, (gn - 1).bit_length()) if gn > 1 else 1
    sh_pt = bits_b + 2
    sh_len = bits_b + 3
    max_len = 0xFFFFFFFF >> sh_len  # lens above this are sentinels anyway

    def pk_of(keys, is_point, batch, typ, live):
        lenw = keys[:, w - 1]
        sent = (lenw > max_len) | ~live
        pk = (
            (lenw << sh_len)
            | (jnp.uint32(is_point) << sh_pt)
            | (batch.astype(jnp.uint32) << 2)
            | jnp.uint32(typ)
        )
        return jnp.where(sent, K.SENTINEL_WORD, pk)

    rb_k, re_k = fl(g["read_begin"]), fl(g["read_end"])
    wb_k, we_k = fl(g["write_begin"]), fl(g["write_end"])
    main_live = ~jnp.all(state.main_keys == K.SENTINEL_WORD, axis=-1)
    zero_b = jnp.zeros((m,), jnp.int32)
    pks = jnp.concatenate([
        pk_of(state.main_keys, 0, zero_b, 0, main_live),
        pk_of(rb_k, 1, r_batch, 0, read_live),
        pk_of(re_k, 1, r_batch, 1, read_live),
        pk_of(wb_k, 1, w_batch, 2, write_live),
        pk_of(we_k, 1, w_batch, 3, write_live),
    ])

    def col(i):
        cols = [state.main_keys[:, i], rb_k[:, i], re_k[:, i],
                wb_k[:, i], we_k[:, i]]
        # dead rows must sort to the tail with their pk sentinel
        sent = pks == K.SENTINEL_WORD
        return jnp.where(sent, K.SENTINEL_WORD, jnp.concatenate(cols))

    iota = jnp.arange(r_rows, dtype=jnp.int32)
    # main_ver rides the sort as a value operand (+1 operand at
    # ~0.45ns/row) so the merge phase needs no 2.9M-row gather for it
    mver_col = jnp.concatenate([
        state.main_ver,
        jnp.full((2 * rn + 2 * wn,), VERSION_NEG, jnp.int32),
    ])
    ops = [col(i) for i in range(w - 1)] + [pks, iota, mver_col]
    s = jax.lax.sort(ops, num_keys=w)
    skw = s[: w - 1]
    spk, siota = s[w - 1], s[w]
    s_mver = s[w + 1]

    is_sent = spk == K.SENTINEL_WORD
    s_is_main = (((spk >> sh_pt) & 1) == 0) & ~is_sent
    s_len = spk >> sh_len

    # block = run of rows with one full key (byte words + len)
    same_prev = jnp.ones((r_rows,), bool)
    for c in skw:
        same_prev &= c == _shift_down(c, jnp.uint32(0xDEADBEEF))
    same_prev &= s_len == _shift_down(s_len, jnp.uint32(0xDEADBEEF))
    key_new = ~same_prev
    key_new = key_new.at[0].set(True)

    bi = jnp.cumsum(key_new.astype(jnp.int32)) - 1          # block index
    cm = jnp.cumsum(s_is_main.astype(jnp.int32))            # incl. main count
    # mains before each row's BLOCK: at a block-start row that is
    # cm - is_main there; cm is nondecreasing, so a running max carries
    # it across the block — no block-start gathers needed
    mains_before_block = jax.lax.cummax(
        jnp.where(key_new, cm - s_is_main.astype(jnp.int32), -1)
    )
    il_row = cm - 1                    # searchsorted-right(key) - 1 vs main
    ir_row = mains_before_block - 1    # searchsorted-left(key) - 1 vs main

    # ---- per-batch local ranks: one BATCHED sort over [G, P] ----------
    # Dense ranks of the full key (byte words + len) among each batch's
    # own point rows — identical to the global block ranks restricted
    # per batch (what the intra-batch fixpoint needs), but computed by
    # a [G, 2(NR+NW)]-shaped sort + row cumsum + inverse sort instead
    # of the r3-r5 [r_rows, G] one-hot cumsum + flat gather: the r5
    # jax.profiler trace attributed the two largest skeleton fusions
    # (~41 ms/group at bench shapes) to that one-hot machinery, while
    # these sorts stream ~2.1M rows once. Dead rows key to the
    # sentinel; their ranks are garbage and every consumer masks by
    # read_live/write_live (unchanged contract).
    if "lcum" in _ablate:
        lq_lo = lq_hi = jnp.zeros((gn, nr), jnp.int32)
        lw_lo = lw_hi = jnp.zeros((gn, nw), jnp.int32)
    else:
        p_per = 2 * nr + 2 * nw
        rl2 = read_live.reshape(gn, nr)
        wl2 = write_live.reshape(gn, nw)
        live_p = jnp.concatenate([rl2, rl2, wl2, wl2], axis=1)  # [G, P]

        def pcol(i):
            c = jnp.concatenate([
                rb_k[:, i].reshape(gn, nr), re_k[:, i].reshape(gn, nr),
                wb_k[:, i].reshape(gn, nw), we_k[:, i].reshape(gn, nw),
            ], axis=1)
            return jnp.where(live_p, c, K.SENTINEL_WORD)

        iota_p = jnp.broadcast_to(
            jnp.arange(p_per, dtype=jnp.int32)[None, :], (gn, p_per)
        )
        ps = jax.lax.sort(
            [pcol(i) for i in range(w)] + [iota_p], num_keys=w
        )
        pnew = jnp.zeros((gn, p_per), bool)
        for c in ps[:w]:
            prev = jnp.concatenate(
                [jnp.full((gn, 1), 0xDEADBEEF, c.dtype), c[:, :-1]], axis=1
            )
            pnew |= c != prev
        pnew = pnew.at[:, 0].set(True)
        prank = jnp.cumsum(pnew.astype(jnp.int32), axis=1) - 1
        _, lrank2 = jax.lax.sort([ps[w], prank], num_keys=1)  # [G, P]
        lq_lo = lrank2[:, :nr]
        lq_hi = lrank2[:, nr : 2 * nr]
        lw_lo = lrank2[:, 2 * nr : 2 * nr + nw]
        lw_hi = lrank2[:, 2 * nr + nw :]

    # ---- per-point data back to input order: ONE sort, not scatters ----
    # Route by ROW ORIGIN (point rows are siota >= m, live or dead), so
    # every point ordinal 0..p_pts-1 appears exactly once and a stable
    # sort keyed by ordinal is a perfect inverse permutation. One
    # 4-operand sort (~r_rows x 4 x 0.45ns) replaces four ~50ns/update
    # scatters. Dead points now carry GARBAGE values (the old scatters
    # filled -1/0): every consumer masks by read_live/write_live.
    p_pts = 2 * rn + 2 * wn
    po_all = jnp.where(siota >= m, siota - m, p_pts)
    sp = jax.lax.sort(
        [po_all, bi, il_row, ir_row], num_keys=1
    )
    rank_pt = sp[1][:p_pts]
    il_pt = sp[2][:p_pts]
    ir_pt = sp[3][:p_pts]

    rank_rb, rank_re = rank_pt[:rn], rank_pt[rn : 2 * rn]
    rank_wb = rank_pt[2 * rn : 2 * rn + wn]
    rank_we = rank_pt[2 * rn + wn :]
    il = il_pt[:rn]
    ir = ir_pt[rn : 2 * rn]

    # span-violation latch for the short_span_limit fast paths
    span_ok = jnp.asarray(True)

    def direct_range_op(values, lo, hi, *, op, span):
        """op over values[lo:hi] per query via `span` direct gathers —
        exact when hi-lo <= span (the caller latches violations)."""
        fn, ident = rangemax._OPS[op]
        n = values.shape[0]
        acc = jnp.full(lo.shape, ident, values.dtype)
        for d in range(span):
            pos = lo + d
            v = values[jnp.clip(pos, 0, n - 1)]
            acc = fn(acc, jnp.where(pos < hi, v, ident))
        return acc

    # ---- phase 1: reads vs. persistent (pre-group) history -------------
    if "mainq" in _ablate:
        stale_hit = jnp.zeros((rn,), bool)
    elif short_span_limit:
        ss = short_span_limit
        span_ok &= jnp.max(
            jnp.where(read_live, (ir + 1) - jnp.maximum(il, 0), 0)
        ) <= ss
        vmax = direct_range_op(
            state.main_ver, jnp.maximum(il, 0), ir + 1, op="max", span=ss
        )
        stale_hit = (vmax > read_snap) & read_live
    else:
        main_tab = rangemax.build(state.main_ver, op="max")
        vmax = rangemax.query(main_tab, jnp.maximum(il, 0), ir + 1, op="max")
        stale_hit = (vmax > read_snap) & read_live

    if extra_stale is not None:
        # externally-probed history hits (tiered path): same standing as
        # phase-1 segment hits on this call's own state
        stale_hit = stale_hit | (fl(extra_stale) & read_live)

    # ---- per-txn read windows (replaces scatter segment-reductions) ----
    # LAYOUT CONTRACT (utils/packing.pack_batch): within a batch, reads
    # are grouped by txn in nondecreasing txn order, and padded rows
    # carry read_txn == B — so the flat segment id below is globally
    # nondecreasing and every txn's reads occupy one contiguous window
    # [off[t], off[t+1]) of the flat read array. Per-txn reductions then
    # become cumsum + two flat gathers instead of a ~50ns/update
    # scatter. (The sharded path only flips validity bits, never
    # reorders rows, so clipping preserves the contract.)
    seg_id = r_batch * (b + 1) + r_txn              # [RN], nondecreasing
    off_flat = _sorted_counts(seg_id, gn * (b + 1))  # [G*(b+1)+1]
    offs2 = off_flat[:-1].reshape(gn, b + 1)         # off[i*(b+1)+k]
    win_lo = offs2[:, :b]                            # [G, B] flat bounds
    win_hi = offs2[:, 1:]

    def per_txn_any(read_bits):
        cs = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            jnp.cumsum(read_bits.astype(jnp.int32)),
        ])
        return (cs[win_hi.reshape(-1)] - cs[win_lo.reshape(-1)]) > 0

    hist_conflict_txn0 = per_txn_any(stale_hit)

    # ---- phase 2: per-batch fixpoints over a running coverage map ------
    # Batches resolve IN ORDER inside the trace, exactly like the
    # sequential pipeline: batch i's reads first query `seg_ver` — the
    # running piecewise map of the group's committed-write versions so
    # far — with the exact version-vs-snapshot comparison (a snapshot
    # between two group versions sees precisely the earlier writes), then
    # run the round-2 alternating fixpoint against their OWN batch's
    # writers only. Chains therefore stay within one batch (2-3
    # iterations); the earlier whole-group fixpoint paid G-deep
    # cross-batch chains and a full coverage rebuild per iteration.
    leaves_local = _next_pow2(2 * nr + 2 * nw)
    r_txn2 = r_txn.reshape(gn, nr)
    read_live2 = read_live.reshape(gn, nr)
    snap2 = read_snap.reshape(gn, nr)
    stale2 = stale_hit.reshape(gn, nr)
    w_txn2 = w_txn.reshape(gn, nw)
    w_live2 = write_live.reshape(gn, nw)
    wlo2 = jnp.where(w_live2, lw_lo, 0)
    whi2 = jnp.where(w_live2, lw_hi, 0)
    rank_rb2 = rank_rb.reshape(gn, nr)
    rank_re2 = rank_re.reshape(gn, nr)
    rank_wb2 = rank_wb.reshape(gn, nw)
    rank_we2 = rank_we.reshape(gn, nw)
    too_old2 = too_old.reshape(gn, b)
    txn_valid2 = txn_valid.reshape(gn, b)
    read_index2 = fl(g["read_index"]).reshape(gn, nr)

    # The per-batch step runs under lax.scan: ONE traced/compiled body
    # regardless of G (the unrolled loop's compile time grew ~linearly
    # with G and exceeded 35 minutes at G=16 on this host). The carry is
    # the running coverage map (+ the span latch); everything else rides
    # the scan's per-batch xs slices. Batch 0 needs no special case: the
    # initial all-NEG seg_ver answers every cross query with "no
    # earlier write".
    def batch_step(carry, xs):
        seg_ver, span_ok, fix_ok = carry
        (lqlo, lqhi, wlo, whi, rrb, rre, rwb, rwe, rtxn, rlive, wlive,
         wtxn, snap, stale, toold, tvalid, ridx, ver, twl, twh) = xs
        converged = jnp.asarray(True)

        def per_txn(read_bits):
            # txn-window cumsum-diff (bits must be pre-masked by rlive;
            # see the layout contract where the windows are built)
            cs = jnp.concatenate([
                jnp.zeros((1,), jnp.int32),
                jnp.cumsum(read_bits.astype(jnp.int32)),
            ])
            return (cs[twh] - cs[twl]) > 0

        if short_span_limit and gn > 1:
            # the cross-batch query walks GLOBAL block ranks — its span
            # must be latched too, or wide reads would silently miss
            # earlier in-group writes. At G=1 the cross query itself is
            # statically dead (skipped below), so latching its span
            # would be a spurious refusal.
            span_ok &= jnp.max(
                jnp.where(rlive, rre - rrb, 0)
            ) <= short_span_limit
        if short_span_limit:
            span_ok &= jnp.max(
                jnp.where(wlive, whi - wlo, 0)
            ) <= short_span_limit
            span_ok &= jnp.max(
                jnp.where(rlive, lqhi - lqlo, 0)
            ) <= short_span_limit

        if "cross" in _ablate or gn == 1:
            # G=1: the cross query runs BEFORE this batch's writes fold
            # into seg_ver, and with a single batch seg_ver is still the
            # all-NEG initial carry — the query is statically dead, so
            # skip its table build entirely (the biggest in-kernel cost
            # of the per-batch tiered path, and a free win for the
            # classic resolve_batch G=1 specialization).
            cross_g = jnp.zeros((nr,), bool)
        elif short_span_limit:
            gmax = direct_range_op(
                seg_ver, rrb, rre, op="max", span=short_span_limit
            )
            cross_g = (gmax > snap) & rlive
        else:
            # two-level table: this build runs once PER BATCH inside the
            # scan over the full ~r_rows domain — the flat doubling
            # table's 23 full-width levels were the cross phase's cost
            # (~70ms/group, r4 ablations); build2 writes ~6.6 passes
            # (an r5 experiment replaced this per-batch build with
            # scan-carried prefix COUNTS of committed-write endpoints —
            # algorithmically fewer full-width passes, but it measured
            # 526.6 vs 415.5 ms/group on v5e: the big carried arrays +
            # dynamic_update_slice under the scan cost more than the
            # build they removed (round-5 measurement). Reverted.)
            gtab = rangemax.build2(seg_ver, op="max")
            gmax = rangemax.query2(gtab, rrb, rre, op="max")
            cross_g = (gmax > snap) & rlive
        ok_g = tvalid & ~toold & ~per_txn(stale | cross_g)

        def same_hits_g(committed_g):
            val = jnp.where(
                committed_g[wtxn] & wlive, wtxn, INT32_POS
            )
            if short_span_limit:
                # direct S-wide cover: scatter-min val at every covered
                # leaf (exact under the span latch)
                flat = jnp.full((leaves_local + 1,), INT32_POS, jnp.int32)
                for d in range(short_span_limit):
                    pos = wlo + d
                    idx = jnp.where(pos < whi, pos, leaves_local)
                    flat = flat.at[idx].min(val)
                mw = flat[:leaves_local]
                minw = direct_range_op(
                    mw, lqlo, lqhi, op="min", span=short_span_limit
                )
            else:
                # radix-2 structures. An r5 experiment switched this
                # pipeline to radix-4 (min_cover4/build4/query4 — half
                # the sequential levels, 4-endpoint batched gathers):
                # it measured SLOWER in-kernel, 431.7 vs 379.2 ms/group
                # at bench shapes (round-5 measurement) — the 2x
                # gather/scatter data outweighs the halved level count
                # here. The radix-4 structures stay in ops/ (parity-
                # tested) as a measured-negative option.
                mw = segtree.min_cover(leaves_local, wlo, whi, val)
                mtab = rangemax.build(mw, op="min")
                minw = rangemax.query(mtab, lqlo, lqhi, op="min")
            return (minw < rtxn) & rlive

        def cond(c):
            committed_g, prev, _h = c
            return jnp.any(committed_g != prev)

        def body(c):
            committed_g, _prev, _h = c
            h = same_hits_g(committed_g)
            return ok_g & ~per_txn(h & ok_g[rtxn]), committed_g, h

        if "fixpoint" in _ablate:
            committed_g = ok_g
            final_same_g = jnp.zeros((nr,), bool)
        elif "fix1" in _ablate:  # diagnostic: exactly one application
            h0 = same_hits_g(ok_g)
            committed_g = ok_g & ~per_txn(h0 & ok_g[rtxn])
            final_same_g = h0 & ok_g[rtxn]
        else:
            # Unrolled applications first, residual while_loop after: a
            # while ITERATION under the batch scan measured ~5x an
            # unrolled application (r4 ablations: 129ms/group of loop
            # iterations at uniform vs 13ms/group for an application),
            # so `fixpoint_unroll` straight-line applications cover the
            # workload's typical convergence depth and the loop usually
            # runs ZERO iterations. Deeper chains still resolve exactly
            # in the loop — the unroll is a perf knob, never semantics.
            h_prev = same_hits_g(ok_g)
            c_prev = ok_g
            c_cur = ok_g & ~per_txn(h_prev & ok_g[rtxn])
            for _ in range(max(1, fixpoint_unroll) - 1):
                h_prev = same_hits_g(c_cur)
                c_prev, c_cur = c_cur, ok_g & ~per_txn(
                    h_prev & ok_g[rtxn]
                )
            if fixpoint_latch or "nowhile" in _ablate:
                # LATCH mode: no residual while_loop at all — its mere
                # presence measured ~50ms/group of XLA pessimization
                # even at zero iterations (r4: 405 vs 354 ms/group).
                # Convergence is CHECKED, not assumed: an unconverged
                # batch trips the group-wide latch, the state returns
                # UNCHANGED, and the host re-dispatches on the exact
                # while kernel (the short_span_limit refusal pattern).
                converged = ~jnp.any(c_cur != c_prev)
                committed_g, last_h = c_cur, h_prev
            else:
                committed_g, _, last_h = jax.lax.while_loop(
                    cond, body, (c_cur, c_prev, h_prev)
                )
            # last_h is the hits AT the fixpoint (carried from prev ==
            # fixpoint — the round-2 kernel's argument).
            final_same_g = last_h & ok_g[rtxn]

        if "seg" not in _ablate:
            # fold this batch's committed writes into the running map
            cw = committed_g[wtxn] & wlive
            dd = (
                jnp.zeros((r_rows + 1,), jnp.int32)
                .at[jnp.where(cw, rwb, r_rows)].add(1)
                .at[jnp.where(cw, rwe, r_rows)].add(-1)[:r_rows]
            )
            covered = jnp.cumsum(dd) > 0
            seg_ver = jnp.where(covered, ver, seg_ver)

        # first conflicting read-range index per txn: reads sit in range
        # order inside their window, so the first hit POSITION carries
        # the min index — locate it by compacting hit positions to the
        # front with one small sort and gathering at the window's
        # preceding-hit count.
        csh = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            jnp.cumsum(final_same_g.astype(jnp.int32)),
        ])
        n_before = csh[twl]
        tot_h = csh[twh] - n_before
        iota_nr = jnp.arange(nr, dtype=jnp.int32)
        (tpos,) = jax.lax.sort(
            [jnp.where(final_same_g, iota_nr, jnp.int32(nr))]
        )
        p = tpos[jnp.clip(n_before, 0, nr - 1)]
        fidx = ridx[jnp.clip(p, 0, nr - 1)]
        first_g = jnp.where(tot_h > 0, fidx, INT32_POS)
        return (seg_ver, span_ok, fix_ok & converged), (
            committed_g, final_same_g, cross_g, first_g
        )

    # The initial carry must inherit the axis-varying type of the traced
    # inputs, or lax.scan rejects the carry under shard_map (the sharded
    # multi-resolver path). `bi` derives from the co-sort of the SHARDED
    # history state, so it carries the manual-axis varyingness exactly
    # when anything does; adding 0*bi[0] is numerically a no-op.
    seg_ver0 = jnp.full((r_rows,), VERSION_NEG, jnp.int32) + 0 * bi[0]
    span_ok = span_ok & (bi[0] == bi[0])
    fix_ok0 = bi[0] == bi[0]  # True, with the shard_map varying type
    lane_base = (jnp.arange(gn, dtype=jnp.int32) * nr)[:, None]
    xs = (
        lq_lo, lq_hi, wlo2, whi2, rank_rb2, rank_re2, rank_wb2,
        rank_we2, r_txn2, read_live2, w_live2, w_txn2, snap2, stale2,
        too_old2, txn_valid2, read_index2, versions,
        win_lo - lane_base, win_hi - lane_base,
    )
    (seg_ver, span_ok, fix_ok), (committed2, same2, cross2, first2) = (
        jax.lax.scan(batch_step, (seg_ver0, span_ok, fix_ok0), xs)
    )
    committed = committed2.reshape(-1)
    final_same = same2.reshape(-1)
    # The cross-batch report is NOT masked by `ok`: sequentially these
    # writes sit in history when batch i resolves, and the round-2
    # kernel reports hist_conflict_read masked only by read_live — a
    # txn condemned by pre-group history still reports its other
    # conflicting reads (tests/test_group_parity.py prestate case).
    final_cross = cross2.reshape(-1)

    # ---- verdicts ------------------------------------------------------
    hist_conflict_read = stale_hit | final_cross
    hist_conflict_txn = hist_conflict_txn0 | per_txn_any(final_cross)

    first_idx = first2.reshape(-1)
    intra_first_range = jnp.where(
        committed | ~txn_valid | too_old | hist_conflict_txn,
        -1,
        jnp.where(first_idx == INT32_POS, -1, first_idx),
    )

    verdict = jnp.where(
        too_old,
        TOO_OLD,
        jnp.where(committed & txn_valid, COMMITTED, CONFLICT),
    ).astype(jnp.int32)

    v2 = verdict.reshape(gn, b)
    committed_count = jnp.sum(
        (committed & txn_valid).reshape(gn, b).astype(jnp.int32), axis=1
    )
    too_old_count = jnp.sum(too_old.reshape(gn, b).astype(jnp.int32), axis=1)
    conflict_count = (
        jnp.sum(txn_valid.reshape(gn, b).astype(jnp.int32), axis=1)
        - committed_count
        - too_old_count
    )

    # ---- phase 3: merge committed writes into history ------------------
    # `seg_ver` after the batch loop IS the group's committed-write map
    # (last writer's version per block — what sequential merges leave).
    gval = seg_ver[jnp.clip(bi, 0, r_rows - 1)]

    mval = jnp.where(s_is_main, s_mver, VERSION_NEG)

    def last_valid(a, bb):
        av, am = a
        bv, bm = bb
        return jnp.where(bm, bv, av), am | bm

    if "merge" in _ablate:
        new_state = state._replace(
            overflow=state.overflow | (seg_ver[0] > jnp.int32(2**30))
        )
        overflow = new_state.overflow
    else:
        carry_val, _ = jax.lax.associative_scan(
            last_valid, (mval, s_is_main)
        )

        new_val = jnp.maximum(carry_val, gval)
        new_val = jnp.where(new_val < final_floor, VERSION_NEG, new_val)
        prev_val = _shift_down(new_val, jnp.int32(VERSION_NEG))
        keep = key_new & ~is_sent & (new_val != prev_val)

        new_count = jnp.sum(keep.astype(jnp.int32))
        # ~span_ok: a short_span_limit build saw a wider range than
        # configured — same loud-refusal discipline as capacity overflow
        overflow = state.overflow | (new_count > m) | ~span_ok

        # Compact kept rows by SORT, not scatter: a 2.9M-row scatter
        # measured ~200ms while lax.sort streams the same rows in ~7ms
        # (the platform cost model). One packed key — dropped rows to
        # the back, kept rows in original (already key-sorted) order —
        # makes it a single 5-operand sort; rows past new_count are
        # masked back to sentinel/NEG after the slice.
        ckey = ((~keep).astype(jnp.uint32) << 31) | (
            iota.astype(jnp.uint32) & 0x7FFFFFFF
        )
        len_word = jnp.where(is_sent, K.SENTINEL_WORD, s_len)
        s2 = jax.lax.sort(
            [ckey] + list(skw) + [len_word, new_val], num_keys=1
        )
        live = jnp.arange(m, dtype=jnp.int32) < new_count
        new_keys = jnp.stack(
            [
                jnp.where(live, c[:m], K.SENTINEL_WORD)
                for c in list(s2[1:w]) + [s2[w]]
            ],
            axis=-1,
        )
        new_ver = jnp.where(live, s2[w + 1][:m], VERSION_NEG)

        new_state = H.VersionHistory(
            main_keys=new_keys,
            main_ver=new_ver,
            oldest=jnp.maximum(state.oldest, final_floor),
            overflow=overflow,
        )
    unconv = ~fix_ok
    if fixpoint_latch:
        # a tripped latch must leave the persistent history UNTOUCHED:
        # the host re-runs the whole group on the exact while kernel
        # against the same input state
        new_state = jax.tree.map(
            lambda old, new: jnp.where(unconv, old, new), state, new_state
        )
    out = GroupVerdict(
        verdict=v2,
        hist_conflict_read=hist_conflict_read.reshape(gn, nr),
        intra_first_range=intra_first_range.reshape(gn, b),
        committed_count=committed_count,
        conflict_count=conflict_count,
        too_old_count=too_old_count,
        overflow=jnp.broadcast_to(overflow, (gn,)),
        unconverged=jnp.broadcast_to(unconv, (gn,)),
    )
    return new_state, out

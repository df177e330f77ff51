"""Delta-tiered conflict resolution: G-independent compile, tiered merge.

The round-3..5 group kernel (ops/group.py) co-sorts the FULL persistent
history with every point of all G batches: one skeleton of
r_rows = M + 2G(NR+NW) rows, plus a full-width cross-phase table build
per batch inside its scan. Two measured walls followed (VERDICT r5):

* XLA compile time grows with G through the G-sized skeleton arrays
  (G=16 at bench shapes exceeded 35 minutes), capping the main
  throughput lever — group size — at MAX_GROUP=16.
* Every group pays full-skeleton history-merge passes (~180ms/group at
  bench shapes) even though a group's writes touch a sliver of history.

This module is the round-6 restructure. History becomes TWO tiers:

* `main` — the big compacted tier. IMMUTABLE during a group, so its
  range-max table is built once per group and every batch probes it
  with binary searches (+ the table query) — no main-sized sort
  anywhere in the group hot path.
* `delta` — a small tier holding the boundaries written since the last
  compaction. Each batch resolves against delta with the EXACT group
  kernel at G=1 (`ops/group.resolve_group` — same mega-sort/cumsum
  machinery, over D + 2(NR+NW) rows instead of M + 2G(NR+NW)) and
  merges its committed writes into delta in the same call. Delta
  occupancy scales with DISTINCT written boundaries, so hot-key (zipf)
  streams keep it tiny.

`resolve_group_tiered` runs the per-batch body under ONE `lax.scan`:
every shape in the body is independent of G, so XLA traces and compiles
the body once no matter the group size — G=32/64 costs the same compile
as G=2, and the ~100ms dispatch fence amortizes across a group as large
as the version chain allows. Cross-batch visibility inside a group is
exact by construction: batch j's committed writes land in delta with
version_j before batch j+1's body runs, and the delta query compares
versions against each read's snapshot — precisely what sequential
resolution would find in history (no seg_ver carry needed).

`compact` folds delta into main in one device program (co-sort of
M + D boundary rows, two carry scans, pointwise max, GC at the floor,
sort-compaction) — the only main-sized pass, off the per-batch path and
scheduled by the host every `compact_interval` batches.

Device-side hot-key dedup (`dedup_reads=U`): identical read conflict
ranges are sort+unique'd and only U DISTINCT ranges probe main, so the
binary-search traffic scales with distinct keys, not points. Zipf skew
alone does not make them few: a 64K-txn batch of 8 scrambled-zipfian
(0.99) point reads a txn over 10M keys has about 305K distinct read
ranges of its 524K (seeded batches of the benchmark's hot_rmw stream,
simulated on a CPU host), so U would have to be 512K there. A batch
with more distinct live ranges than U trips the unconverged latch:
state unchanged, host re-dispatches the exact kernel. Loud refusal,
never a silent wrong answer.

Sorted-endpoint RANGE SWEEP (`range_sweep=True`, ISSUE 14 — the
device-native range-overlap path): the default main-tier probe pays a
per-batch binary search per read range against the carried main keys
(the platform's single most expensive primitive — ops/keys.searchsorted
note) plus a bounded per-covered-block probe window for the end key
(ops/history.query_reads_vmax), which is exactly the regime where wide
scans lost to the CPU skiplist (0.28x on 500-key scans, r5). The sweep
replaces all of it with ONE co-sort per GROUP: main's boundary rows and
every batch's read begin/end endpoints stream through one lax.sort
(endpoint tie order re < main < rb gives searchsorted-left/right
semantics from a running main-row count), ranks invert back to input
order by a second sort, and each batch's probe inside the scan is one
O(1) range-max table query over [il, ir]. Wide scans therefore cost
O((M + G*R) log) streaming sorted work per group — the lax.sort
~0.45ns/row/operand class — instead of per-read log-M gather rounds
per batch, and there is NO dedup latch on this path (nothing is probed
per distinct range), so a range-heavy stream never escapes to the host.

Decisions are bit-identical to the reference's sequential pipeline
(tests/test_delta_parity.py drives the tiered kernel against the Python
oracle on adversarial shapes; the sweep path is pinned against the
probe path and the oracle on range-heavy streams).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.ops import group as G
from foundationdb_tpu.ops import history as H
from foundationdb_tpu.ops import keys as K
from foundationdb_tpu.ops import rangemax

VERSION_NEG = H.VERSION_NEG

# The scan-based tiered kernel has no sort-key bit-packing constraint on
# G (ops/group.MAX_GROUP's reason); this cap is a sanity bound only.
MAX_GROUP_TIERED = 64


class TieredState(NamedTuple):
    """Two-tier MVCC write history: immutable-per-group main + delta."""

    main: H.VersionHistory   # big tier, compacted periodically
    delta: H.VersionHistory  # small tier: boundaries since last compaction


class BatchVerdict(NamedTuple):
    """One batch's row of a GroupVerdict (ConflictBatch's per-batch
    result, fdbserver/include/fdbserver/ConflictSet.h:41-46)."""

    verdict: jnp.ndarray          # [B] int32 (CONFLICT/TOO_OLD/COMMITTED)
    hist_conflict_read: jnp.ndarray  # [NR] bool — per read range, history hit
    intra_first_range: jnp.ndarray   # [B] int32 — first intra-batch
    #                                  conflicting read-range index, else -1
    committed_count: jnp.ndarray  # [] int32
    conflict_count: jnp.ndarray   # [] int32
    too_old_count: jnp.ndarray    # [] int32
    overflow: jnp.ndarray         # [] bool — history capacity exceeded by
    #   this batch's merge (or latched earlier). Surfaced in the verdict so
    #   the sync the host already pays to read verdicts also proves the
    #   history they were computed against didn't truncate (ADVICE r1).
    fixpoint_iters: jnp.ndarray   # [] int32 — intra-batch fixpoint
    #   applications run (GroupVerdict.fixpoint_iters)


def init(config: KernelConfig) -> TieredState:
    d = config.delta_capacity
    delta = H.VersionHistory(
        main_keys=K.sentinel_like(d, config.key_words),
        main_ver=jnp.full((d,), VERSION_NEG, jnp.int32),
        oldest=jnp.int32(VERSION_NEG),
        overflow=jnp.asarray(False),
    )
    return TieredState(main=H.init(config), delta=delta)


def _shift_down(x, fill):
    """x[i-1] with `fill` at i=0 (prev-row view of a sorted column)."""
    return jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])


def _main_stale(main: H.VersionHistory, main_tab, rb, re, rsnap, rvalid,
                dedup: int):
    """Probe the (immutable) main tier for one batch's read ranges.

    Returns (stale [NR] bool, dedup_ok [] bool). With dedup=0 every live
    range pays its own binary search; with dedup=U identical (begin,
    end) ranges are sort+unique'd and only U distinct representatives
    are searched, the shared vmax gathered back to every duplicate
    (snapshots differ per duplicate, so the compare stays per-read).
    A batch with more than U distinct live ranges sets dedup_ok=False —
    the caller's latch, same discipline as short_span_limit.
    """
    if dedup == 0:
        vmax = H.query_reads_vmax(main, rb, re, main_tab)
        return (vmax > rsnap) & rvalid, jnp.asarray(True)

    nr, w = rb.shape

    def col(arr, i):
        # dead rows key to the sentinel so they sort to the tail; real
        # keys are detected by the LENGTH word (<= max_key_bytes + 1,
        # never near the sentinel)
        return jnp.where(rvalid, arr[:, i], K.SENTINEL_WORD)

    cols = [col(rb, i) for i in range(w)] + [col(re, i) for i in range(w)]
    iota = jnp.arange(nr, dtype=jnp.int32)
    s = jax.lax.sort(cols + [iota], num_keys=2 * w)
    new = jnp.zeros((nr,), bool)
    for c in s[: 2 * w]:
        new = new | (c != _shift_down(c, jnp.uint32(0xDEADBEEF)))
    new = new.at[0].set(True)
    sorted_live = s[w - 1] != K.SENTINEL_WORD  # rb length word
    uh = jnp.cumsum(new.astype(jnp.int32)) - 1  # unique rank, sorted order
    n_uniq = jnp.sum((new & sorted_live).astype(jnp.int32))
    ok = n_uniq <= dedup

    # compact the unique heads' (begin, end) rows into [U, W] buffers by
    # ONE sort (the platform cost model prefers sorts to scatters)
    ckey = jnp.where(new & sorted_live, uh, jnp.int32(nr))
    s2 = jax.lax.sort([ckey] + list(s[: 2 * w]), num_keys=1)
    urb = jnp.stack([c[:dedup] for c in s2[1 : w + 1]], axis=-1)
    ure = jnp.stack([c[:dedup] for c in s2[w + 1 :]], axis=-1)

    vmax_u = H.query_reads_vmax(main, urb, ure, main_tab)  # [U]

    # unique rank back to input order: invert the first sort's perm with
    # a second small sort (stable), then gather each duplicate's vmax
    _, uh_in = jax.lax.sort([s[2 * w], uh], num_keys=1)
    vmax = vmax_u[jnp.clip(uh_in, 0, dedup - 1)]
    return (vmax > rsnap) & rvalid, ok


def sweep_read_ranks(main_keys, rb, re, rvalid):
    """Sorted-endpoint sweep: main-tier il/ir ranks for a whole group's
    read ranges in ONE co-sort (no per-read binary search).

    main_keys: [M, W] sorted main boundaries (sentinel tail);
    rb, re: [R, W] read range begins/ends (R = all batches' reads,
    flattened); rvalid: [R] liveness. Returns (il, ir) int32 [R] with
    il = searchsorted_right(main, rb) - 1 and
    ir = searchsorted_left(main, re) - 1 — the exact positions
    ops/history.query_reads_vmax derives per batch, here read off a
    running main-row count over the sorted endpoint order. Dead rows
    carry garbage ranks; callers mask by rvalid (their range-max query
    over a garbage [lo, hi) is harmless — the stale compare is masked).

    Tie order at equal full keys is re(0) < main(1) < rb(2): an rb row
    then counts every main row <= rb before it (searchsorted-right) and
    an re row counts only main rows < re (searchsorted-left), so ONE
    inclusive cumsum serves both endpoint kinds.
    """
    m, w = main_keys.shape
    r = rb.shape[0]
    n = m + 2 * r
    max_len = 0xFFFFFFFF >> 2

    def pk_of(keys, tie, live):
        lenw = keys[:, w - 1]
        sent = (lenw > max_len) | ~live
        return jnp.where(
            sent, K.SENTINEL_WORD, (lenw << 2) | jnp.uint32(tie)
        )

    main_live = ~jnp.all(main_keys == K.SENTINEL_WORD, axis=-1)
    pks = jnp.concatenate([
        pk_of(main_keys, 1, main_live),
        pk_of(rb, 2, rvalid),
        pk_of(re, 0, rvalid),
    ])

    def col(i):
        c = jnp.concatenate([main_keys[:, i], rb[:, i], re[:, i]])
        return jnp.where(pks == K.SENTINEL_WORD, K.SENTINEL_WORD, c)

    iota = jnp.arange(n, dtype=jnp.int32)
    s = jax.lax.sort([col(i) for i in range(w - 1)] + [pks, iota],
                     num_keys=w)
    spk, siota = s[w - 1], s[w]
    is_main = ((spk & 3) == 1) & (spk != K.SENTINEL_WORD)
    rank = jnp.cumsum(is_main.astype(jnp.int32)) - 1  # searchsorted - 1
    # invert to input order: every query ordinal 0..2R-1 appears exactly
    # once (dead rows included — sentinel keys move them, not their
    # iota identity), so a stable sort keyed by ordinal is a perfect
    # inverse permutation (the group kernel's per-point routing trick)
    po_all = jnp.where(siota >= m, siota - m, 2 * r)
    sp = jax.lax.sort([po_all, rank], num_keys=1)
    ranks_q = sp[1][: 2 * r]
    return ranks_q[:r], ranks_q[r:]


def batch_body(main: H.VersionHistory, main_tab, carry, xs, b: int, *,
               short_span_limit: int = 0, fixpoint_unroll: int = 3,
               fixpoint_latch: bool = False, dedup_reads: int = 0,
               range_sweep: bool = False):
    """One batch of the tiered scan: probe the immutable main tier,
    resolve against (and merge committed writes into) the delta tier
    via the exact group kernel at G=1.

    carry = (delta, trip); xs = one batch's device_args leaves; b = the
    static txn capacity. Shared verbatim by the single-device scan
    (`resolve_group_tiered`) and the mesh-sharded kernel
    (parallel/sharding.py), which runs this same body per shard on the
    partition-clipped batch — the two paths cannot drift.

    With `range_sweep` the xs tree additionally carries this batch's
    precomputed main-tier ranks ("sweep_il"/"sweep_ir" — one co-sort
    per group OUTSIDE the scan, see sweep_read_ranks) and the probe is
    a single range-max table query; dedup_reads must be 0 (the sweep
    has no per-range searches to dedup, so there is no latch either).
    """
    delta, trip = carry
    xs = dict(xs)
    sweep_il = xs.pop("sweep_il", None)
    sweep_ir = xs.pop("sweep_ir", None)
    # per-read snapshots (padding rows carry read_txn == b)
    snap_pad = jnp.concatenate([
        xs["snapshot"].astype(jnp.int32),
        jnp.full((1,), VERSION_NEG, jnp.int32),
    ])
    rsnap = snap_pad[jnp.clip(xs["read_txn"], 0, b)]
    if range_sweep:
        vmax = rangemax.query(
            main_tab, jnp.maximum(sweep_il, 0), sweep_ir + 1, op="max"
        )
        stale_main = (vmax > rsnap) & xs["read_valid"]
        dedup_ok = jnp.asarray(True)
    else:
        stale_main, dedup_ok = _main_stale(
            main, main_tab, xs["read_begin"], xs["read_end"],
            rsnap, xs["read_valid"], dedup_reads,
        )
    g1 = jax.tree.map(lambda v: v[None], xs)
    delta2, out = G.resolve_group(
        delta, g1,
        short_span_limit=short_span_limit,
        fixpoint_unroll=fixpoint_unroll,
        fixpoint_latch=fixpoint_latch,
        extra_stale=stale_main[None],
    )
    trip2 = trip | out.unconverged[0] | ~dedup_ok
    return (delta2, trip2), jax.tree.map(lambda v: v[0], out)


def attach_sweep_ranks(main: H.VersionHistory, g: dict) -> dict:
    """Precompute the whole group's main-tier sweep ranks against an
    immutable main tier and attach them to the stacked tree
    ("sweep_il"/"sweep_ir", [G, NR]) for batch_body's range_sweep
    probe. ONE endpoint co-sort per group; shared by the single-device
    scan and the per-shard body (which calls it on the CLIPPED group
    against its shard-local main)."""
    gn, nr, w = g["read_begin"].shape
    il, ir = sweep_read_ranks(
        main.main_keys,
        g["read_begin"].reshape(gn * nr, w),
        g["read_end"].reshape(gn * nr, w),
        g["read_valid"].reshape(gn * nr),
    )
    out = dict(g)
    out["sweep_il"] = il.reshape(gn, nr)
    out["sweep_ir"] = ir.reshape(gn, nr)
    return out


def sweep_rows_per_group(m: int, gn: int, nr: int) -> int:
    """The sweep's structural cost accounting: rows co-sorted by the
    per-group endpoint sweep (main boundaries + 2 endpoints per read) —
    the perf ledger's range-path analog of the merge-row counts."""
    return m + 2 * gn * nr


def resolve_group_tiered(state: TieredState, g: dict, *,
                         short_span_limit: int = 0,
                         fixpoint_unroll: int = 3,
                         fixpoint_latch: bool = False,
                         dedup_reads: int = 0,
                         range_sweep: bool = False):
    """Resolve G stacked batches against the tiered history.

    Same contract as ops/group.resolve_group (g is a stacked device_args
    tree, versions strictly ascending; returns (state', GroupVerdict))
    with two differences:

    * every per-batch shape is independent of G — the body runs under
      one lax.scan, so compile cost does not grow with the group size;
    * GroupVerdict.unconverged also trips on the dedup latch
      (> dedup_reads distinct live read ranges in some batch). Either
      trip returns the UNCHANGED input state; the host re-dispatches on
      the exact kernel (fixpoint_latch=False, dedup_reads=0).
    """
    gn, b = g["txn_valid"].shape
    if gn > MAX_GROUP_TIERED:
        raise ValueError(f"group of {gn} > MAX_GROUP_TIERED {MAX_GROUP_TIERED}")

    # main is immutable for the whole group: ONE table build amortizes
    # across all G batches' probes
    main_tab = rangemax.build(state.main.main_ver, op="max")
    if range_sweep:
        if dedup_reads:
            raise ValueError("range_sweep and dedup_reads are exclusive")
        # the sorted-endpoint sweep runs OUTSIDE the scan (main is
        # immutable for the group): every batch's il/ir ranks ride the
        # scan's xs slices and the in-scan probe is one table query
        g = attach_sweep_ranks(state.main, g)

    def body(carry, xs):
        return batch_body(
            state.main, main_tab, carry, xs, b,
            short_span_limit=short_span_limit,
            fixpoint_unroll=fixpoint_unroll,
            fixpoint_latch=fixpoint_latch,
            dedup_reads=dedup_reads,
            range_sweep=range_sweep,
        )

    (delta_f, trip), outs = jax.lax.scan(
        body, (state.delta, jnp.asarray(False)), g
    )
    new_state = TieredState(main=state.main, delta=delta_f)
    if fixpoint_latch or dedup_reads:
        # a tripped latch must leave BOTH tiers untouched: the host
        # re-runs the whole group on the exact kernel against the same
        # input state (the group kernel's own latch discipline)
        new_state = jax.tree.map(
            lambda old, new: jnp.where(trip, old, new), state, new_state
        )
    return new_state, G.GroupVerdict(
        verdict=outs.verdict,
        hist_conflict_read=outs.hist_conflict_read,
        intra_first_range=outs.intra_first_range,
        committed_count=outs.committed_count,
        conflict_count=outs.conflict_count,
        too_old_count=outs.too_old_count,
        # per-batch delta latch (capacity/span) | the main tier's own
        overflow=outs.overflow | state.main.overflow,
        unconverged=jnp.broadcast_to(trip, (gn,)),
        fixpoint_iters=outs.fixpoint_iters,
    )


def compact(state: TieredState) -> TieredState:
    """Fold the delta tier into main: one device program.

    The combined map is pointwise max of the two piecewise-constant
    tiers (merges only ever RAISE a key's version, so max is exact).
    Implementation: co-sort main and delta boundary rows (main first at
    equal keys), run one last-value carry scan PER TIER, take the max at
    each block's last row, GC below the floor, drop redundant
    boundaries, and compact kept rows by sort — the group kernel's
    merge-phase discipline at M + D rows. Delta resets to empty; a
    latched delta overflow folds into main.overflow (never lost).
    """
    main, delta = state.main, state.delta
    m, w = main.main_keys.shape
    d = delta.main_keys.shape[0]
    n = m + d
    floor = jnp.maximum(main.oldest, delta.oldest)

    # pk packs (len << 1) | tier so equal full keys group into a block
    # of <= 2 rows with the main row FIRST; sentinel rows shift to
    # >= 0x7FFFFFFF after unpacking (no real length gets near it)
    pk = jnp.concatenate([
        (main.main_keys[:, w - 1] << 1) | jnp.uint32(0),
        (delta.main_keys[:, w - 1] << 1) | jnp.uint32(1),
    ])
    val = jnp.concatenate([main.main_ver, delta.main_ver])
    iota = jnp.arange(n, dtype=jnp.int32)  # sorted-row positions (ckey)
    ops = [
        jnp.concatenate([main.main_keys[:, i], delta.main_keys[:, i]])
        for i in range(w - 1)
    ] + [pk, val]
    s = jax.lax.sort(ops, num_keys=w)
    skw, spk, sval = s[: w - 1], s[w - 1], s[w]

    s_len = spk >> 1
    is_real = s_len < jnp.uint32(0x7FFFFFFF)
    is_m = ((spk & 1) == 0) & is_real
    is_d = ((spk & 1) == 1) & is_real

    def last_valid(a, bb):
        av, am = a
        bv, bm = bb
        return jnp.where(bm, bv, av), am | bm

    carry_m, _ = jax.lax.associative_scan(
        last_valid, (jnp.where(is_m, sval, VERSION_NEG), is_m)
    )
    carry_d, _ = jax.lax.associative_scan(
        last_valid, (jnp.where(is_d, sval, VERSION_NEG), is_d)
    )
    v = jnp.maximum(carry_m, carry_d)
    vf = jnp.where(v < floor, jnp.int32(VERSION_NEG), v)

    # block = run of rows with one full key; blocks have <= 2 rows (each
    # tier's boundaries are distinct), main-first by the pk tie-break
    same_prev = jnp.ones((n,), bool)
    for c in skw:
        same_prev &= c == _shift_down(c, jnp.uint32(0xDEADBEEF))
    same_prev &= s_len == _shift_down(s_len, jnp.uint32(0xDEADBEEF))
    key_new = (~same_prev).at[0].set(True)
    block_last = jnp.concatenate([key_new[1:], jnp.ones((1,), bool)])

    # value in force at this key = vf at the block's LAST row (both
    # carries complete there); the PREVIOUS block's value is one row
    # back for 1-row blocks, two rows back for 2-row blocks
    sh1 = _shift_down(vf, jnp.int32(VERSION_NEG))
    sh2 = _shift_down(sh1, jnp.int32(VERSION_NEG))
    pvf = jnp.where(key_new, sh1, sh2)

    keep = block_last & is_real & (vf != pvf)
    new_count = jnp.sum(keep.astype(jnp.int32))
    overflow = main.overflow | delta.overflow | (new_count > m)

    # compact kept rows by SORT, not scatter (platform cost model):
    # dropped rows to the back, kept rows in key order
    ckey = ((~keep).astype(jnp.uint32) << 31) | (
        iota.astype(jnp.uint32) & 0x7FFFFFFF
    )
    len_word = jnp.where(is_real, s_len.astype(jnp.uint32), K.SENTINEL_WORD)
    s2 = jax.lax.sort([ckey] + list(skw) + [len_word, vf], num_keys=1)
    live = jnp.arange(m, dtype=jnp.int32) < new_count
    new_keys = jnp.stack(
        [
            jnp.where(live, c[:m], K.SENTINEL_WORD)
            for c in list(s2[1:w]) + [s2[w]]
        ],
        axis=-1,
    )
    new_ver = jnp.where(live, s2[w + 1][:m], jnp.int32(VERSION_NEG))

    new_main = H.VersionHistory(
        main_keys=new_keys,
        main_ver=new_ver,
        oldest=floor,
        overflow=overflow,
    )
    new_delta = H.VersionHistory(
        main_keys=K.sentinel_like(d, w),
        main_ver=jnp.full((d,), VERSION_NEG, jnp.int32),
        oldest=floor,
        overflow=jnp.asarray(False),
    )
    return TieredState(main=new_main, delta=new_delta)


def boundary_counts(state: TieredState):
    """(main, delta) live-boundary counts — the bench ledger's
    merge-row accounting."""
    return H.boundary_count(state.main), H.boundary_count(state.delta)


def boundary_counts_per_shard(state: TieredState):
    """([S] main, [S] delta) live-boundary counts of a SHARD-STACKED
    tiered state (leading shard axis on every leaf) — the fdbtop kernel
    panel's worst-shard tier-occupancy input. vmap of the single-tier
    counter so the liveness rule has one source of truth."""
    per_shard = jax.vmap(H.boundary_count)
    return per_shard(state.main), per_shard(state.delta)

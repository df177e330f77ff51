"""Multi-resolver sharding: the keyspace-partition axis on a device mesh.

The reference scales conflict detection by partitioning the keyspace
across resolver processes: commit proxies split each transaction's
conflict ranges by the `keyResolvers` map and send each resolver only the
pieces inside its partition (ResolutionRequestBuilder,
fdbserver/CommitProxyServer.actor.cpp:105-261), then combine the per-
resolver verdicts with `min()` (determineCommittedTransactions,
:1551-1567). Crucially each resolver is *independent*: a transaction that
passes locally has its writes merged into that resolver's history even if
another resolver aborts it globally — there is no cross-resolver
consensus inside a batch.

That independence is exactly what makes the TPU mapping clean: resolver
shards become a `Mesh` axis. Each device holds one shard's history
tiers, the packed batch is replicated, every device clips the
batch's ranges to its own key partition (the device-side equivalent of
ResolutionRequestBuilder's splitting), runs the identical conflict
kernel, and the per-shard verdicts merge with one `lax.pmin` over the ICI
ring — the reference's min() combine as a collective. One jitted
`shard_map` call per group; no host round-trip between shards.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.ops import history as H
from foundationdb_tpu.ops import keys as K
from foundationdb_tpu.ops.rangemax import INT32_POS
from foundationdb_tpu.parallel.mesh import AXIS
from foundationdb_tpu.utils import packing


def _shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking OFF: the group
    kernel's residual while_loop has no replication rule, and every
    output's cross-shard agreement is established explicitly by the
    pmin/psum combines."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def lex_max(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Rowwise max of packed keys ([..., W] uint32)."""
    return jnp.where(K.lex_less(a, b)[..., None], b, a)


def lex_min(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(K.lex_less(a, b)[..., None], a, b)


def clip_batch(batch: dict, lo: jnp.ndarray, hi: jnp.ndarray) -> dict:
    """Clip every conflict range to the shard partition [lo, hi).

    Device-side ResolutionRequestBuilder: ranges outside the partition
    drop out (valid=False); ranges straddling a boundary shrink to the
    overlap. `has_reads` is recomputed from the surviving read rows — a
    txn whose reads all live on other shards is a blind write here and
    must not classify tooOld on this shard (the reference never sends
    those reads to this resolver at all).
    """
    out = dict(batch)
    rb = lex_max(batch["read_begin"], lo)
    re = lex_min(batch["read_end"], hi)
    rv = batch["read_valid"] & K.lex_less(rb, re)
    wb = lex_max(batch["write_begin"], lo)
    we = lex_min(batch["write_end"], hi)
    wv = batch["write_valid"] & K.lex_less(wb, we)

    b = batch["txn_valid"].shape[0]
    trash = b
    has_reads = (
        jnp.zeros((b + 1,), jnp.int32)
        .at[jnp.where(rv, batch["read_txn"], trash)]
        .max(rv.astype(jnp.int32))[:b]
    ) > 0
    out.update(
        read_begin=rb, read_end=re, read_valid=rv,
        write_begin=wb, write_end=we, write_valid=wv,
        has_reads=has_reads,
    )
    return out


def make_partition(
    boundaries: Sequence[bytes], config: KernelConfig
) -> tuple[np.ndarray, np.ndarray]:
    """Internal partition boundaries -> per-shard (lo, hi) packed keys.

    `boundaries` are the n_shards-1 interior split keys (ascending); shard
    0 starts at b"" and the last shard is capped by the +inf sentinel, so
    the shards tile the whole keyspace — the keyResolvers map's contract.
    """
    n_shards = len(boundaries) + 1
    w = config.key_words
    lo = np.zeros((n_shards, w), np.uint32)
    hi = np.zeros((n_shards, w), np.uint32)
    packed = [packing.pack_key(b, config.max_key_bytes) for b in boundaries]
    sentinel = np.full((w,), 0xFFFFFFFF, np.uint32)
    for s in range(n_shards):
        lo[s] = packed[s - 1] if s > 0 else packing.pack_key(b"", config.max_key_bytes)
        hi[s] = packed[s] if s < n_shards - 1 else sentinel
    return lo, hi


def _keys_below(bound: np.ndarray, keys: np.ndarray, first: np.ndarray,
                or_equal: bool) -> int:
    """How many packed `keys` lie below the packed `bound` (or at it,
    with `or_equal`). `first` is their first words, which decide almost
    every key; keys whose first word ties the bound's are compared
    whole."""
    n = int(np.count_nonzero(first < bound[0]))
    tied = np.flatnonzero(first == bound[0])
    if len(tied):
        b = tuple(bound)
        n += sum(1 for k in map(tuple, keys[tied])
                 if k < b or (or_equal and k == b))
    return n


def partition_loads(batch: packing.PackedBatch,
                    bounds: np.ndarray) -> np.ndarray:
    """Read and write conflict ranges of a packed batch that land in each
    partition of the interior split keys `bounds` ([n_shards - 1, W]
    packed, ascending): ResolutionRequestBuilder's split, so a range
    that straddles a boundary counts in every partition it touches.
    Host numpy on the batch's live rows; [n_shards] int64."""
    loads = np.zeros(len(bounds) + 1, np.int64)
    for begin, end, valid, count in (
        (batch.read_begin, batch.read_end, batch.read_valid, batch.n_reads),
        (batch.write_begin, batch.write_end, batch.write_valid,
         batch.n_writes),
    ):
        live = valid[:count]
        rows = slice(0, count) if live.all() else np.flatnonzero(live)
        begin, end = begin[rows], end[rows]
        b0 = np.ascontiguousarray(begin[:, 0])
        e0 = np.ascontiguousarray(end[:, 0])
        # [begin, end) touches partition s iff begin < its upper bound
        # and end > its lower bound; as begin < end, every range with
        # end <= the lower bound also has begin below the upper one
        below = [_keys_below(b, begin, b0, False) for b in bounds]
        ended = [_keys_below(b, end, e0, True) for b in bounds]
        loads += np.array(below + [len(b0)]) - np.array([0] + ended)
    return loads


# ---------------------------------------------------------------------------
# The MESH-SHARDED DELTA-TIERED kernel (ISSUE 11): the production tiered
# path (ops/delta.py — the kernel TpuConflictSet._dispatch_tiered runs)
# made mesh-native. Conflict history is partitioned by key range across
# the `resolver` mesh axis: each device holds one shard's MAIN range-max
# tier + DELTA tier, clips the replicated packed group to its partition
# (the device-side ResolutionRequestBuilder split), probes its own main
# tier and resolves/merges against its own delta tier locally via the
# shared per-batch body (ops/delta.batch_body — the single-device scan
# runs the IDENTICAL code), and the per-shard verdict / conflict-read /
# overflow bitmasks combine with `pmin`/`psum`/`pmax` collectives inside
# the SAME compiled shard_map program. One dispatch per group; no host
# round-trip between shards.
#
# Semantics are the reference's multi-resolver deployment: each shard
# merges its LOCALLY committed
# writes into its delta tier (phantom commits included), verdicts
# min-combine (determineCommittedTransactions). Decisions are therefore
# bit-identical to N independent tiered resolvers over the same
# partition AND to the multi-resolver CPU oracle; a 1-shard mesh
# degenerates to the single-device tiered kernel bit-for-bit.


def default_boundaries(n_shards: int) -> list[bytes]:
    """Even byte-prefix partition of the keyspace: the n_shards-1
    interior split keys. Balance is workload-dependent (callers with a
    key-sample pass explicit boundaries — the ResolutionBalancer's
    job); correctness never depends on it."""
    if not 1 <= n_shards <= 256:
        raise ValueError(f"n_shards must be in [1, 256], got {n_shards}")
    return [bytes([(256 * (i + 1)) // n_shards]) for i in range(n_shards - 1)]


def _tiered_spec_state(axis: str = AXIS):
    from foundationdb_tpu.ops import delta as D

    hist = H.VersionHistory(
        main_keys=P(axis), main_ver=P(axis), oldest=P(axis),
        overflow=P(axis),
    )
    return D.TieredState(main=hist, delta=hist)


def init_sharded_tiered(config: KernelConfig, mesh: Mesh,
                        boundaries: Sequence[bytes]):
    """(stacked sharded TieredState, part_lo, part_hi) for a mesh.

    Every leaf carries a leading shard axis laid out with
    NamedSharding(mesh, P(AXIS)) — device i holds shard i's tiers and
    partition bounds; nothing is replicated but the batch."""
    from foundationdb_tpu.ops import delta as D

    axis = config.shard_axis
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh must have a {axis!r} axis")
    n_shards = mesh.shape[axis]
    if len(boundaries) != n_shards - 1:
        raise ValueError(
            f"{n_shards} shards need {n_shards - 1} interior boundaries, "
            f"got {len(boundaries)}"
        )
    if list(boundaries) != sorted(set(boundaries)):
        raise ValueError("shard boundaries must be strictly ascending")
    lo, hi = make_partition(boundaries, config)
    shard = NamedSharding(mesh, P(axis))
    part_lo = jax.device_put(lo, shard)
    part_hi = jax.device_put(hi, shard)
    single = D.init(config)
    stacked = jax.tree.map(
        lambda x: np.broadcast_to(
            np.asarray(x), (n_shards,) + np.asarray(x).shape
        ).copy(),
        single,
    )
    state = jax.tree.map(lambda x: jax.device_put(x, shard), stacked)
    return state, part_lo, part_hi


def _shard_resolve_group_tiered(state, g: dict, lo, hi, *,
                                short_span_limit: int,
                                fixpoint_unroll: int,
                                fixpoint_latch: bool,
                                dedup_reads: int,
                                range_sweep: bool = False,
                                axis: str = AXIS):
    """Per-device body: the tiered group scan on the clipped batch plus
    the cross-shard combine. Leading shard axis squeezed on entry."""
    from foundationdb_tpu.ops import delta as D
    from foundationdb_tpu.ops import group as G

    state = jax.tree.map(lambda x: x[0], state)
    lo = lo[0]
    hi = hi[0]
    gn, b = g["txn_valid"].shape

    # device-side ResolutionRequestBuilder: every batch in the stack
    # clipped to this shard's [lo, hi) partition
    local = jax.vmap(lambda bt: clip_batch(bt, lo, hi))(g)
    # main is immutable for the whole group: one table build per shard
    from foundationdb_tpu.ops import rangemax as _rm

    main_tab = _rm.build(state.main.main_ver, op="max")
    if range_sweep:
        # ISSUE 14: the per-group sorted-endpoint sweep runs PER SHARD
        # against the shard-local main tier, on the CLIPPED ranges —
        # same ops/delta machinery as the single-device scan, inside
        # the same shard_map program (no extra collective: ranks are
        # shard-local inputs to the shard-local probe)
        local = D.attach_sweep_ranks(state.main, local)

    def body(carry, xs):
        return D.batch_body(
            state.main, main_tab, carry, xs, b,
            short_span_limit=short_span_limit,
            fixpoint_unroll=fixpoint_unroll,
            fixpoint_latch=fixpoint_latch,
            dedup_reads=dedup_reads,
            range_sweep=range_sweep,
        )

    (delta_f, trip), outs = jax.lax.scan(
        body, (state.delta, jnp.asarray(False)), local
    )

    # ---- cross-shard combine: ONE collective round per group ----------
    # min() verdict combine (determineCommittedTransactions) on ICI.
    verdict = jax.lax.pmin(outs.verdict, axis)  # [G, B]
    # conflict-read bitmask: OR across shards as a psum of hits (the
    # design brief's cross-resolver psum merge)
    hist_read = (
        jax.lax.psum(outs.hist_conflict_read.astype(jnp.int32), axis) > 0
    )
    first = jnp.where(
        outs.intra_first_range < 0, INT32_POS, outs.intra_first_range
    )
    first = jax.lax.pmin(first, axis)
    first = jnp.where(first == INT32_POS, -1, first)
    # overflow accounting: any-shard reduction of (per-batch delta latch
    # | this shard's main tier latch)
    overflow = (
        jax.lax.pmax(
            (outs.overflow | state.main.overflow).astype(jnp.int32), axis
        ) > 0
    )
    # dedup/fixpoint latch: ANY shard tripping refuses the whole group
    trip_any = jax.lax.pmax(trip.astype(jnp.int32), axis) > 0
    # the deepest shard's fixpoint is the batch's
    fixpoint_iters = jax.lax.pmax(outs.fixpoint_iters, axis)

    # decision counts from the COMBINED verdict (a local count would
    # count phantom commits): TransactionResult CONFLICT=0 / TOO_OLD=1 /
    # COMMITTED=3, padding masked by txn_valid
    valid = g["txn_valid"]
    committed = jnp.sum(
        ((verdict == 3) & valid).astype(jnp.int32), axis=1
    )
    conflicted = jnp.sum(
        ((verdict == 0) & valid).astype(jnp.int32), axis=1
    )
    too_old = jnp.sum(
        ((verdict == 1) & valid).astype(jnp.int32), axis=1
    )

    new_state = D.TieredState(main=state.main, delta=delta_f)
    if fixpoint_latch or dedup_reads:
        # a tripped latch must leave every shard's tiers untouched: the
        # host re-runs the whole group on the exact kernel against the
        # same input state (the tiered kernel's latch discipline, with
        # the trip reduced across shards so all devices agree)
        new_state = jax.tree.map(
            lambda old, new: jnp.where(trip_any, old, new),
            D.TieredState(main=state.main, delta=state.delta), new_state,
        )
    new_state = jax.tree.map(lambda x: x[None], new_state)
    return new_state, G.GroupVerdict(
        verdict=verdict,
        hist_conflict_read=hist_read,
        intra_first_range=first,
        committed_count=committed,
        conflict_count=conflicted,
        too_old_count=too_old,
        overflow=overflow,
        unconverged=jnp.broadcast_to(trip_any, (gn,)),
        fixpoint_iters=fixpoint_iters,
    )


def _shard_compact(state):
    """Per-device compaction: fold this shard's delta into its main
    (ops/delta.compact verbatim — no cross-shard dependency)."""
    from foundationdb_tpu.ops import delta as D

    single = jax.tree.map(lambda x: x[0], state)
    return jax.tree.map(lambda x: x[None], D.compact(single))


# One compiled program per (mesh, static-switch tuple): shared across
# TpuConflictSet instances like the module-level single-device jits.
_TIERED_SHARD_JITS: dict = {}
_COMPACT_SHARD_JITS: dict = {}
_COLLECTIVE_PROBE_JITS: dict = {}


def tiered_sharded_jit(mesh: Mesh, short_span_limit: int,
                       fixpoint_unroll: int, fixpoint_latch: bool,
                       dedup_reads: int, range_sweep: bool = False,
                       axis: str = AXIS):
    """The compiled mesh-sharded tiered group kernel: ONE shard_map
    program per dispatch (clip + scan + pmin/psum combine), compiled
    once per (mesh, static switches) — the scan body is G-independent
    exactly like the single-device tiered kernel."""
    key = (mesh, short_span_limit, fixpoint_unroll, fixpoint_latch,
           dedup_reads, range_sweep, axis)
    fn = _TIERED_SHARD_JITS.get(key)
    if fn is None:
        spec_state = _tiered_spec_state(axis)
        body = partial(
            _shard_resolve_group_tiered,
            short_span_limit=short_span_limit,
            fixpoint_unroll=fixpoint_unroll,
            fixpoint_latch=fixpoint_latch,
            dedup_reads=dedup_reads,
            range_sweep=range_sweep,
            axis=axis,
        )
        # no donation: the latch fallback re-dispatches the same input
        # state on the exact program (the single-device tiered jits
        # share this contract)
        fn = jax.jit(
            _shard_map(
                body, mesh=mesh,
                in_specs=(spec_state, P(), P(axis), P(axis)),
                out_specs=(spec_state, P()),
            )
        )
        _TIERED_SHARD_JITS[key] = fn
    return fn


def compact_sharded_jit(mesh: Mesh, axis: str = AXIS):
    key = (mesh, axis)
    fn = _COMPACT_SHARD_JITS.get(key)
    if fn is None:
        spec_state = _tiered_spec_state(axis)
        fn = jax.jit(
            _shard_map(
                _shard_compact, mesh=mesh,
                in_specs=(spec_state,), out_specs=spec_state,
            )
        )
        _COMPACT_SHARD_JITS[key] = fn
    return fn


def collective_probe_jit(mesh: Mesh, n: int, axis: str = AXIS):
    """A combine-only program (the pmin + psum + pmax round the sharded
    kernel runs per group, on verdict-shaped arrays): its fenced wall
    time is the measured per-group collective cost, sampled by
    TpuConflictSet on the overflow-check syncs so the fdbtop kernel
    panel can report the collective share of resolve time."""
    key = (mesh, n, axis)
    fn = _COLLECTIVE_PROBE_JITS.get(key)
    if fn is None:

        def probe(v, r):
            a = jax.lax.pmin(v, axis)
            s = jax.lax.psum(r, axis)
            m = jax.lax.pmax(v, axis)
            return jnp.sum(a) + jnp.sum(s) + jnp.sum(m)

        fn = jax.jit(
            _shard_map(
                probe, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            )
        )
        _COLLECTIVE_PROBE_JITS[key] = fn
    return fn

"""TpuConflictSet: the host-facing conflict-detection object.

Plays the role of the reference's ConflictSet + ConflictBatch pair
(fdbserver/include/fdbserver/ConflictSet.h:30-75): persistent MVCC write
history plus a batch-at-a-time detect API. Differences are all
TPU-motivated:

* State lives on device as `ops.delta.TieredState`: an immutable main
  tier plus a small delta tier. Each batch is one jitted call of the
  tiered kernel (`ops.delta.resolve_group_tiered` at G=1), which merges
  the batch's committed writes into delta; the host folds delta into
  main every `compact_interval` batches (`compact_history`).
* Versions are rebased to int32 offsets of `base_version`; the rebase
  shifts every stored offset on device when the window drifts too far.
* Capacity overflow is latched on device and surfaced in every
  BatchVerdict; `complete()` (the second half of `resolve()`, after
  `issue()`) checks it on the same sync that reads the verdicts, so no
  decision computed against a truncated history is ever externalized.
  The async `resolve_packed` path (bench) checks every
  OVERFLOW_CHECK_INTERVAL batches to preserve pipelining.

The conflicting-key report follows the reference's recording order:
history-phase hits record every conflicting read-range index in
begin-key order (ranges are scanned sorted — SkipList.cpp:83,942), while
the intra-batch phase records only the first hit in range order and only
for txns the history phase didn't already condemn (:880-899).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_base import (
    STAGE_NAMES,
    BatchResult,
    HistoryOverflowError,
    KernelStageMetrics,
)
from foundationdb_tpu.models.types import CommitTransaction, TransactionResult
from foundationdb_tpu.ops import delta as _D
from foundationdb_tpu.ops import history as H
from foundationdb_tpu.utils import packing
from foundationdb_tpu.utils.metrics import stage
from foundationdb_tpu.utils.probes import code_probe, declare

# ISSUE 14 rare-path coverage: the range-scan sweep probe actually
# dispatching (vs silently falling back to the probe path) and the
# pressure-driven spill fold actually replacing a latch+raise — both
# expected by the range_heavy soak spec.
declare("resolver.range_sweep", "resolver.delta_spill")

# Rebase when offsets pass 2**30 (window is ~5e6; huge safety margin).
REBASE_THRESHOLD = 1 << 30


def _rebase(state: H.VersionHistory, delta):
    """Shift every stored version offset down by delta (device-side)."""
    d = jnp.int32(delta)

    def shift(v):
        return jnp.where(v == H.VERSION_NEG, v, jnp.maximum(v - d, H.VERSION_NEG + 1))

    return state._replace(
        main_ver=shift(state.main_ver),
        oldest=shift(state.oldest),
    )


# Module-level jitted kernels: shared across all TpuConflictSet instances
# so N resolvers with the same KernelConfig compile once, not N times.
# State is deliberately NOT donated to the resolve kernel: the mega-sort
# gathers against the history buffers, and gathers from donated/carried
# buffers measure ~2x slower than from plain arguments on v5e
# (scripts/price_primitives.py).


def _rebase_tiered(state: _D.TieredState, delta):
    """Shift both tiers' version offsets down by delta (device-side)."""
    return _D.TieredState(
        main=_rebase(state.main, delta), delta=_rebase(state.delta, delta)
    )


_REBASE_TIERED = jax.jit(_rebase_tiered, donate_argnums=0)
# Compaction runs once per compact_interval BATCHES, off the per-batch
# path; like the group kernel it does NOT donate (its gathers read the
# carried buffers — the price_primitives donated-gather penalty).
_COMPACT = jax.jit(_D.compact)

_TIERED_JITS: dict = {}


def _named_partial(fn, **static):
    """functools.partial(fn, **static) under fn's own name: jax.jit
    names a program after its function's __name__, which a bare
    partial lacks (its program would be `jit__unknown`), and a profiler
    trace finds a program by that name."""
    import functools

    bound = functools.partial(fn, **static)
    bound.__name__ = fn.__name__
    return bound


def _resolve_tiered_jit(short_span_limit: int, fixpoint_unroll: int = 3,
                        fixpoint_latch: bool = False, dedup_reads: int = 0,
                        range_sweep: bool = False):
    """One compiled TIERED group kernel per static-switch tuple
    (ops/delta.resolve_group_tiered). The scan body inside is
    G-independent, so the same tuple serves every group size with one
    body compile. `range_sweep` swaps the main-tier probe for the
    per-group sorted-endpoint sweep (no per-read binary search, no
    dedup latch)."""
    key = (short_span_limit, fixpoint_unroll, fixpoint_latch, dedup_reads,
           range_sweep)
    fn = _TIERED_JITS.get(key)
    if fn is None:
        fn = jax.jit(_named_partial(
            _D.resolve_group_tiered, short_span_limit=short_span_limit,
            fixpoint_unroll=fixpoint_unroll,
            fixpoint_latch=fixpoint_latch,
            dedup_reads=dedup_reads,
            range_sweep=range_sweep,
        ))
        _TIERED_JITS[key] = fn
    return fn

#: each verdict code's TransactionResult, for the reply's verdict list
_RESULTS = {int(r): r for r in TransactionResult}

#: Overflow is checked host-side every this many batches (each check
#: forces a device sync; the merge itself is async).
OVERFLOW_CHECK_INTERVAL = 32


def _stack_one(args: dict) -> dict:
    """One batch's device_args -> a G=1 stacked tree (leading [1] axis)."""
    out = {}
    for k, v in args.items():
        if isinstance(v, (int, float, np.generic)):
            v = np.asarray(v)
        out[k] = v[None]
    return out


def _result_columns(out: _D.BatchVerdict) -> tuple:
    """The result columns a reply is assembled from, and the batch's
    fixpoint applications."""
    return (out.verdict, out.overflow, out.hist_conflict_read,
            out.intra_first_range, out.fixpoint_iters)


@dataclasses.dataclass
class PendingBatch:
    """One issued batch: the packed host batch, its BatchVerdict still
    on the device, and what assembly needs; `host` holds the read-back
    result columns once `TpuConflictSet.read_back` has waited for them."""

    batch: packing.PackedBatch
    out: _D.BatchVerdict
    #: per txn, whether it asked for the conflicting-key report
    report: np.ndarray | list
    begin_key_of_row: Callable[[int], bytes]
    #: when the issue ended (perf_counter): the readback runs from here
    issued_at: float
    host: tuple | None = None


class TpuConflictSet:
    """Batch MVCC conflict detection with device-resident history.

    State is a TieredState (main + delta tier, ops/delta.py), every
    resolve dispatches the G-independent tiered kernel, and the host
    folds delta into main every `config.compact_interval` batches (a
    fused group counts its G).

    With `config.n_shards > 1` the tiered path runs MESH-SHARDED
    (parallel/sharding.py, ISSUE 11): both tiers are partitioned by key
    range across an n_shards-device mesh axis via NamedSharding, every
    dispatch is ONE compiled shard_map program (per-device clip + local
    tiered scan + pmin/psum verdict combine), and compaction / rebase /
    the dedup latch / overflow accounting are per-shard state with
    any-shard collective reductions. Pass `mesh=` to pin the device
    mesh (tests use the virtual CPU mesh); by default one is built from
    the default backend's devices. `shard_boundaries` are the
    n_shards-1 interior split keys (default: even byte-prefix split).
    Decisions match the reference's multi-resolver deployment exactly
    (per-shard local merges, min() combine — see parallel/sharding.py).
    """

    def __init__(self, config: KernelConfig, base_version: int = 0, *,
                 mesh=None, shard_boundaries=None):
        self.config = config
        self.base_version = base_version
        # Guard the production path against the known large-m flattened
        # gather miscompile class before the first decision is served
        # (ADVICE r3). Once per (platform, m) per process; XLA:CPU never
        # exhibited the bug and the sim/test lanes run there, so the
        # check is accelerator-only.
        from foundationdb_tpu.ops import rangemax as _rm

        if jax.default_backend() != "cpu":
            _rm.flat_gather_selftest(config.history_capacity)
        self.sharded = getattr(config, "n_shards", 0) > 1
        #: set on sharded instances (the staging thread replicates
        #: against it; None = plain single-device device_put)
        self._batch_sharding = None
        self._mesh = None
        #: always-on stage telemetry (see KernelStageMetrics)
        self.metrics = KernelStageMetrics()
        if self.sharded:
            from jax.sharding import NamedSharding, PartitionSpec as _P

            from foundationdb_tpu.parallel import mesh as _mesh_mod
            from foundationdb_tpu.parallel import sharding as _sh

            axis = getattr(config, "shard_axis", _mesh_mod.AXIS)
            self._mesh = mesh if mesh is not None else _mesh_mod.resolver_mesh(
                config.n_shards, axis=axis
            )
            if self._mesh.shape.get(axis) != config.n_shards:
                raise ValueError(
                    f"mesh axis {axis!r} has {self._mesh.shape.get(axis)} "
                    f"device(s); config.n_shards is {config.n_shards}"
                )
            boundaries = (
                list(shard_boundaries) if shard_boundaries is not None
                else _sh.default_boundaries(config.n_shards)
            )
            self.shard_boundaries = boundaries
            #: the packed interior split keys, for the host's count of
            #: each partition's ranges (_sample_shard_skew)
            self._split_keys = _sh.make_partition(boundaries, config)[0][1:]
            self.state, self._part_lo, self._part_hi = (
                _sh.init_sharded_tiered(config, self._mesh, boundaries)
            )
            self._batch_sharding = NamedSharding(self._mesh, _P())
            self.metrics.shard_count = config.n_shards
            self._collective_probe_warm = False
        else:
            self.state = _D.init(config)
        #: the compiled row capacities a served batch is packed at
        #: (packing.row_rungs): each batch dispatches at the smallest
        #: that holds it, one compiled program per rung
        self.rungs = packing.row_rungs(config)
        self._batches_since_check = 0
        self._batches_since_compact = 0
        #: conservative live-boundary bound of the delta tier since the
        #: last compaction (2 x write rows per dispatched batch): the
        #: delta_spill pressure signal — host arithmetic only, so spill
        #: decisions never cost a device sync (and are therefore
        #: invariant across pipelined/sharded/compact_interval paths)
        self._spill_bound_rows = 0
        self._prewarmed_exact: set = set()

    # -- ConflictBatch-equivalent API -----------------------------------

    def resolve(
        self, transactions: list[CommitTransaction], version: int
    ) -> BatchResult:
        """Detect conflicts for one batch committing at `version`.

        Equivalent to addTransaction xN + detectConflicts
        (fdbserver/Resolver.actor.cpp:330-345): returns per-txn verdicts
        and the conflicting-key-range report, and merges committed writes
        into history at `version`.
        """
        return self.complete(self.issue(transactions, version))

    def issue(
        self, transactions: list[CommitTransaction], version: int
    ) -> PendingBatch:
        """The first half of resolve(): rebase, pack, place and dispatch
        the batch (with any compaction or overflow check it falls due
        for), and return without waiting for the device. Batches issue
        in version order; `complete` gives each its BatchResult."""
        self._maybe_rebase(version)
        with stage(self.metrics.pack, STAGE_NAMES["pack"]):
            batch = packing.pack_batch(
                transactions, version, self.base_version, self.config,
                self.rungs,
            )
            self._sample_shard_skew(batch)
        return self._issue(
            batch,
            report=[t.report_conflicting_keys for t in transactions],
            begin_key_of_row=lambda r: transactions[
                int(batch.read_txn[r])
            ].read_conflict_ranges[int(batch.read_index[r])][0],
        )

    # -- columnar path (r12: the wire-to-kernel resolve hop) -------------

    def pack_columnar_batch(
        self, cols: packing.ColumnarBatch, version: int
    ) -> packing.PackedBatch:
        """Rebase + decode a columnar wire batch straight into kernel
        tensors (packing.pack_batch_columnar — byte-identical to
        pack_batch on the equivalent transaction list, so decisions are
        identical by construction), at the smallest rung that holds
        them. No per-txn Python objects. Split from resolve_columnar so
        the wire ResolverRole can bracket exactly this stage with its
        ColumnarDecode trace event."""
        self._maybe_rebase(version)
        with stage(self.metrics.pack, STAGE_NAMES["pack"]):
            batch = packing.pack_batch_columnar(
                cols, version, self.base_version, self.config, self.rungs
            )
            self._sample_shard_skew(batch)
        self.metrics.counters.add("columnarBatches")
        return batch

    def resolve_columnar_packed(
        self, cols: packing.ColumnarBatch, batch: packing.PackedBatch
    ) -> BatchResult:
        """Dispatch + reply assembly for a pack_columnar_batch result."""
        return self.complete(self.issue_columnar_packed(cols, batch))

    def issue_columnar_packed(
        self, cols: packing.ColumnarBatch, batch: packing.PackedBatch
    ) -> PendingBatch:
        """The dispatch half of resolve_columnar_packed. The
        conflicting-key report's begin keys slice out of the blob
        lazily: only the history-hit rows of the txns that asked for
        the report are touched."""
        return self._issue(
            batch,
            report=(np.asarray(cols.flags) & packing.COLUMNAR_FLAG_REPORT)
            != 0,
            begin_key_of_row=lambda r: packing.columnar_key(cols, r),
        )

    def resolve_columnar(
        self, cols: packing.ColumnarBatch, version: int
    ) -> BatchResult:
        """Columnar twin of resolve(): flat wire columns in, BatchResult
        out, never materializing per-transaction objects."""
        batch = self.pack_columnar_batch(cols, version)
        return self.resolve_columnar_packed(cols, batch)

    def _sample_shard_skew(self, batch: packing.PackedBatch) -> None:
        """On a sharded instance, the packed batch's balance over the
        partitions: the most conflict ranges one partition got, over the
        mean, in % (100 is even; n_shards x 100 is all in one). The load
        FoundationDB's ResolutionBalancer watches across resolvers."""
        if not self.sharded:
            return
        from foundationdb_tpu.parallel import sharding as _sh

        loads = _sh.partition_loads(batch, self._split_keys)
        if loads.any():
            self.metrics.shard_skew.sample(loads.max() / loads.mean() * 100)

    def _maybe_rebase(self, version: int) -> None:
        if version - self.base_version > REBASE_THRESHOLD:
            delta = version - self.base_version - (1 << 20)
            self.state = _REBASE_TIERED(self.state, np.int32(delta))
            self.base_version += delta
            self.metrics.counters.add("rebases")

    def _issue(
        self, batch: packing.PackedBatch, report, begin_key_of_row
    ) -> PendingBatch:
        """The shared dispatch of the object and columnar paths: place
        the packed batch on the device, dispatch it and start the copy
        of its result columns to the host, which then runs as soon as
        the program ends."""
        self.metrics.counters.add("resolveBatches")
        out = self.resolve_args(batch.device_args())
        for col in _result_columns(out):
            col.copy_to_host_async()
        return PendingBatch(batch, out, report, begin_key_of_row,
                            time.perf_counter())

    def read_back(self, pending: PendingBatch) -> None:
        """Wait for an issued batch's result columns and hold them on
        the host. It touches nothing but `pending`, so the wait may run
        on another thread than the one that issues and completes; the
        span marks it there, and `complete` samples the readback."""
        with stage(None, STAGE_NAMES["readback"]):
            pending.host = tuple(
                np.asarray(c) for c in _result_columns(pending.out)
            )

    def complete(self, pending: PendingBatch) -> BatchResult:
        """The second half of a resolve: the readback (unless read_back
        already waited), the overflow refusal and the reply's assembly.
        The readback is sampled from the issue's end to here: the wait
        for the device and the copy, and where another thread waited,
        the hand-back. Refuses to externalize decisions computed against
        a truncated history (ADVICE r1 — the interval-based check is
        only for the async packed path)."""
        if pending.host is None:
            self.read_back(pending)
        t0 = time.perf_counter()
        readback_s = t0 - pending.issued_at
        self.metrics.readback.sample(readback_s)
        verdict, overflow, hist_read, intra_first, iters = pending.host
        if bool(overflow):
            self._raise_overflow()
        self.metrics.fixpoint_iters.sample(int(iters))
        n = pending.batch.n_txns
        with stage(self.metrics.assemble, STAGE_NAMES["assemble"]):
            result = self._assemble(
                pending.batch, verdict[:n], hist_read, intra_first[:n],
                pending.report, pending.begin_key_of_row,
            )
        self.metrics.fence.sample(readback_s + time.perf_counter() - t0)
        return result

    def _transfer(self, args: dict) -> dict:
        """Place one dispatch's kernel arguments on the device as the
        jitted call would place them itself (uncommitted on the default
        device, or replicated over the mesh on a sharded instance). The
        transfer stage times the copy's issue; the copy completes on a
        runtime thread, and the device's wait for it lands in the
        readback. Samples the host bytes handed over, the bytes they
        take on the device, and the read + write rows of the rung
        dispatched."""
        m = self.metrics
        m.arg_bytes.sample(sum(v.nbytes for v in args.values()))
        m.dispatch_rows.sample(
            args["read_valid"].size + args["write_valid"].size
        )
        with stage(m.transfer, STAGE_NAMES["transfer"]):
            placed = jax.device_put(args, self._batch_sharding)
        m.device_arg_bytes.sample(
            sum(v.on_device_size_in_bytes() for v in placed.values())
        )
        return placed

    def _raise_overflow(self) -> None:
        self._batches_since_check = 0
        self.metrics.counters.add("overflowRaised")
        raise HistoryOverflowError(
            f"history_capacity={self.config.history_capacity} / "
            f"delta_capacity={self.config.delta_capacity} exceeded; "
            "increase it (or lower the MVCC window / write rate, or "
            "compact the delta tier more often)"
        )

    def resolve_packed(self, batch: packing.PackedBatch) -> _D.BatchVerdict:
        """Kernel-only path for pre-packed batches (bench / perf tests).

        Skips the Python packer and reply assembly; the caller owns
        version rebasing (offsets must fit int32).
        """
        return self.resolve_args(batch.device_args())

    def resolve_args(self, args) -> _D.BatchVerdict:
        """One batch through the tiered kernel (G=1) from an
        already-materialized device_args tree (host numpy or
        device-resident arrays alike): its BatchVerdict."""
        return self._dispatch_tiered(
            self._transfer(_stack_one(args)), first_row=True
        )

    def _tiered_jit(self, ssl, unroll, latch, dedup, sweep=False):
        """The compiled tiered kernel for this instance: the module
        single-device jit, or — on a sharded instance — the mesh
        shard_map program with this instance's partition bound (ONE
        compiled program per group: clip + per-shard scan + pmin/psum
        combine; see parallel/sharding.tiered_sharded_jit)."""
        if not self.sharded:
            return _resolve_tiered_jit(ssl, unroll, latch, dedup, sweep)
        from foundationdb_tpu.parallel import sharding as _sh

        fn = _sh.tiered_sharded_jit(
            self._mesh, ssl, unroll, latch, dedup,
            range_sweep=sweep,
            axis=getattr(self.config, "shard_axis", _sh.AXIS),
        )
        return lambda st, args: fn(st, args, self._part_lo, self._part_hi)

    def _dispatch_tiered(self, stacked_args, check_latch: bool = True,
                         first_row: bool = False):
        """Dispatch one stacked group on the tiered kernel, honoring the
        latch contract (fixpoint latch OR dedup overflow both surface as
        GroupVerdict.unconverged with the state unchanged): by default
        the host re-dispatches the same args on the exact kernel
        (fixpoint_latch=False, dedup_reads=0). Pipelined callers pass
        check_latch=False and fall back themselves. Auto-compaction runs
        every config.compact_interval BATCHES. With `first_row` (a G=1
        group of one batch) the dispatch stage also slices that batch's
        BatchVerdict out of the group result."""
        cfg = self.config
        ssl = getattr(cfg, "short_span_limit", 0)
        unroll = getattr(cfg, "fixpoint_unroll", 3)
        latch = getattr(cfg, "fixpoint_latch", False)
        dedup = getattr(cfg, "dedup_reads", 0)
        sweep = getattr(cfg, "range_sweep", False)
        kb = int(stacked_args["version"].shape[0])
        if getattr(cfg, "delta_spill", False):
            # SPILL-AND-COMPACT (ISSUE 14): before a dispatch whose
            # conservative boundary bound could overflow the delta tier
            # (each batch adds at most 2 boundary rows per write row of
            # the rung it dispatches at; the host tracks the bound so no
            # device sync is ever paid),
            # fold delta into MAIN with the compaction program — an
            # asynchronous device dispatch like any batch — instead of
            # letting the in-kernel latch trip and raise. A stream
            # sized past delta_capacity completes on device with zero
            # host exact-kernel re-dispatches; only a SINGLE group
            # whose own bound exceeds delta_capacity still reaches the
            # latch+raise backstop (a configuration error spill cannot
            # paper over).
            add = 2 * int(stacked_args["write_valid"].shape[-1]) * kb
            if self._spill_bound_rows + add > cfg.delta_capacity:
                self.compact_history()
                self.metrics.counters.add("spills")
                code_probe(True, "resolver.delta_spill")
            self._spill_bound_rows += add
        if sweep:
            self.metrics.counters.add("sweepGroups")
            code_probe(True, "resolver.range_sweep")
        if (latch or dedup) and check_latch:
            # prewarm the EXACT program at first sight of a shape, so a
            # latch/dedup trip swaps programs instead of paying an XLA
            # compile inside the commit path (the prewarm_exact
            # discipline, applied automatically on the checked path;
            # pipelined callers pass check_latch=False and prewarm
            # explicitly). The exact kernel does not donate state, so
            # one discarded execution is side-effect-free. The sweep is
            # not a latch source, so the fallback program keeps it —
            # same probe, exact fixpoint.
            shape_key = tuple(
                (k, tuple(stacked_args[k].shape)) for k in sorted(stacked_args)
            )
            if shape_key not in self._prewarmed_exact:
                self._prewarmed_exact.add(shape_key)
                self._tiered_jit(ssl, unroll, False, 0, sweep)(
                    self.state, stacked_args
                )
        with stage(self.metrics.kernel, STAGE_NAMES["dispatch"]):
            state2, outs = self._tiered_jit(ssl, unroll, latch, dedup, sweep)(
                self.state, stacked_args
            )
            self.metrics.counters.add("groupDispatches")
            if (latch or dedup) and check_latch and bool(
                np.asarray(outs.unconverged).any()
            ):
                self.metrics.counters.add("latchTrips")
                self.metrics.counters.add("exactFallbacks")
                state2, outs = self._tiered_jit(ssl, unroll, False, 0, sweep)(
                    self.state, stacked_args
                )
            if first_row:
                outs = _D.BatchVerdict(
                    verdict=outs.verdict[0],
                    hist_conflict_read=outs.hist_conflict_read[0],
                    intra_first_range=outs.intra_first_range[0],
                    committed_count=outs.committed_count[0],
                    conflict_count=outs.conflict_count[0],
                    too_old_count=outs.too_old_count[0],
                    overflow=outs.overflow[0],
                    fixpoint_iters=outs.fixpoint_iters[0],
                )
        self.state = state2
        self._batches_since_check += kb - 1
        self._maybe_check_overflow()
        # auto-compaction counts BATCHES (a fused group counts G), so
        # per-batch resolve() callers pay the main-sized compaction at
        # the same cadence as the fused bench stream
        self._batches_since_compact += kb
        interval = getattr(cfg, "compact_interval", 0)
        if interval and self._batches_since_compact >= interval:
            self.compact_history()
        return outs

    def compact_history(self) -> None:
        """Fold the delta tier into main (ops/delta.compact): one
        device program, dispatched asynchronously like any batch — the
        only main-sized pass in the tiered design, off the per-batch
        path."""
        self._batches_since_compact = 0
        self._spill_bound_rows = 0
        self.metrics.counters.add("compactions")
        with stage(self.metrics.compact, STAGE_NAMES["compact"]):
            if self.sharded:
                from foundationdb_tpu.parallel import sharding as _sh

                self.state = _sh.compact_sharded_jit(
                    self._mesh,
                    axis=getattr(self.config, "shard_axis", _sh.AXIS),
                )(self.state)
            else:
                self.state = _COMPACT(self.state)

    def resolve_group_args(self, stacked_args, check_latch: bool = True):
        """Resolve K batches stacked on a leading axis (a device_args
        tree whose leaves carry a leading [K] axis) in ONE dispatch of
        the tiered kernel: a GroupVerdict with [K, ...] leaves, in batch
        order, state chained across the K batches inside the program.
        Versions must ascend across the stack (sequencer contract).

        With `config.fixpoint_latch` or `config.dedup_reads` the kernel
        may REFUSE a group (GroupVerdict.unconverged; the returned state
        is the unchanged input state). By default this method honors the
        kernel contract itself: it host-checks the latch and re-dispatches
        the same args on the exact kernel (ADVICE r4 — callers must never
        see untrustworthy verdicts). The check costs one device sync per
        group; pipelined callers that fence once per stream (bench.py)
        pass check_latch=False and fall back themselves. Call
        `prewarm_exact` up front so the fallback swaps programs in
        milliseconds instead of paying an XLA compile mid-stream.
        """
        return self._dispatch_tiered(stacked_args, check_latch=check_latch)

    def resolve_group_stream(self, host_groups: list,
                             check_latch: bool = True) -> list:
        """Resolve a stream of pre-stacked groups with the staging
        pipeline (kept for callers that stack their own groups; see
        resolve_stream_pipelined for the full pack→transfer→compute
        pipeline over flat batches)."""
        return self._pipelined(
            host_groups, lambda g: g, check_latch=check_latch
        )

    def resolve_stream_pipelined(self, batches: list, *, chunk: int = 8,
                                 depth: int = 2,
                                 check_latch: bool = False) -> list:
        """Resolve a stream of host-side PackedBatches through a
        PACK→TRANSFER→COMPUTE pipeline at sub-group depth (VERDICT r5
        task 2 — the r4-r5 double buffering staged whole pre-stacked
        groups and still packed on the critical thread).

        A staging thread stacks `chunk` batches at a time
        (packing.stack_device_args — bulk numpy, the vectorized packer's
        output format) and issues the asynchronous host->device copy;
        the MAIN thread only dispatches compute. jax.device_put rides
        its own stream, so the pack+copy of chunk k+1 overlaps the
        compute of chunk k, with at most `depth` staged chunks in
        flight. Returns the GroupVerdicts in chunk order; the caller
        fences when it consumes them (check_latch defaults False like
        every pipelined path — callers handle an unconverged chunk by
        falling back to the exact kernel themselves)."""
        groups = [
            batches[lo : lo + chunk] for lo in range(0, len(batches), chunk)
        ]
        return self._pipelined(
            groups, packing.stack_device_args,
            depth=depth, check_latch=check_latch,
        )

    def _pipelined(self, items: list, pack_fn, *, depth: int = 2,
                   check_latch: bool = True) -> list:
        """Shared staging-thread pipeline: pack_fn(item) -> stacked host
        args, device_put on the staging thread, compute on this one.

        A consumer-side failure (e.g. HistoryOverflowError from the
        overflow interval check) must not strand the staging thread
        blocked on the bounded queue holding staged device buffers: the
        abort flag makes every producer put bounded, and the finally
        drains whatever was staged before joining."""
        import queue as _queue
        import threading

        if not items:
            return []
        q: _queue.Queue = _queue.Queue(maxsize=max(1, depth))
        done = object()
        abort = threading.Event()

        def _put(obj) -> bool:
            while not abort.is_set():
                try:
                    q.put(obj, timeout=0.05)
                    return True
                except _queue.Full:
                    continue
            return False

        def _stage():
            try:
                for item in items:
                    t0 = time.perf_counter()
                    host = pack_fn(item)
                    t1 = time.perf_counter()
                    # sharded instances replicate the packed chunk over
                    # the mesh here, on the staging thread — the
                    # compute thread's dispatch then finds every shard's
                    # copy already in flight (same overlap contract as
                    # the single-device async copy)
                    if self._batch_sharding is not None:
                        staged = jax.device_put(host, self._batch_sharding)
                    else:
                        staged = jax.device_put(host)
                    # pack + copy-issue stage timings, off the compute
                    # thread (the copy itself overlaps compute; its true
                    # cost shows up in the fenced transfer metric of
                    # stage_ledger passes)
                    self.metrics.pack.sample(t1 - t0)
                    self.metrics.transfer.sample(time.perf_counter() - t1)
                    self.metrics.counters.add("stagedChunks")
                    if not _put(staged):
                        return
            except BaseException as e:  # surfaced on the consumer thread
                _put(e)
                return
            _put(done)

        t = threading.Thread(
            target=_stage, name="resolver-staging", daemon=True
        )
        t.start()
        outs = []
        try:
            while True:
                staged = q.get()
                if staged is done:
                    break
                if isinstance(staged, BaseException):
                    raise staged
                outs.append(
                    self.resolve_group_args(staged, check_latch=check_latch)
                )
        finally:
            abort.set()
            while True:
                try:
                    q.get_nowait()
                except _queue.Empty:
                    break
            t.join()
        return outs

    def prewarm_exact(self, stacked_args) -> None:
        """Warm the exact kernel for this args shape so a latch trip
        swaps programs in milliseconds instead of stalling the version
        chain behind an XLA compile — the reference resolver never
        stalls its chain (fdbserver/Resolver.actor.cpp:283-296). The
        kernel does not donate state, so executing it once and
        discarding the results is side-effect-free; the compile lands in
        both the jit call cache and the persistent compile cache. No-op
        when neither the fixpoint latch nor the dedup latch can trip."""
        cfg = self.config
        if not (cfg.fixpoint_latch or cfg.dedup_reads):
            return
        _, outs = self._tiered_jit(
            cfg.short_span_limit, cfg.fixpoint_unroll, False, 0,
            cfg.range_sweep,
        )(self.state, stacked_args)
        jax.block_until_ready(outs.verdict)

    def _sample_collective(self) -> None:
        """Time one fenced dispatch of the combine-only probe program
        (the pmin/psum round the sharded kernel pays per group) on the
        sync the overflow check already forced — the measured collective
        cost behind qos()'s collective_time_share. First call compiles;
        that run is discarded, not sampled."""
        from foundationdb_tpu.parallel import sharding as _sh

        cfg = self.config
        fn = _sh.collective_probe_jit(
            self._mesh, cfg.max_txns,
            axis=getattr(cfg, "shard_axis", _sh.AXIS),
        )
        v = jnp.zeros((cfg.max_txns,), jnp.int32)
        r = jnp.zeros((cfg.max_reads,), jnp.int32)
        if not self._collective_probe_warm:
            self._collective_probe_warm = True
            jax.block_until_ready(fn(v, r))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(v, r))
        self.metrics.collective.sample(time.perf_counter() - t0)

    def _state_device(self):
        """The device holding the history state (= the dispatch
        device); None when it can't be read (host numpy state, exotic
        shardings) — device_memory_stats then falls back to device 0."""
        try:
            devices = self.state.main.overflow.devices()
            return next(iter(devices)) if len(devices) == 1 else None
        except Exception:
            return None

    def kernel_cost_analysis(self, stacked_args) -> dict:
        """HLO cost-model extraction (utils/perf.cost_analysis_of) for
        the group program this instance would dispatch on
        `stacked_args`: FLOPs / bytes accessed per compiled resolver
        kernel, recorded per bench run so hardware sessions can compare
        achieved rates against the roofline. Lower+compile of a warm
        signature is a persistent-cache hit, so this costs
        de/serialization, not a compile. Empty dict on any failure."""
        from foundationdb_tpu.utils import perf as _perf

        cfg = self.config
        ssl = getattr(cfg, "short_span_limit", 0)
        unroll = getattr(cfg, "fixpoint_unroll", 3)
        latch = getattr(cfg, "fixpoint_latch", False)
        if self.sharded:
            from foundationdb_tpu.parallel import sharding as _sh

            fn = _sh.tiered_sharded_jit(
                self._mesh, ssl, unroll, latch,
                getattr(cfg, "dedup_reads", 0),
                range_sweep=getattr(cfg, "range_sweep", False),
                axis=getattr(cfg, "shard_axis", _sh.AXIS),
            )
            return _perf.cost_analysis_of(
                fn, self.state, stacked_args, self._part_lo, self._part_hi
            )
        fn = _resolve_tiered_jit(
            ssl, unroll, latch, getattr(cfg, "dedup_reads", 0),
            getattr(cfg, "range_sweep", False),
        )
        return _perf.cost_analysis_of(fn, self.state, stacked_args)

    def _re_anchor_spill_bound(self, d_live: float) -> None:
        """ISSUE 15 (ROADMAP PR-14 headroom (b)): tighten the delta_spill
        pressure bound to the REAL delta occupancy, piggybacked on the
        sync the overflow check already paid — zero extra fences.

        The host bound accrues 2 x the write rows of the rung each batch
        dispatched at (duplicate keys and merged ranges make the true
        boundary count far smaller on most streams); at this sync every
        dispatched batch has completed, so the measured live boundary
        count IS the exact occupancy the bound conservatively
        over-estimates.
        Re-anchoring to min(bound, live) keeps the bound conservative
        (batches dispatched after the sync keep accruing the worst
        case) while shedding the accumulated over-estimate — ~2x fewer
        pressure spills on overlapping-write streams, with DECISIONS
        UNCHANGED (spill timing only moves compaction points, and
        decisions are compaction-cadence invariant — pinned in
        tests/test_range_sweep.py)."""
        bound = int(d_live)
        if bound < self._spill_bound_rows:
            self._spill_bound_rows = bound
            self.metrics.counters.add("spillBoundAnchors")

    def _maybe_check_overflow(self) -> None:
        self._batches_since_check += 1
        if self._batches_since_check >= OVERFLOW_CHECK_INTERVAL:
            with stage(self.metrics.overflow_check,
                       STAGE_NAMES["overflow_check"]):
                self.check_overflow()

    def check_overflow(self) -> None:
        """Device sync: raise if a merge ever exceeded either tier's
        capacity (a latched delta overflow survives compaction by
        folding into main.overflow)."""
        self._batches_since_check = 0
        if self.sharded:
            # any-shard overflow; occupancy samples take the WORST
            # shard's live counts (the fdbtop per-shard panel input)
            tripped = bool(np.asarray(self.state.main.overflow).any()) or (
                bool(np.asarray(self.state.delta.overflow).any())
            )
            m_cnt, d_cnt = _D.boundary_counts_per_shard(self.state)
            d_live = float(np.asarray(d_cnt).max())
            self.metrics.main_occupancy.sample(float(np.asarray(m_cnt).max()))
            self.metrics.delta_occupancy.sample(d_live)
            self._re_anchor_spill_bound(d_live)
            self._sample_collective()
        else:
            tripped = bool(np.asarray(self.state.main.overflow)) or bool(
                np.asarray(self.state.delta.overflow)
            )
            # tier-occupancy sampling rides the sync this check already
            # paid — two more scalar pulls, no extra fence
            m_cnt, d_cnt = _D.boundary_counts(self.state)
            d_live = float(np.asarray(d_cnt))
            self.metrics.main_occupancy.sample(float(np.asarray(m_cnt)))
            self.metrics.delta_occupancy.sample(d_live)
            self._re_anchor_spill_bound(d_live)
        # device-memory gauges ride the same sync (allocator stats are
        # a host call, no fence; CPU backends report nothing and skip),
        # sampled on the device holding the history state
        self.metrics.sample_device_memory(self._state_device())
        if tripped:
            self._raise_overflow()

    # -- reply assembly --------------------------------------------------

    @staticmethod
    def _assemble(batch, verdict, hist_read, intra_first, report,
                  begin_key_of_row) -> BatchResult:
        """The reply from the read-back result columns, shared by the
        object and columnar paths.

        `report[t]` = the txn asked for the conflicting-key report;
        `begin_key_of_row(r)` = flat read row r's range BEGIN key bytes
        (object path: through the transaction list; columnar: sliced
        from the frame's key blob). Only the txns that asked and
        CONFLICTed are walked, and only their history-hit read rows are
        touched: past the verdict list, the work grows with the reports
        asked for, not with the batch or its history hits.
        """
        verdict = np.asarray(verdict)
        verdicts = list(map(_RESULTS.__getitem__, verdict.tolist()))
        asked = np.asarray(report, bool) & (
            verdict == int(TransactionResult.CONFLICT)
        )
        conflicting: dict[int, list[int]] = {}
        if not asked.any():
            return BatchResult(verdicts=verdicts,
                               conflicting_key_ranges=conflicting)
        # the asking txns' per-read-range history hits, grouped by txn
        read_txn = batch.read_txn[: batch.n_reads]
        hist_hits_by_txn: dict[int, list[tuple[bytes, int]]] = {}
        rows = np.flatnonzero(hist_read[: batch.n_reads] & asked[read_txn])
        for r in rows.tolist():
            hist_hits_by_txn.setdefault(int(read_txn[r]), []).append(
                (begin_key_of_row(r), int(batch.read_index[r]))
            )
        for t in np.flatnonzero(asked).tolist():
            if t in hist_hits_by_txn:
                hits = sorted(hist_hits_by_txn[t])  # begin-key order
                conflicting[t] = [i for _, i in hits]
            elif intra_first[t] >= 0:
                conflicting[t] = [int(intra_first[t])]
        return BatchResult(verdicts=verdicts, conflicting_key_ranges=conflicting)


def stage_ledger(config: KernelConfig, batches, *, fuse: int,
                 kernel_s: float, pipelined_s: float = 0.0,
                 occupancy_delta_capacity: int = None) -> dict:
    """The per-stage ablation ledger: pack / transfer / kernel / fence
    ms per fused group + merge-row accounting, measured through the SAME
    `KernelStageMetrics` instrumentation the live resolve paths emit —
    bench.py is a reader of this function, not an owner of private
    timers.

    * pack: stacking all groups serially on the host (the staging
      thread's work), from the instrumented pack stage.
    * transfer: fenced device_put of the pre-stacked groups (the true
      copy cost; the async pipeline overlaps it with compute).
    * kernel: `kernel_s` — the caller's device-resident measurement for
      the whole stream (the phase-3 number of record).
    * fence: a fenced pass of the same program mix minus `kernel_s` —
      the per-group sync penalty and nothing else.
    * merge rows: what one group's history machinery touches; the
      delta tier's true end-of-stream occupancy comes from a separate
      compaction-disabled pass read via `KernelStageMetrics` occupancy
      samples.
    """
    import dataclasses as _dc

    from foundationdb_tpu.utils.packing import stack_device_args

    n_batches = len(batches)
    groups = [batches[g: g + fuse] for g in range(0, n_batches, fuse)]
    n_groups = len(groups)

    # pack + fenced transfer, through the instrumented stages
    cs = TpuConflictSet(config)
    host_groups = []
    for grp in groups:
        t0 = time.perf_counter()
        host_groups.append(stack_device_args(grp))
        cs.metrics.pack.sample(time.perf_counter() - t0)
    staged = []
    for hg in host_groups:
        t0 = time.perf_counter()
        dev = jax.device_put(hg)
        # fencing per group IS the measurement here: the ledger reports
        # the true per-group copy cost the async pipeline overlaps
        jax.block_until_ready(dev)  # flowcheck: ignore[jax.block-in-loop]
        cs.metrics.transfer.sample(time.perf_counter() - t0)
        staged.append(dev)
    pack_s = cs.metrics.pack.total
    transfer_s = cs.metrics.transfer.total

    # fenced pass: same program mix as the async measurement pass
    # (identical config incl. compaction cadence), per-group sync
    t0 = time.perf_counter()
    for dg in staged:
        out_f = cs.resolve_group_args(dg, check_latch=False)
        np.asarray(out_f.verdict)  # per-group fence
    fenced_s = time.perf_counter() - t0

    nrw = config.max_reads + config.max_writes
    ledger = {
        "pack_ms_per_group": round(pack_s / n_groups * 1e3, 1),
        "transfer_ms_per_group": round(transfer_s / n_groups * 1e3, 1),
        "kernel_ms_per_group": round(kernel_s / n_groups * 1e3, 1),
        "fence_ms_per_group": round(
            max(0.0, fenced_s - kernel_s) / n_groups * 1e3, 1
        ),
        "pipelined_ms_per_group": round(pipelined_s / n_groups * 1e3, 1),
    }
    # separate UNTIMED pass with compaction disabled: the delta tier's
    # true end-of-stream occupancy (what a batch's skeleton actually
    # co-sorts when compaction is deferred). Delta sized for the window
    # worst case — a capacity sized for the compaction cadence would
    # overflow with compaction off.
    occ_cap = occupancy_delta_capacity or config.history_capacity
    # delta_spill off too: a pressure fold mid-pass would reset the
    # very occupancy this pass exists to measure
    cs_occ = TpuConflictSet(
        _dc.replace(config, compact_interval=0, delta_capacity=occ_cap,
                    delta_spill=False)
    )
    for dg in staged:
        cs_occ.resolve_group_args(dg, check_latch=False)
    m_cnt, d_cnt = _D.boundary_counts(cs_occ.state)
    d_live = int(np.asarray(d_cnt))
    m_live = int(np.asarray(m_cnt))
    cs_occ.metrics.delta_occupancy.sample(float(d_live))
    cs_occ.metrics.main_occupancy.sample(float(m_live))
    ledger["merge_rows_tiered_per_batch_cap"] = config.delta_capacity + 2 * nrw
    ledger["merge_rows_tiered_per_batch_live"] = d_live + 2 * nrw
    ledger["delta_live_boundaries"] = d_live
    ledger["main_live_boundaries"] = m_live
    return ledger


# ---------------------------------------------------------------------------
# Contention-profile routing (VERDICT r4 task 2): batch size alone does
# not predict which backend wins — the r5 device measurements on the
# three graded configs (bench.py BENCH_MODE=*, logs *_r5.log) are:
#
#   uniform 1M keyspace:        device 0.70-0.97M vs skiplist ~0.31M (wins 2-3x)
#   zipf hot-key contention:    device 0.72M vs skiplist 1.07M  (LOSES, 0.68x)
#   range-heavy (500-key scans): device 0.59M vs skiplist 2.10M (LOSES, 0.28x)
#
# The CPU skiplist thrives exactly where the TPU kernel's fixed-width
# data-parallel passes cannot early-out: hot-key streams (conflict
# chains deepen, most txns abort fast on CPU) and wide scans (the
# skiplist skips subtrees; the kernel pays every covered block). Both
# regimes are CHEAPLY detectable host-side from the packed batch.


def _fold_key64(data, jj=None):
    """Fold each key row of a [N, ncol] big-endian WORD array into one
    int64 anchored at the first VARYING word — the ONE classifier core
    `profile_batch` (packed uint32 words) and `profile_transactions`
    (raw key bytes packed to words) both run, so the two can never
    disagree on a keyspace again (ISSUE 14 satellite: one used to fold
    the first varying word, the other stripped the BYTE-granularity
    common prefix and read 8 bytes — a long shared prefix put the two
    windows at different offsets and the span/dup thresholds diverged).

    Keyspaces with a common prefix (subspaces, short keys) keep leading
    words constant, so the span window anchors at the first word that
    varies. The successor word joins the low slot only when it VARIES
    in the sample: a constant successor — including the zero padding
    past short keys, which is how the packed and raw representations
    used to diverge — would scale every span by 2^32. (Duplicate
    detection does NOT use this fold: _classify compares full key rows,
    exactly — a fold window would collapse keys differing outside it.)

    jj: optional (j, use_succ) from a previous call, so range END keys
    fold through the same window as their BEGIN keys.
    Returns (vals [N] int64, (j, use_succ)).
    """
    import numpy as np

    ncol = data.shape[1]
    if jj is None:
        j = 0
        while j < ncol - 1 and len(np.unique(data[:, j])) == 1:
            j += 1
        use_succ = j + 1 < ncol and len(np.unique(data[:, j + 1])) > 1
        jj = (j, use_succ)
    j, use_succ = jj
    if use_succ:
        hi, lo = data[:, j], data[:, j + 1]
    else:
        # the varying word is effectively the LAST one: it must occupy
        # the LOW slot or every span/dup scales by 2^32
        hi, lo = np.zeros(len(data), np.int64), data[:, j]
    return (hi << 32) | lo, jj


def _keys_to_words(keys, width: int):
    """Raw key bytes -> [N, width] int64 big-endian uint32 words, zero-
    padded — the same word layout utils/packing gives a PackedBatch's
    key tensors (minus the length word), so _fold_key64 sees the
    identical representation from both classifiers."""
    import numpy as np

    out = np.zeros((len(keys), width), np.int64)
    for i, k in enumerate(keys):
        padded = k.ljust(width * 4, b"\0")[: width * 4]
        out[i] = np.frombuffer(padded, dtype=">u4").astype(np.int64)
    return out


#: classification thresholds shared by both classifiers (one source of
#: truth): duplicate-write-key rate above DUP_HOT is hot-key contention
#: (zipf-0.99 over 10M keys measures ~0.5+; uniform 64K/1M ~0.03), and
#: a mean read span above SPAN_RANGE keyspace units is range-heavy
#: (point reads span ~1-2; the range config's scans span hundreds).
PROFILE_DUP_HOT = 0.25
PROFILE_SPAN_RANGE = 32


def _classify(wrows, rbvals, revals) -> str:
    """Shared threshold logic: `wrows` is the [N, ncol] write-key WORD
    array — duplicate detection is EXACT row uniqueness (a fold window
    would collapse keys differing outside it into spurious hot_key;
    zero padding keeps uniqueness identical between the packed and raw
    representations) — while spans use the folded int64 window."""
    import numpy as np

    if len(wrows):
        dup = 1.0 - len(np.unique(wrows, axis=0)) / len(wrows)
        if dup > PROFILE_DUP_HOT:
            return "hot_key"
    if len(rbvals):
        span = float(np.mean(np.minimum(
            np.maximum(revals - rbvals, 0), 1 << 20
        )))
        if span > PROFILE_SPAN_RANGE:
            return "range_heavy"
    return "uniform"


def profile_batch(batch, sample: int = 2048) -> str:
    """Classify a PackedBatch's contention regime: "uniform" |
    "hot_key" | "range_heavy". Host-side, O(sample)."""
    import numpy as np

    nw = max(1, batch.n_writes)
    nr = max(1, batch.n_reads)

    def words(arr, n):
        a = arr[: min(n, sample)].astype(np.int64)
        return a[:, :-1] if a.shape[1] > 1 else a  # drop the length word

    rb, jj = _fold_key64(words(batch.read_begin, nr))
    re, _ = _fold_key64(words(batch.read_end, nr), jj)
    return _classify(words(batch.write_begin, nw), rb, re)


def profile_transactions(txns, sample: int = 512) -> str:
    """profile_batch for raw CommitTransaction lists (the resolver's
    input shape). Host-side, O(sample). Packs the sampled keys into the
    SAME big-endian word representation a PackedBatch carries and runs
    the same _fold_key64 core, so a resolver that routed on raw
    transactions and a bench that routed on the packed batch agree by
    construction (pinned in tests/test_contention_router.py)."""
    writes = [
        r[0] for t in txns[:sample] for r in t.write_conflict_ranges
    ][:sample]
    reads = [
        r for t in txns[:sample] for r in t.read_conflict_ranges
    ][:sample]
    if len(writes) < 16 and not reads:
        return "uniform"
    width = max(
        [1] + [-(-len(k) // 4) for k in writes]
        + [-(-len(b) // 4) for b, _ in reads]
        + [-(-len(e) // 4) for _, e in reads]
    )
    # the same minimum-sample discipline as before the r14 unification:
    # a <16-write sample gives a dup estimate too noisy to act on
    wrows = _keys_to_words(writes if len(writes) >= 16 else [], width)
    if reads:
        rbvals, jj = _fold_key64(
            _keys_to_words([b for b, _ in reads], width)
        )
        revals, _ = _fold_key64(
            _keys_to_words([e for _, e in reads], width), jj
        )
    else:
        rbvals = revals = _keys_to_words([], width)[:, 0]
    return _classify(wrows, rbvals, revals)


def backend_for_profile(profile: str, config=None) -> str:
    """The measured winner per regime (table above) — NARROWED as the
    kernel grows the structure each regime needs, until the router has
    nothing left to route away (ROADMAP "kill the CPU fallback"):

    * hot_key stays on device with the r6 tiered+dedup kernel (the
      delta tier's merge rows scale with distinct boundaries and the
      dedup probe's searches with distinct ranges — the zipf attack);
    * range_heavy stays on device with the r14 SORTED-ENDPOINT SWEEP
      (config.range_sweep): wide scans cost one streaming co-sort per
      group plus O(1) table queries instead of per-read binary searches
      with a per-covered-block probe window — the regime where the
      fixed-width kernel lost 0.28x to the skiplist's subtree skipping
      no longer exists as a kernel shape.

    The narrowed thresholds encode each design's expected winner;
    bench.py's zipf and ycsb_e configs re-measure them every hardware
    run, so a regression shows up in the graded numbers, not silently
    in routing."""
    if profile == "uniform":
        return "tpu"
    if (
        profile == "hot_key"
        and config is not None
        and getattr(config, "dedup_reads", 0) > 0
    ):
        return "tpu"
    if (
        profile == "range_heavy"
        and config is not None
        and getattr(config, "range_sweep", False)
    ):
        return "tpu"
    return "cpu"


def fallback_free(config) -> bool:
    """True when this config leaves the router nothing to route away:
    every contention profile resolves on the device (tiered kernel with
    the dedup probe for hot_key, the endpoint sweep for range_heavy)
    and delta pressure spills-and-compacts instead of raising. The
    "no fallback" predicate README's router section documents.

    Note dedup_reads and range_sweep are per-profile probe choices and
    mutually exclusive on ONE instance — a deployment covers all
    profiles by routing per stream (route_stream picks the backend
    from the leading batches, and the resolver configures the probe
    for the profile it routed)."""
    return bool(
        config is not None
        and getattr(config, "delta_spill", False)
        and (
            getattr(config, "dedup_reads", 0) > 0
            or getattr(config, "range_sweep", False)
        )
    )


def route_stream(batches, config, sample_batches: int = 2) -> str:
    """Pick the backend for a stream from its leading batches' profiles
    + the batch-capacity gate (RESOLVER_TPU_MIN_BATCH): TPU for
    large-batch uniform streams — and, with the tiered+dedup kernel
    configured, hot-key streams too; with the tiered+sweep kernel,
    range-heavy streams too (see backend_for_profile — a fully
    configured deployment has nothing left to route away).
    Used by the resolver role when resolver_backend="tpu"."""
    from foundationdb_tpu.utils.knobs import SERVER_KNOBS

    if config.max_txns < SERVER_KNOBS.RESOLVER_TPU_MIN_BATCH:
        return "cpu"
    profiles = [profile_batch(b) for b in batches[:sample_batches]]
    chosen = {backend_for_profile(p, config) for p in profiles}
    if chosen == {"tpu"}:
        return "tpu"
    return "cpu"

"""What the conflict-set backends share, with no JAX in it.

The stage telemetry (`KernelStageMetrics`), the result and overflow
types, the CPU backend and the backend factory live here so that the
JAX-free resolvers (the native skip-list role, the simulation's CPU
backend) never import JAX: `make_conflict_set` imports the device
conflict set (`models/conflict_set.py`) only when it builds one. The
device module re-exports every name here.
"""

from __future__ import annotations

import dataclasses

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.types import CommitTransaction, TransactionResult
from foundationdb_tpu.utils.metrics import CounterCollection, LatencySample

#: The Resolver role's per-batch stages, in the order one batch passes
#: through them: status key (`qos.stages`) -> the host event each
#: stage's span carries on a profiler trace. The spans are siblings that
#: tile the handling of one batch, none enclosing another.
STAGE_NAMES = {
    "decode": "Resolver.resolveBatch.Decode",
    "key_sample": "Resolver.resolveBatch.KeySample",
    "pack": "Resolver.resolveBatch.Pack",
    "transfer": "Resolver.resolveBatch.Transfer",
    "dispatch": "Resolver.resolveBatch.Dispatch",
    "compact": "Resolver.resolveBatch.Compact",
    "overflow_check": "Resolver.resolveBatch.OverflowCheck",
    "readback": "Resolver.resolveBatch.Readback",
    "assemble": "Resolver.resolveBatch.Assemble",
    "encode": "Resolver.resolveBatch.Encode",
}


class KernelStageMetrics:
    """Always-on per-stage telemetry for the resolver kernel.

    First-class `LatencySample`/`CounterCollection` metrics emitted
    continuously from the resolve paths — pack / transfer / kernel /
    fence stage timings, tier occupancy, compaction cadence, dedup
    latch and exact-kernel fallback counts, overflow events. bench.py's
    ablation ledger and `cluster_status()`'s `resolver.kernel` section
    are READERS of this object; neither carries private timers.

    Timing semantics: stage samples are host wall-clock seconds.
    "transfer" covers placing the kernel's arguments on the device,
    "kernel" the jitted dispatch call (on asynchronous backends that is
    issue time); "fence" covers what follows the issue on the served
    path, the wait for and copy of the results ("readback", from the
    issue's end until the completion takes them up) and the reply
    assembly ("assemble"). "compact" and "overflow_check" time the
    compaction dispatch and the periodic overflow sync, which fall
    between the dispatch and the end of the issue. `arg_bytes` and
    `device_arg_bytes` sample, per transfer, the host bytes handed to
    the device and the bytes they take there, `dispatch_rows` the
    read + write rows of the rung the batch was packed at, and
    `fixpoint_iters`, per completed batch, the intra-batch fixpoint
    applications its kernel ran. Counters are event counts and
    deterministic per run; the periodic trace_counters flush ships only
    those, so traced simulation output stays bit-reproducible.
    """

    def __init__(self):
        self.counters = CounterCollection(
            "ResolverKernelMetrics",
            [
                "resolveBatches",
                "groupDispatches",
                "columnarBatches",
                "stagedChunks",
                "compactions",
                # pressure-driven delta->MAIN folds (delta_spill): the
                # compactions counter includes these; spills counts the
                # pressure-triggered subset — the "no raise, no host
                # re-dispatch" accounting the spill tests pin
                "spills",
                # overflow-check syncs where the measured live delta
                # occupancy tightened the host-side spill bound (real
                # occupancy, not the 2*max_writes worst case, drives
                # pressure spills)
                "spillBoundAnchors",
                # groups dispatched through the sorted-endpoint sweep
                # probe (range_sweep) — the range-path structural count
                "sweepGroups",
                "latchTrips",
                "exactFallbacks",
                "rebases",
                "overflowRaised",
                "warmCompiles",
            ],
        )
        # warm-compile / first-dispatch seconds (ResolverRole startup
        # prewarm records here so a compile stall is attributed to
        # startup, never hidden inside the first batch's commit latency)
        self.compile = LatencySample("compileSeconds")
        self.pack = LatencySample("packSeconds")
        self.transfer = LatencySample("transferSeconds")
        self.kernel = LatencySample("kernelSeconds")
        self.fence = LatencySample("fenceSeconds")
        self.compact = LatencySample("compactSeconds")
        self.overflow_check = LatencySample("overflowCheckSeconds")
        self.readback = LatencySample("readbackSeconds")
        self.assemble = LatencySample("assembleSeconds")
        self.arg_bytes = LatencySample("argBytes")
        self.device_arg_bytes = LatencySample("deviceArgBytes")
        self.dispatch_rows = LatencySample("dispatchRows")
        self.fixpoint_iters = LatencySample("fixpointIters")
        # tier occupancy (tiered kernel): live boundary rows per tier,
        # sampled at the overflow-check syncs (no extra device fences).
        # On a MESH-SHARDED instance the samples are the WORST shard's
        # counts (per-shard tiers fill independently; the panel wants
        # the one closest to overflow).
        self.delta_occupancy = LatencySample("deltaLiveBoundaries")
        self.main_occupancy = LatencySample("mainLiveBoundaries")
        # mesh-sharded kernel: shard count + the measured
        # per-group collective (pmin/psum combine) seconds, sampled
        # from the combine-only probe program on the overflow-check
        # syncs — the fdbtop kernel panel's per-shard columns
        self.shard_count = 1
        self.collective = LatencySample("collectiveSeconds")
        # mesh-sharded kernel: per batch, the most conflict ranges one
        # partition got over the mean, in % (sampled in the pack stage)
        self.shard_skew = LatencySample("shardSkewPct")
        # device-memory gauges: live-buffer + peak bytes on
        # the dispatch device, sampled on the same overflow-check syncs
        # (no extra fences); zero on backends that don't report (CPU)
        self.device_bytes_in_use = 0
        self.device_peak_bytes = 0

    def sample_device_memory(self, device=None) -> None:
        """Pull the device allocator's live/peak byte gauges — called
        from the overflow-check sync the resolve paths already pay,
        with the DISPATCH device (where the history state lives): on a
        multi-device host, device 0's allocator says nothing about an
        impending OOM on the device actually resolving batches.
        Host-dependent values: they feed status/qos readers only, never
        a CounterCollection the deterministic trace flush ships."""
        from foundationdb_tpu.utils import perf as _perf

        stats = _perf.device_memory_stats(device)
        if stats:
            self.device_bytes_in_use = stats.get("bytes_in_use", 0)
            self.device_peak_bytes = max(
                self.device_peak_bytes, stats.get("peak_bytes_in_use", 0)
            )

    def as_dict(self) -> dict:
        out: dict = dict(self.counters.as_dict())
        for s in (self.compile, self.pack, self.transfer, self.kernel,
                  self.fence, self.delta_occupancy, self.main_occupancy,
                  self.collective):
            out[s.name] = s.as_dict()
        out["shardCount"] = self.shard_count
        out["deviceBytesInUse"] = self.device_bytes_in_use
        out["devicePeakBytes"] = self.device_peak_bytes
        return out

    def stages(self) -> dict:
        """The conflict set's share of the role's `qos.stages`: each
        stage sample, the two byte samples, the dispatched rows and the
        fixpoint applications, as_dict() by status key, and on a sharded
        instance the partition skew."""
        out = {
            "pack": self.pack.as_dict(),
            "transfer": self.transfer.as_dict(),
            "dispatch": self.kernel.as_dict(),
            "compact": self.compact.as_dict(),
            "overflow_check": self.overflow_check.as_dict(),
            "readback": self.readback.as_dict(),
            "assemble": self.assemble.as_dict(),
            "arg_bytes": self.arg_bytes.as_dict(),
            "device_arg_bytes": self.device_arg_bytes.as_dict(),
            "dispatch_rows": self.dispatch_rows.as_dict(),
            "fixpoint_iters": self.fixpoint_iters.as_dict(),
        }
        if self.shard_count > 1:
            out["shard_skew"] = self.shard_skew.as_dict()
        return out

    def qos(self) -> dict:
        """The compressed occupancy view the saturation layer reads
        (status `qos` / fdbtop): per-batch kernel seconds (the fixed
        per-dispatch cost the tpu-force p99 backup rides on), the share
        of resolve wall time inside the device stages, and tier fill —
        one small dict, not the full stage-sample dump (as_dict)."""
        from foundationdb_tpu.utils import compile_cache as _cc

        batches = self.counters.get("resolveBatches")
        stage_total = (
            self.pack.total + self.transfer.total + self.kernel.total
            + self.fence.total
        )
        cc = _cc.stats()
        d_occ = self.delta_occupancy.max or 0.0
        m_occ = self.main_occupancy.max or 0.0
        return {
            "batches": batches,
            "group_dispatches": self.counters.get("groupDispatches"),
            "kernel_seconds_per_batch": (
                stage_total / batches if batches else 0.0
            ),
            "kernel_p99_seconds": self.kernel.quantile(0.99),
            # per-stage p99s (the fdbtop kernel panel's columns)
            "stage_p99_seconds": {
                "pack": self.pack.quantile(0.99),
                "transfer": self.transfer.quantile(0.99),
                "kernel": self.kernel.quantile(0.99),
                "fence": self.fence.quantile(0.99),
            },
            "compile_seconds": self.compile.total,
            # compile-cache observability (utils/compile_cache.py —
            # process-global: the XLA compiler and its cache are too)
            "compile_cache_hits": cc["cache_hits"],
            "compile_cache_misses": cc["cache_misses"],
            "last_compile_seconds": cc["last_compile_seconds"],
            # device-memory gauges from the overflow-check syncs
            "device_bytes_in_use": self.device_bytes_in_use,
            "device_peak_bytes": self.device_peak_bytes,
            "delta_occupancy": d_occ,
            "main_occupancy": m_occ,
            "compactions": self.counters.get("compactions"),
            # pressure spills (delta_spill) and sweep-probed
            # groups (range_sweep) — the "router has nothing left to
            # route away" accounting, zero on unconfigured instances
            "spills": self.counters.get("spills"),
            "sweep_groups": self.counters.get("sweepGroups"),
            "fallbacks": (
                self.counters.get("latchTrips")
                + self.counters.get("exactFallbacks")
            ),
            # mesh-sharded kernel columns (fdbtop per-shard panel;
            # zeros/1 on single-device backends so REQUIRED_SENSORS
            # pins them on every backend). The worst_shard_* keys ALIAS
            # the occupancy values above — sharded instances sample the
            # worst shard's counts into the same LatencySamples, so one
            # source value feeds both names and they cannot drift. The
            # collective share is measured combine-probe seconds over
            # per-batch resolve seconds.
            "shards": self.shard_count,
            "worst_shard_delta_occupancy": d_occ,
            "worst_shard_main_occupancy": m_occ,
            "collective_time_share": (
                min(
                    1.0,
                    (self.collective.total / self.collective.count)
                    / (stage_total / batches),
                )
                if self.collective.count and batches and stage_total
                else 0.0
            ),
        }


class HistoryOverflowError(RuntimeError):
    """Compacted history exceeded `history_capacity`.

    The reference's skip list grows without bound inside the MVCC window;
    our capacity is static. Overflow means the config is undersized for
    the write rate x window product — a config error, never silent
    wrong answers.
    """


@dataclasses.dataclass
class BatchResult:
    verdicts: list[TransactionResult]
    conflicting_key_ranges: dict[int, list[int]]


class CpuConflictSet:
    """CPU fallback behind the resolver_backend knob: the same
    ConflictBatch interface served by the exact host-side semantic model
    (testing.oracle.ConflictOracle — the reference's SkipList semantics
    without a device). Mirrors BASELINE.json's contract that the CPU
    path stays available (`resolver_backend=cpu`), e.g. for
    deterministic simulation without device calls."""

    def __init__(self, config: KernelConfig, base_version: int = 0):
        from foundationdb_tpu.testing.oracle import ConflictOracle, OracleTxn

        self.config = config
        self._oracle_txn = OracleTxn
        self._oracle = ConflictOracle(window=config.window_versions)
        # same metrics surface as TpuConflictSet so status readers never
        # special-case the backend (stage samples stay empty: the CPU
        # path has no pack/transfer/kernel split)
        self.metrics = KernelStageMetrics()

    def resolve(
        self, transactions: list[CommitTransaction], version: int
    ) -> BatchResult:
        self.metrics.counters.add("resolveBatches")
        res = self._oracle.resolve(
            [
                self._oracle_txn(
                    t.read_conflict_ranges,
                    t.write_conflict_ranges,
                    t.read_snapshot,
                    t.report_conflicting_keys,
                )
                for t in transactions
            ],
            version,
        )
        verdicts = [TransactionResult(v) for v in res.verdicts]
        conflicting = {
            t: idxs
            for t, idxs in res.conflicting_ranges.items()
            if transactions[t].report_conflicting_keys
            and verdicts[t] == TransactionResult.CONFLICT
        }
        return BatchResult(verdicts=verdicts, conflicting_key_ranges=conflicting)

    def check_overflow(self) -> None:
        pass  # unbounded host memory


def make_conflict_set(config: KernelConfig, backend: str = None):
    """The resolver_backend knob gate (BASELINE.json: the TPU path sits
    behind a knob; the CPU path remains selectable).

    With backend "tpu", configs whose batch capacity sits under
    SERVER_KNOBS.RESOLVER_TPU_MIN_BATCH auto-route to the CPU backend:
    at small batches the device dispatch alone exceeds the CPU's whole
    resolve (measured — bench.py BENCH_SMALL=1), so the TPU serves the
    loaded/batched regime and the CPU the latency regime. Explicit
    backend="tpu-force" bypasses the threshold (benches, tests)."""
    if backend is None:
        from foundationdb_tpu.utils.knobs import SERVER_KNOBS

        backend = SERVER_KNOBS.RESOLVER_BACKEND
    if backend == "tpu":
        from foundationdb_tpu.utils.knobs import SERVER_KNOBS

        if config.max_txns < SERVER_KNOBS.RESOLVER_TPU_MIN_BATCH:
            # Loud reroute: the default KernelConfig sizes
            # max_txns at 1024, well under the measured device/CPU
            # crossover, so backend="tpu" quietly serving CPU would be
            # a silent surprise. The gate is on the config's static
            # batch CAPACITY — the kernel is compiled for max_txns, so
            # capacity bounds the largest batch this instance could
            # ever route and is the honest static proxy for load.
            from foundationdb_tpu.utils.trace import SEV_WARN, TraceEvent

            TraceEvent(
                "ResolverBackendAutoRouted", severity=SEV_WARN
            ).detail("Requested", "tpu").detail("Chosen", "cpu").detail(
                "MaxTxns", config.max_txns
            ).detail(
                "MinBatch", SERVER_KNOBS.RESOLVER_TPU_MIN_BATCH
            ).log()
            return CpuConflictSet(config)
    if backend in ("tpu", "tpu-force"):
        from foundationdb_tpu.models.conflict_set import TpuConflictSet

        return TpuConflictSet(config)
    if backend == "cpu":
        return CpuConflictSet(config)
    raise ValueError(f"unknown resolver_backend {backend!r}")

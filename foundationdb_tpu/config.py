"""Static kernel configuration.

Every shape in the conflict kernel is static (XLA requirement); this config
pins the capacities. The host packer pads variable-size batches up to these
caps. Mirrors the role the reference's knobs play for the resolver
(fdbclient/ServerKnobs.cpp:36-44 — MVCC window knobs), but as compile-time
shape parameters rather than runtime constants.
"""

from __future__ import annotations

import dataclasses
import math


def _ceil_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Compile-time shapes for the conflict-resolution kernel.

    Attributes:
      max_key_bytes: maximum conflict-range key length the packed
        representation can hold exactly. Keys are encoded as big-endian
        uint32 words plus a final length word, which preserves FDB's key
        ordering contract exactly (byte-lexicographic, shorter-before-longer
        — fdbserver/SkipList.cpp:123-139).
      max_txns: txn capacity per batch (B).
      max_reads: total read-conflict-range capacity per batch (flattened).
      max_writes: total write-conflict-range capacity per batch (flattened).
      history_capacity: boundary capacity of the "main" version map. Must
        hold the live MVCC window's write boundaries (~2*max_writes per
        batch x window/version-step batches); overflow raises, never
        silently drops.
      window_versions: MVCC window: newOldestVersion = version - window
        (reference: MAX_WRITE_TRANSACTION_LIFE_VERSIONS = 5e6,
        fdbclient/ServerKnobs.cpp:43, used at fdbserver/Resolver.actor.cpp:331).
    """

    max_key_bytes: int = 24
    max_txns: int = 1024
    max_reads: int = 4096
    max_writes: int = 4096
    history_capacity: int = 1 << 15
    window_versions: int = 5_000_000
    #: 0 = fully general range structures. A positive S compiles the
    #: group kernel's range ops as direct S-wide gathers/scatters —
    #: much faster for point-ish conflict ranges — with a loud latch
    #: (overflow) if any live range ever spans more than S rank blocks.
    #: See ops/group.resolve_group.
    short_span_limit: int = 0
    #: Straight-line fixpoint applications compiled before the residual
    #: while_loop (ops/group.resolve_group). A while ITERATION measured
    #: ~5x an unrolled application (r4 ablations), so the unroll should
    #: cover the workload's typical convergence depth: ~3 at uniform
    #: contention, ~6 under hot-key (zipf) contention, ~12 for
    #: wide-range workloads (scripts/iters_model.py). Exactness never
    #: depends on it — deeper chains fall through to the loop.
    fixpoint_unroll: int = 3
    #: True compiles the group kernel WITHOUT the residual while_loop —
    #: its mere presence costs ~50ms/group of XLA pessimization at zero
    #: iterations (r4 measured). Convergence is then CHECKED per batch:
    #: a deeper-than-unroll chain trips GroupVerdict.unconverged, the
    #: state returns unchanged, and the caller re-dispatches on the
    #: exact kernel. Loud refusal, never a silent wrong answer.
    fixpoint_latch: bool = False
    #: Boundary capacity of the DELTA tier (ops/delta.py): new
    #: per-group writes land here, queried alongside the immutable main
    #: tier and folded into main by a periodic device-side compaction.
    #: Every per-batch shape in the tiered kernel is independent of the
    #: group size G (one lax.scan body), so XLA compiles once regardless
    #: of G. Sizing: must hold the boundaries written between
    #: compactions (<= 2*max_writes per batch, window-trimmed); overflow
    #: raises, never truncates. Unset (None), it is sized by that rule:
    #: 2 * max_writes * max(compact_interval, 1) (`dataclasses.replace`
    #: keeps the size so made). It must be positive.
    delta_capacity: int | None = None
    #: >0 compiles device-side HOT-KEY DEDUP of read conflict ranges
    #: before the main-tier probe: identical (begin, end) ranges are
    #: sort+unique'd and only this many DISTINCT ranges are binary-
    #: searched against main, so probe work scales with distinct keys,
    #: not points (the kernel-side attack on zipf contention). A batch
    #: with more distinct live read ranges than this trips the
    #: unconverged latch — state unchanged, host re-dispatches the exact
    #: kernel — never a silent wrong answer.
    dedup_reads: int = 0
    #: True compiles the tiered kernel's main-tier probe as a SORTED-
    #: ENDPOINT SWEEP (ops/delta.sweep_read_ranks): the whole group's
    #: read endpoints co-sort with the immutable main tier's boundary
    #: rows ONCE per group, il/ir ranks fall out of a running main-row
    #: count (searchsorted-right/left semantics from the sort order),
    #: and every batch's probe is then one O(1) range-max table query —
    #: no per-read binary searches against carried state, no bounded
    #: probe window, no dedup latch. Wide scans (range_heavy streams)
    #: cost O((M + G*R) log) streaming sorted work per GROUP instead of
    #: per-covered-block probes per batch, which is what lets
    #: backend_for_profile keep range_heavy on the device. Mutually
    #: exclusive with dedup_reads (they compile the same
    #: probe differently — pick per contention profile).
    range_sweep: bool = False
    #: True raises delta-capacity pressure handling from latch-and-raise
    #: to SPILL-AND-COMPACT: before a dispatch whose conservative
    #: boundary bound (2*max_writes per batch since the last fold) could
    #: overflow the delta tier, the host dispatches the compaction
    #: program (ops/delta.compact — delta folds into MAIN on device) and
    #: then the group, all asynchronously — no device sync, no
    #: HistoryOverflowError, no host exact-kernel re-dispatch. A stream
    #: sized past delta_capacity completes on device; the latch+raise
    #: remains only as the misconfiguration backstop (a SINGLE group's
    #: bound exceeding delta_capacity cannot be spilled around).
    delta_spill: bool = False
    #: The host folds delta into main after at least this many
    #: BATCHES have resolved since the last compaction (TpuConflictSet
    #: auto-compaction; a fused group of G batches counts G). Counting
    #: batches — not dispatches — keeps the per-batch resolve() hot
    #: path off the main-sized compaction pass at the same cadence the
    #: fused bench pays. 0 = only explicit compaction. Size
    #: delta_capacity for at least this many batches' boundaries
    #: (<= 2*max_writes each, window-trimmed).
    compact_interval: int = 8
    #: > 1 runs the MESH-SHARDED tiered kernel (parallel/sharding.py):
    #: conflict history (main + delta tier) is partitioned by key range
    #: across an n_shards-device mesh axis, every device clips the
    #: replicated packed batch to its partition, probes/merges its own
    #: tiers, and the per-shard verdicts min-combine on device
    #: (`lax.pmin`; conflict-read bitmasks via `lax.psum`) inside ONE
    #: compiled shard_map program — one pod slice acting as n_shards
    #: reference resolvers with no host round-trip between them.
    #: Per-shard history semantics are EXACTLY the reference's
    #: multi-resolver deployment (each shard merges its locally
    #: committed writes — phantom commits included), so decisions match
    #: the multi-resolver CPU path bit-for-bit. 0/1 = single-device
    #: kernel.
    n_shards: int = 0
    #: Mesh axis name the sharded kernel partitions over (must match
    #: the Mesh handed to TpuConflictSet; parallel.mesh.AXIS default).
    shard_axis: str = "resolver"

    def __post_init__(self):
        if self.max_key_bytes % 4 != 0:
            raise ValueError("max_key_bytes must be a multiple of 4")
        # history_capacity may be any size (nothing in the kernel needs it
        # to be a power of two); the batch caps must be pow2 for the rank
        # space / cover structures.
        for name in ("max_txns", "max_reads", "max_writes"):
            v = getattr(self, name)
            if v & (v - 1):
                raise ValueError(f"{name} must be a power of two, got {v}")
        if self.delta_capacity is None:
            object.__setattr__(
                self, "delta_capacity",
                2 * self.max_writes * max(self.compact_interval, 1),
            )
        elif self.delta_capacity <= 0:
            raise ValueError(
                f"delta_capacity must be positive, got {self.delta_capacity}"
            )
        if self.dedup_reads > self.max_reads:
            raise ValueError("dedup_reads cannot exceed max_reads")
        if self.range_sweep and self.dedup_reads:
            raise ValueError(
                "range_sweep and dedup_reads compile the same main-tier "
                "probe differently (sweep ranks vs dedup'd binary "
                "searches) — configure one per contention profile"
            )
        if self.n_shards < 0:
            raise ValueError("n_shards must be >= 0")

    # ---- derived shapes -------------------------------------------------

    @property
    def key_words(self) -> int:
        """uint32 words per packed key: byte words + 1 length word."""
        return self.max_key_bytes // 4 + 1

    @property
    def num_points(self) -> int:
        """Rank-space capacity: every read/write range contributes 2 points."""
        return 2 * (self.max_reads + self.max_writes)

    @property
    def segtree_size(self) -> int:
        """Leaf count of the intra-batch segment tree (pow2 >= num_points)."""
        return _ceil_pow2(self.num_points)

    @property
    def segtree_levels(self) -> int:
        return int(math.log2(self.segtree_size))

    @property
    def history_log(self) -> int:
        return int(math.log2(self.history_capacity)) + 1

    def scaled(self, **overrides) -> "KernelConfig":
        return dataclasses.replace(self, **overrides)


#: A deliberately tiny config for CPU-hosted unit tests.
TEST_CONFIG = KernelConfig(
    max_key_bytes=8,
    max_txns=64,
    max_reads=256,
    max_writes=256,
    history_capacity=1 << 10,
    window_versions=1000,
)

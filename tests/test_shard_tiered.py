"""Mesh-sharded delta-tiered kernel parity (ISSUE 11).

The production tiered path under shard_map (parallel/sharding.py via
TpuConflictSet(config.n_shards > 1)) must reproduce the reference's
multi-resolver deployment bit-for-bit: independent per-shard tiered
histories over a keyspace partition, locally-committed writes merged
per shard (phantom commits included), verdicts min-combined on device
(`pmin`; conflict-read bitmasks via `psum`). Oracles:

* MultiResolverOracle — the reference semantics model (always exact);
* the SINGLE-DEVICE tiered kernel — exact whenever no transaction can
  phantom-commit across shards: a degenerate partition (one empty
  shard) and shard-local workloads pin that equivalence.

Covers the ISSUE-11 satellite checklist: 1/2/4/8 virtual-device CPU
meshes, duplicate/overlapping-range and window-edge streams, per-shard
compaction-cadence invariance, the dedup-latch fallback, per-shard
overflow surviving compaction, and the PR-3 ResolutionBalancer
conservative-writes audit shape with the sharded kernel in the sim.

Runs in the kernel parity lane (8-device CPU mesh, -m kernel).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import TpuConflictSet
from foundationdb_tpu.models.conflict_base import (
    CpuConflictSet,
    HistoryOverflowError,
)
from foundationdb_tpu.models.types import CommitTransaction
from foundationdb_tpu.parallel.mesh import cpu_mesh
from foundationdb_tpu.parallel.sharding import default_boundaries
from foundationdb_tpu.testing.oracle import MultiResolverOracle, OracleTxn
from foundationdb_tpu.utils import packing
from foundationdb_tpu.utils.packing import stack_device_args

from conftest import random_range

# compile-heavy kernel tests: run with -m kernel (fast lane: -m 'not kernel')
pytestmark = pytest.mark.kernel


def tiered_config(n_shards=0, **kw):
    d = dict(
        max_key_bytes=8,
        max_txns=16,
        max_reads=32,
        max_writes=32,
        history_capacity=512,
        window_versions=1000,
        delta_capacity=256,
        compact_interval=1,
        n_shards=n_shards,
    )
    d.update(kw)
    return KernelConfig(**d)


def make_sharded(cfg, boundaries):
    return TpuConflictSet(
        cfg, mesh=cpu_mesh(cfg.n_shards), shard_boundaries=boundaries
    )


def even_boundaries(n):
    # conftest.random_range draws keys from alphabet bytes 0..3, so the
    # interior splits land inside that space to spread load across
    # shards (default_boundaries' byte-prefix split would put every
    # test key in shard 0 — legal, but it wouldn't exercise clipping).
    # For n=8 the odd splits bisect each first-byte bucket.
    if n <= 4:
        return [bytes([(4 * (i + 1)) // n]) for i in range(n - 1)]
    assert n == 8
    return [
        bytes([i // 2, 2]) if i % 2 else bytes([i // 2])
        for i in range(1, 8)
    ]


def to_oracle(txns):
    return [
        OracleTxn(
            read_conflict_ranges=t.read_conflict_ranges,
            write_conflict_ranges=t.write_conflict_ranges,
            read_snapshot=t.read_snapshot,
            report_conflicting_keys=t.report_conflicting_keys,
        )
        for t in txns
    ]


def random_txn(rng, *, snap_lo, snap_hi, n_ranges=2, blind_prob=0.15,
               dup_pool=None, report_prob=0.5):
    def draw():
        if dup_pool is not None and rng.random() < 0.7:
            return dup_pool[int(rng.integers(0, len(dup_pool)))]
        return random_range(rng)

    reads = [] if rng.random() < blind_prob else [
        draw() for _ in range(1 + int(rng.integers(0, n_ranges)))
    ]
    writes = [draw() for _ in range(1 + int(rng.integers(0, n_ranges)))]
    return CommitTransaction(
        read_conflict_ranges=reads,
        write_conflict_ranges=writes,
        read_snapshot=int(rng.integers(snap_lo, snap_hi)),
        report_conflicting_keys=bool(rng.random() < report_prob),
    )


def gen_stream(rng, n_batches, *, base=1000, step=100, n_txns=10,
               dup_pool=None):
    out = []
    for i in range(n_batches):
        version = base + (i + 1) * step
        out.append((
            [
                random_txn(
                    rng, snap_lo=max(0, base - 2 * step), snap_hi=version,
                    dup_pool=dup_pool,
                )
                for _ in range(n_txns)
            ],
            version,
        ))
    return out


def run_verdicts(cs, stream):
    return [
        [int(v) for v in cs.resolve(txns, ver).verdicts]
        for txns, ver in stream
    ]


def oracle_verdicts(oracle, stream):
    return [
        oracle.resolve(to_oracle(txns), ver).verdicts
        for txns, ver in stream
    ]


# ---------------------------------------------------------------------------
# Random-stream parity at every mesh width.


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_sharded_tiered_matches_multi_resolver_oracle(n_shards):
    rng = np.random.default_rng(n_shards)
    boundaries = even_boundaries(n_shards)
    cfg = tiered_config(n_shards=n_shards)
    dev = make_sharded(cfg, boundaries)
    oracle = MultiResolverOracle(boundaries, window=cfg.window_versions)
    stream = gen_stream(rng, 6)
    assert run_verdicts(dev, stream) == oracle_verdicts(oracle, stream)


def test_sharded_columnar_matches_multi_resolver_oracle():
    """The r12 acceptance pin: the COLUMNAR wire frame driven through a
    2-shard mesh (proxy-side pack_columnar -> codec roundtrip ->
    resolve_columnar, exactly the wire ResolverRole's path) must match
    the multi-resolver oracle AND the object-path sharded instance
    batch for batch — pack once, shard the same arrays over the mesh.
    """
    from foundationdb_tpu.wire import codec

    rng = np.random.default_rng(12)
    boundaries = even_boundaries(2)
    cfg = tiered_config(n_shards=2)
    dev_obj = make_sharded(cfg, boundaries)
    dev_col = make_sharded(cfg, boundaries)
    oracle = MultiResolverOracle(boundaries, window=cfg.window_versions)
    stream = gen_stream(rng, 6)
    got_obj = run_verdicts(dev_obj, stream)
    got_col = []
    for txns, ver in stream:
        msg = codec.decode(codec.encode(codec.ResolveBatchColumnar(
            prev_version=-1, version=ver, last_received_version=-1,
            cols=packing.pack_columnar(txns),
        )))
        res = dev_col.resolve_columnar(msg.cols, ver)
        got_col.append([int(v) for v in res.verdicts])
    assert got_col == got_obj == oracle_verdicts(oracle, stream)


@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_tiered_matches_classic_sharded(n_shards):
    """The tiered shard_map kernel must agree batch for batch with the
    reference multi-resolver semantics (MultiResolverOracle)."""
    rng = np.random.default_rng(40 + n_shards)
    boundaries = even_boundaries(n_shards)
    cfg = tiered_config(n_shards=n_shards)
    dev = make_sharded(cfg, boundaries)
    oracle = MultiResolverOracle(boundaries, window=cfg.window_versions)
    stream = gen_stream(rng, 6)
    assert run_verdicts(dev, stream) == oracle_verdicts(oracle, stream)


# ---------------------------------------------------------------------------
# Single-device equivalence on phantom-free shapes.


def test_degenerate_partition_matches_single_device():
    """A partition whose interior boundary exceeds every live key keeps
    ALL activity on shard 0 — no transaction can phantom-commit across
    shards, so the 2-shard mesh must equal the single-device tiered
    kernel exactly (verdicts AND conflicting-key reports)."""
    rng = np.random.default_rng(5)
    cfg = tiered_config(n_shards=2)
    dev = make_sharded(cfg, [b"\xf0\xf0\xf0"])
    single = TpuConflictSet(dataclasses.replace(cfg, n_shards=0))
    stream = gen_stream(rng, 6)
    for txns, ver in stream:
        got = dev.resolve(txns, ver)
        want = single.resolve(txns, ver)
        assert got.verdicts == want.verdicts
        assert got.conflicting_key_ranges == want.conflicting_key_ranges


def test_shard_local_workload_matches_single_device():
    """Each transaction's ranges confined to ONE shard: clipping routes
    every whole transaction to exactly one shard, phantom commits are
    impossible, and the 4-shard decisions equal the single-device
    kernel's."""
    rng = np.random.default_rng(9)
    boundaries = even_boundaries(4)
    cfg = tiered_config(n_shards=4)
    dev = make_sharded(cfg, boundaries)
    single = TpuConflictSet(dataclasses.replace(cfg, n_shards=0))

    def local_txn(version):
        first = int(rng.integers(0, 4))  # the owning shard's byte
        def key():
            return bytes([first]) + bytes(
                rng.integers(0, 4, size=int(rng.integers(1, 4)),
                             dtype=np.uint8)
            )
        def rr():
            a, b = sorted([key(), key()])
            return (a, b) if a != b else (a, a + b"\x00")
        return CommitTransaction(
            read_conflict_ranges=[rr() for _ in range(2)],
            write_conflict_ranges=[rr()],
            read_snapshot=int(rng.integers(800, version)),
        )

    version = 1000
    for _ in range(8):
        version += 100
        txns = [local_txn(version) for _ in range(10)]
        got = dev.resolve(txns, version)
        want = single.resolve(txns, version)
        assert got.verdicts == want.verdicts


# ---------------------------------------------------------------------------
# Adversarial shapes: duplicates/overlaps, window edges, cadences.


@pytest.mark.parametrize("seed", range(2))
def test_duplicate_and_overlapping_ranges_dedup_parity(seed):
    """Hot-key adversarial stream (most ranges from a small duplicate
    pool): the PER-SHARD dedup probe must be decision-identical to
    dedup-off and to the multi-resolver oracle."""
    rng = np.random.default_rng(200 + seed)
    pool = [random_range(rng) for _ in range(4)]
    stream = gen_stream(rng, 5, dup_pool=pool)
    boundaries = even_boundaries(2)
    oracle = MultiResolverOracle(boundaries, window=1000)
    want = oracle_verdicts(oracle, stream)
    res_d = run_verdicts(
        make_sharded(tiered_config(n_shards=2, dedup_reads=16), boundaries),
        stream,
    )
    res_p = run_verdicts(
        make_sharded(tiered_config(n_shards=2), boundaries), stream
    )
    assert res_d == want
    assert res_p == want


def test_window_edge_versions_sharded():
    """Snapshots exactly at / one beside the MVCC floor, with the two
    ranges on DIFFERENT shards: the too-old boundary and GC floor must
    match the multi-resolver oracle at every offset."""
    boundaries = [b"\x02"]
    cfg = tiered_config(n_shards=2, window_versions=100)
    dev = make_sharded(cfg, boundaries)
    oracle = MultiResolverOracle(boundaries, window=100)
    k = lambda i: bytes([i])
    stream = []
    for snap in (99, 100, 101, 199, 200):
        stream.append((
            [
                CommitTransaction([(k(1), k(2))], [(k(1), k(2))],
                                  read_snapshot=snap),
                CommitTransaction([(k(3), k(4))], [(k(3), k(4))],
                                  read_snapshot=snap),
                CommitTransaction([], [(k(1), k(4))], read_snapshot=snap),
            ],
            200 + len(stream),
        ))
    assert run_verdicts(dev, stream) == oracle_verdicts(oracle, stream)


def canonical_map_rows(main_keys, main_ver):
    rows = []
    for j in range(main_keys.shape[0]):
        if all(x == 0xFFFFFFFF for x in main_keys[j]):
            continue
        rows.append((tuple(main_keys[j]), int(main_ver[j])))
    rows.sort()
    dedup = {}
    for kk, v in rows:
        dedup[kk] = v
    out = []
    for kk in sorted(dedup):
        if not out or out[-1][1] != dedup[kk]:
            out.append((kk, dedup[kk]))
    return out


@pytest.mark.parametrize("interval", [2, 4, 0])
def test_compaction_cadence_invariance_per_shard(interval):
    """Decisions must not depend on WHEN each shard folds delta into
    main, and after a final explicit compaction every shard's combined
    key->version map must be identical across cadences."""
    rng = np.random.default_rng(42)
    stream = gen_stream(rng, 6)
    boundaries = even_boundaries(2)
    ref_cfg = tiered_config(n_shards=2, compact_interval=1,
                            delta_capacity=512)
    ref = make_sharded(ref_cfg, boundaries)
    want = run_verdicts(ref, stream)
    ref.compact_history()
    ref_maps = [
        canonical_map_rows(
            np.asarray(ref.state.main.main_keys)[s],
            np.asarray(ref.state.main.main_ver)[s],
        )
        for s in range(2)
    ]
    cs = make_sharded(
        tiered_config(n_shards=2, compact_interval=interval,
                      delta_capacity=512),
        boundaries,
    )
    assert run_verdicts(cs, stream) == want, f"interval={interval}"
    cs.compact_history()
    from foundationdb_tpu.ops import delta as D

    _, d_cnt = D.boundary_counts_per_shard(cs.state)
    assert np.asarray(d_cnt).tolist() == [0, 0]
    got_maps = [
        canonical_map_rows(
            np.asarray(cs.state.main.main_keys)[s],
            np.asarray(cs.state.main.main_ver)[s],
        )
        for s in range(2)
    ]
    assert got_maps == ref_maps, (
        f"interval={interval}: per-shard post-compaction maps diverge"
    )


# ---------------------------------------------------------------------------
# Latch / overflow disciplines.


def test_dedup_latch_trips_all_shards_unchanged_and_fallback():
    """More distinct live read ranges than dedup_reads on SOME shard:
    the raw kernel must refuse the whole group (unconverged reduced
    across shards) with EVERY shard's tiers unchanged; the checked host
    path must auto-redispatch the exact kernel and serve decisions
    identical to dedup-off."""
    rng = np.random.default_rng(3)
    boundaries = even_boundaries(2)
    cfg = tiered_config(n_shards=2, dedup_reads=2, compact_interval=0)
    stream = gen_stream(rng, 3)
    batches = [packing.pack_batch(t, v, 0, cfg) for t, v in stream]
    stacked = stack_device_args(batches)

    cs_raw = make_sharded(cfg, boundaries)
    before = jax.tree.map(lambda x: np.asarray(x).copy(), cs_raw.state)
    outs_raw = cs_raw.resolve_group_args(stacked, check_latch=False)
    assert bool(np.asarray(outs_raw.unconverged).all())
    for a, b in zip(
        jax.tree_util.tree_leaves(before),
        jax.tree_util.tree_leaves(cs_raw.state),
    ):
        np.testing.assert_array_equal(a, np.asarray(b))

    cs = make_sharded(cfg, boundaries)
    outs = cs.resolve_group_args(stacked)
    assert not bool(np.asarray(outs.unconverged).any())
    assert cs.metrics.counters.get("exactFallbacks") >= 1
    ref = make_sharded(
        tiered_config(n_shards=2, compact_interval=0), boundaries
    ).resolve_group_args(stacked)
    np.testing.assert_array_equal(
        np.asarray(outs.verdict), np.asarray(ref.verdict)
    )


def test_per_shard_overflow_survives_compaction():
    """Writes aimed at ONE shard overflow only that shard's delta; the
    latched overflow must fold into that shard's main tier across a
    compaction so check_overflow still raises — per-shard overflow is
    never silently lost in the collective accounting."""
    boundaries = [b"\x02"]
    cfg = tiered_config(n_shards=2, delta_capacity=4, compact_interval=0)
    k = lambda i: bytes([i])
    txns = [
        CommitTransaction([], [(k(4 + 2 * i), k(5 + 2 * i))],
                          read_snapshot=50)
        for i in range(8)
    ]  # 16 distinct boundaries, all >= \x02 -> shard 1 only
    cs = make_sharded(cfg, boundaries)
    batch = packing.pack_batch(txns, 100, 0, cfg)
    cs.resolve_group_args(stack_device_args([batch]), check_latch=False)
    ov = np.asarray(cs.state.delta.overflow)
    assert ov.tolist() == [False, True]
    cs.compact_history()
    assert not np.asarray(cs.state.delta.overflow).any()
    with pytest.raises(HistoryOverflowError):
        cs.check_overflow()


def test_sharded_overflow_raises_loudly():
    boundaries = [b"\x02"]
    cfg = tiered_config(n_shards=2, delta_capacity=4, compact_interval=0)
    k = lambda i: bytes([i])
    txns = [
        CommitTransaction([], [(k(4 + 2 * i), k(5 + 2 * i))],
                          read_snapshot=50)
        for i in range(8)
    ]
    cs = make_sharded(cfg, boundaries)
    with pytest.raises(HistoryOverflowError):
        cs.resolve(txns, 100)


# ---------------------------------------------------------------------------
# Group / pipelined dispatch paths + rebase.


def test_sharded_group_path_matches_per_batch():
    """resolve_group_args (one shard_map program for the whole stack)
    must equal the per-batch sharded path batch for batch."""
    rng = np.random.default_rng(7)
    boundaries = even_boundaries(2)
    cfg = tiered_config(n_shards=2, compact_interval=2)
    stream = gen_stream(rng, 6, n_txns=8)
    batches = [packing.pack_batch(t, v, 0, cfg) for t, v in stream]

    seq = make_sharded(cfg, boundaries)
    seq_out = [seq.resolve_args(b.device_args()) for b in batches]

    grp = make_sharded(cfg, boundaries)
    outs = [
        grp.resolve_group_args(stack_device_args(batches[lo:lo + 3]))
        for lo in (0, 3)
    ]
    for i in range(6):
        g, kk = divmod(i, 3)
        np.testing.assert_array_equal(
            np.asarray(outs[g].verdict[kk]), np.asarray(seq_out[i].verdict),
            err_msg=f"verdict batch {i}",
        )
        np.testing.assert_array_equal(
            np.asarray(outs[g].hist_conflict_read[kk]),
            np.asarray(seq_out[i].hist_conflict_read),
            err_msg=f"hist_conflict_read batch {i}",
        )


def test_sharded_pipelined_stream_matches_per_batch():
    """resolve_stream_pipelined on a sharded instance: the staging
    thread's mesh-replicated device_puts must feed the same decisions
    as the per-batch path, chunk by chunk."""
    rng = np.random.default_rng(11)
    boundaries = even_boundaries(2)
    cfg = tiered_config(n_shards=2, compact_interval=2)
    stream = gen_stream(rng, 6, n_txns=8)
    batches = [packing.pack_batch(t, v, 0, cfg) for t, v in stream]
    seq = make_sharded(cfg, boundaries)
    seq_out = [seq.resolve_args(b.device_args()) for b in batches]

    cs = make_sharded(cfg, boundaries)
    outs = cs.resolve_stream_pipelined(batches, chunk=3)
    flat = [
        (g, kk)
        for g in range(len(outs))
        for kk in range(np.asarray(outs[g].verdict).shape[0])
    ]
    assert len(flat) == len(batches)
    for i, (g, kk) in enumerate(flat):
        np.testing.assert_array_equal(
            np.asarray(outs[g].verdict[kk]), np.asarray(seq_out[i].verdict),
            err_msg=f"pipelined batch {i}",
        )
    assert cs.metrics.counters.get("stagedChunks") == 2


def test_sharded_rebase_matches_oracle():
    """The int32 offset rebase must shift every shard's tiers (a
    cross-shard phantom surviving a rebase still conflicts right)."""
    from foundationdb_tpu.models.conflict_set import REBASE_THRESHOLD

    boundaries = [b"\x08"]
    cfg = tiered_config(n_shards=2, window_versions=1 << 33,
                        compact_interval=0)
    k = lambda i: bytes([i])
    v0 = 1000
    far = v0 + REBASE_THRESHOLD + (1 << 21)
    stream = [
        ([CommitTransaction([], [(k(5), k(6))], read_snapshot=v0 - 1),
          CommitTransaction([], [(k(9), k(10))], read_snapshot=v0 - 1)],
         v0),
        ([CommitTransaction([(k(5), k(6))], [(k(9), k(10))],
                            read_snapshot=v0 - 1),
          CommitTransaction([(k(9), k(10))], [(k(11), k(12))],
                            read_snapshot=far - 1)],
         far),
    ]
    dev = make_sharded(cfg, boundaries)
    oracle = MultiResolverOracle(boundaries, window=cfg.window_versions)
    got = run_verdicts(dev, stream)
    assert got == oracle_verdicts(oracle, stream)
    assert dev.metrics.counters.get("rebases") == 1


# ---------------------------------------------------------------------------
# Structural pins: one program per group, no recompile churn.


def test_one_compiled_program_per_group():
    """The sharded dispatch is ONE shard_map program per group: after
    the first (compiling) dispatch, further same-shape groups add zero
    backend compiles and exactly one groupDispatch each — the
    no-host-round-trip pin behind the compile-count ledger metric."""
    from foundationdb_tpu.utils import compile_cache

    compile_cache.instrument()
    rng = np.random.default_rng(13)
    boundaries = even_boundaries(2)
    cfg = tiered_config(n_shards=2, compact_interval=0)
    streams = [gen_stream(rng, 3, base=1000 + 600 * i) for i in range(3)]
    stacks = [
        stack_device_args(
            [packing.pack_batch(t, v, 0, cfg) for t, v in st]
        )
        for st in streams
    ]
    cs = make_sharded(cfg, boundaries)
    cs.resolve_group_args(stacks[0])  # warm (may compile)
    before = compile_cache.stats()["backend_compiles"]
    d0 = cs.metrics.counters.get("groupDispatches")
    for st in stacks[1:]:
        cs.resolve_group_args(st)
    assert compile_cache.stats()["backend_compiles"] == before
    assert cs.metrics.counters.get("groupDispatches") == d0 + 2


def test_sharded_metrics_surface():
    """The fdbtop kernel-panel keys: shard count, worst-shard tier
    occupancy and the measured collective share must flow through
    KernelStageMetrics.qos() on a sharded instance (and exist, zeroed,
    on single-device ones — the REQUIRED_SENSORS contract)."""
    rng = np.random.default_rng(17)
    boundaries = even_boundaries(2)
    cfg = tiered_config(n_shards=2)
    cs = make_sharded(cfg, boundaries)
    for txns, ver in gen_stream(rng, 3):
        cs.resolve(txns, ver)
    cs.check_overflow()
    q = cs.metrics.qos()
    assert q["shards"] == 2
    assert q["worst_shard_main_occupancy"] > 0
    assert 0.0 < q["collective_time_share"] <= 1.0
    single = TpuConflictSet(dataclasses.replace(cfg, n_shards=0))
    q1 = single.metrics.qos()
    assert q1["shards"] == 1
    assert q1["collective_time_share"] == 0.0


def test_config_validation():
    with pytest.raises(ValueError, match="delta_capacity must be positive"):
        KernelConfig(delta_capacity=0)
    with pytest.raises(ValueError, match="delta_capacity must be positive"):
        KernelConfig(delta_capacity=0, n_shards=2)
    with pytest.raises(ValueError, match="n_shards"):
        KernelConfig(delta_capacity=64, n_shards=-1)
    # mesh/boundary mismatches are loud
    cfg = tiered_config(n_shards=2)
    with pytest.raises(ValueError, match="interior"):
        TpuConflictSet(cfg, mesh=cpu_mesh(2), shard_boundaries=[])
    assert len(default_boundaries(4)) == 3


# ---------------------------------------------------------------------------
# The PR-3 ResolutionBalancer conservative-writes audit shape, with the
# sharded kernel inside the sim ensemble.


def test_sharded_soak_seed_passes_with_balancer_audit_shape():
    """api_correctness seed 8: tpu-force and seed % 4 == 0, so the sim
    Resolver runs the MESH-SHARDED tiered kernel inside the fault
    ensemble. The seed must pass every gate — in particular the PR-3
    strict false-abort audit arming rule (single-resolver fault-free
    plans only) must keep tolerating the sharded kernel's
    reference-semantics phantom commits exactly as it tolerates the
    ResolutionBalancer's conservative writes."""
    from foundationdb_tpu.testing.soak import (
        _sharded_mesh_available,
        plan_for_seed,
        run_seed,
    )

    plan = plan_for_seed(8, "api_correctness")
    assert plan.resolver_backend == "tpu-force"  # the sharded-eligible shape
    assert _sharded_mesh_available(2)  # conftest pinned 8 CPU devices
    sig = run_seed(8, spec="api_correctness")
    assert sig[1] > 0  # commits flowed through the sharded kernel

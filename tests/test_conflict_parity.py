"""Kernel-vs-oracle parity: the L1 harness of SURVEY.md §7.2.

Randomized multi-batch workloads through both the TPU kernel
(TpuConflictSet) and the Python semantic oracle; verdicts and
conflicting-key-range reports must match bit-for-bit.
"""

import numpy as np
import pytest

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import TpuConflictSet
from foundationdb_tpu.models.types import CommitTransaction, TransactionResult
from foundationdb_tpu.testing.oracle import ConflictOracle, OracleTxn
from foundationdb_tpu.testing import workloads

# compile-heavy kernel tests: run with -m kernel (fast lane: -m 'not kernel')
pytestmark = pytest.mark.kernel

CFG = KernelConfig(
    max_key_bytes=12,
    max_txns=64,
    max_reads=256,
    max_writes=256,
    history_capacity=1 << 11,
    window_versions=50,
)


def run_parity(seed, wcfg, n_batches, version_step=7, kcfg=CFG):
    rng = np.random.default_rng(seed)
    cs = TpuConflictSet(kcfg)
    oracle = ConflictOracle(window=kcfg.window_versions)
    version = 100
    for b in range(n_batches):
        version += version_step
        txns = workloads.make_batch(rng, wcfg, version, kcfg.window_versions)
        got = cs.resolve(txns, version)
        want = oracle.resolve(
            [
                OracleTxn(
                    t.read_conflict_ranges,
                    t.write_conflict_ranges,
                    t.read_snapshot,
                    t.report_conflicting_keys,
                )
                for t in txns
            ],
            version,
        )
        got_v = [int(v) for v in got.verdicts]
        assert got_v == want.verdicts, (
            f"seed={seed} batch={b}: verdict mismatch\n"
            f"got  {got_v}\nwant {want.verdicts}"
        )
        want_ckr = {
            t: idxs
            for t, idxs in want.conflicting_ranges.items()
            if txns[t].report_conflicting_keys
            and want.verdicts[t] == int(TransactionResult.CONFLICT)
        }
        assert got.conflicting_key_ranges == want_ckr, (
            f"seed={seed} batch={b}: conflicting-range mismatch\n"
            f"got  {got.conflicting_key_ranges}\nwant {want_ckr}"
        )
    cs.check_overflow()


@pytest.mark.parametrize("seed", range(8))
def test_parity_uniform(seed):
    run_parity(seed, workloads.WorkloadConfig(n_txns=24, keyspace=32), n_batches=6)


@pytest.mark.parametrize("seed", range(4))
def test_parity_hot_keys(seed):
    # heavy contention: tiny keyspace, wide ranges
    w = workloads.WorkloadConfig(
        n_txns=20, keyspace=8, point_fraction=0.3, max_read_ranges=2,
        max_write_ranges=2,
    )
    run_parity(seed + 100, w, n_batches=6)


@pytest.mark.parametrize("seed", range(4))
def test_parity_stale_snapshots(seed):
    # exercises tooOld classification and GC interaction
    w = workloads.WorkloadConfig(n_txns=16, keyspace=16, stale_fraction=0.3)
    run_parity(seed + 200, w, n_batches=8, version_step=13)


def test_parity_long_run_with_gc():
    # enough batches that the MVCC window slides and merged history GCs
    w = workloads.WorkloadConfig(n_txns=16, keyspace=24, stale_fraction=0.1)
    run_parity(300, w, n_batches=24, version_step=11)


def test_parity_blind_writes_and_reports():
    w = workloads.WorkloadConfig(
        n_txns=24, keyspace=16, blind_write_fraction=0.4, report_fraction=1.0
    )
    run_parity(500, w, n_batches=6)


def test_intra_batch_chain():
    """A dependency chain: t0 commits, t1 conflicts on t0, t2 commits
    because t1 aborted, t3 conflicts on t2 — exercises fixpoint depth > 2."""
    cs = TpuConflictSet(CFG)
    k = workloads.int_key

    def T(reads=(), writes=(), snap=99):
        return CommitTransaction(
            read_conflict_ranges=[(k(a), k(a) + b"\x00") for a in reads],
            write_conflict_ranges=[(k(a), k(a) + b"\x00") for a in writes],
            read_snapshot=snap,
        )

    txns = [
        T(writes=[1]),
        T(reads=[1], writes=[2]),   # conflicts with t0
        T(reads=[2], writes=[3]),   # t1 aborted -> commits
        T(reads=[3], writes=[4]),   # conflicts with t2
        T(reads=[4], writes=[5]),   # t3 aborted -> commits
    ]
    got = cs.resolve(txns, version=100)
    want = [
        TransactionResult.COMMITTED,
        TransactionResult.CONFLICT,
        TransactionResult.COMMITTED,
        TransactionResult.CONFLICT,
        TransactionResult.COMMITTED,
    ]
    assert got.verdicts == want


def test_scan_fused_path_matches_sequential(rng):
    """resolve_group_args (K batches, one dispatch) must produce exactly
    the oracle's per-batch decisions — the state chains inside the
    scan, across groups."""
    from foundationdb_tpu.config import TEST_CONFIG
    from foundationdb_tpu.utils.packing import pack_batch, stack_device_args

    config = TEST_CONFIG
    w = workloads.WorkloadConfig(n_txns=48, keyspace=300)
    stream = []
    for i in range(6):
        version = (i + 1) * 100
        stream.append((
            workloads.make_batch(rng, w, version, config.window_versions),
            version,
        ))
    oracle = ConflictOracle(window=config.window_versions)
    want = [
        oracle.resolve([
            OracleTxn(t.read_conflict_ranges, t.write_conflict_ranges,
                      t.read_snapshot, t.report_conflicting_keys)
            for t in txns
        ], version).verdicts
        for txns, version in stream
    ]
    fused = TpuConflictSet(config)
    for gi, g in enumerate((stream[:3], stream[3:])):
        outs = fused.resolve_group_args(stack_device_args(
            [pack_batch(txns, v, 0, config) for txns, v in g]
        ))
        for j, (txns, _) in enumerate(g):
            got = np.asarray(outs.verdict[j])[: len(txns)].tolist()
            assert got == want[gi * 3 + j], gi * 3 + j

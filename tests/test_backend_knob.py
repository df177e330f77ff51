"""resolver_backend knob tests: the CPU path beside the TPU path."""

import numpy as np
import pytest

from foundationdb_tpu.cluster.database import ClusterConfig, open_cluster
from foundationdb_tpu.config import TEST_CONFIG
from foundationdb_tpu.models.conflict_set import TpuConflictSet
from foundationdb_tpu.models.conflict_base import (
    CpuConflictSet,
    make_conflict_set,
)
from foundationdb_tpu.testing import workloads


def test_knob_gate_selects_backend():
    from foundationdb_tpu.utils.knobs import SERVER_KNOBS

    # "tpu" auto-routes SMALL configs to the CPU backend (the measured
    # latency-regime threshold, RESOLVER_TPU_MIN_BATCH): TEST_CONFIG's
    # capacity sits far below it
    assert TEST_CONFIG.max_txns < SERVER_KNOBS.RESOLVER_TPU_MIN_BATCH
    assert isinstance(make_conflict_set(TEST_CONFIG, "tpu"), CpuConflictSet)
    assert isinstance(make_conflict_set(TEST_CONFIG), CpuConflictSet)
    # lowering the threshold sends the same config to the device path
    old = SERVER_KNOBS.RESOLVER_TPU_MIN_BATCH
    try:
        SERVER_KNOBS.set("RESOLVER_TPU_MIN_BATCH", 1)
        assert isinstance(make_conflict_set(TEST_CONFIG, "tpu"), TpuConflictSet)
    finally:
        SERVER_KNOBS.set("RESOLVER_TPU_MIN_BATCH", old)
    # "tpu-force" bypasses the threshold outright
    assert isinstance(
        make_conflict_set(TEST_CONFIG, "tpu-force"), TpuConflictSet
    )
    assert isinstance(make_conflict_set(TEST_CONFIG, "cpu"), CpuConflictSet)
    with pytest.raises(ValueError):
        make_conflict_set(TEST_CONFIG, "gpu")


def test_backends_agree_on_random_workload():
    rng = np.random.default_rng(5)
    wcfg = workloads.WorkloadConfig(n_txns=24, keyspace=32, report_fraction=1.0)
    tpu = make_conflict_set(TEST_CONFIG, "tpu-force")
    cpu = make_conflict_set(TEST_CONFIG, "cpu")
    version = 0
    for _ in range(6):
        version += 13
        txns = workloads.make_batch(rng, wcfg, version, TEST_CONFIG.window_versions)
        a = tpu.resolve(txns, version)
        b = cpu.resolve(txns, version)
        assert [int(v) for v in a.verdicts] == [int(v) for v in b.verdicts]
        assert a.conflicting_key_ranges == b.conflicting_key_ranges


def test_cluster_runs_on_cpu_backend():
    sched, cluster, db = open_cluster(
        ClusterConfig(n_resolvers=2, resolver_backend="cpu")
    )

    async def body():
        txn = db.create_transaction()
        txn.set(b"cpu", b"backend")
        await txn.commit()

        t1 = db.create_transaction()
        t2 = db.create_transaction()
        v1 = await t1.get(b"cpu")
        await t2.get(b"cpu")
        t1.set(b"cpu", b"one")
        t2.set(b"cpu", b"two")
        await t1.commit()
        from foundationdb_tpu.cluster.commit_proxy import NotCommitted

        try:
            await t2.commit()
            return v1, "both"
        except NotCommitted:
            return v1, "conflict"

    v1, outcome = sched.run_until(sched.spawn(body()).done)
    assert v1 == b"backend"
    assert outcome == "conflict"
    from foundationdb_tpu.models.conflict_base import CpuConflictSet as C

    assert all(isinstance(r.conflict_set, C) for r in cluster.resolvers)
    cluster.stop()

"""CODE_PROBE coverage: every declared probe must be reachable.

The reference's CI asserts each CODE_PROBE fires somewhere across the
Joshua ensemble (flow/CodeProbe.h + coveragetool). Here: a few ensemble
seeds cover the common rare paths, targeted scenarios drive the rest,
and the test fails if ANY declared probe never fired — so a probe going
dark (dead path or broken randomization) is caught in CI.
"""

import numpy as np
import pytest

from foundationdb_tpu.utils import probes


@pytest.fixture(autouse=True)
def _fresh_probes():
    probes.reset()
    yield


def drive(sched, coro):
    t = sched.spawn(coro, name="drive")
    sched.run_until(t.done)
    return t.done.get()


def test_every_declared_probe_fires():
    from foundationdb_tpu.testing.soak import run_seed

    # -- ensemble seeds: recovery, state txns, conservative writes;
    # seed 29 draws atomic_ops + overload_burst under the r8 draw order
    # (the admission burst sheds at the bounded GRV queue and throttles
    # the budget) ------
    for seed in (3, 5, 29):
        run_seed(seed)

    # -- resolver rare paths --------------------------------------------
    from foundationdb_tpu.config import TEST_CONFIG
    from foundationdb_tpu.models.types import (
        CommitTransaction,
        ResolveTransactionBatchRequest,
    )
    from foundationdb_tpu.resolver import Resolver
    from foundationdb_tpu.runtime.flow import Scheduler

    sched = Scheduler(sim=True)
    # commit_proxy_count=2 so state is never trimmed (proxy 1 never
    # reports in) and total_state_bytes accumulates past the tiny limit
    res = Resolver(sched, TEST_CONFIG, backend="cpu",
                   state_memory_limit=10, commit_proxy_count=2)

    async def resolver_paths():
        await res.resolve(ResolveTransactionBatchRequest(
            prev_version=-1, version=0, last_received_version=-1))
        # state txn big enough to breach the tiny memory limit
        req1 = ResolveTransactionBatchRequest(
            prev_version=0, version=10, last_received_version=0,
            transactions=[CommitTransaction(
                mutations=[("set", b"\xff/big", b"x" * 64)])],
            txn_state_transactions=[0], proxy_id="p0")
        await res.resolve(req1)
        # duplicate of version 10: replayed from outstanding_batches
        dup = await res.resolve(ResolveTransactionBatchRequest(
            prev_version=0, version=10, last_received_version=0,
            transactions=[], proxy_id="p0"))
        assert dup is not None
        # ack version 10, then ask for it again: unknown duplicate, Never
        req2 = ResolveTransactionBatchRequest(
            prev_version=10, version=20, last_received_version=10,
            transactions=[], proxy_id="p0")
        # backpressure check happens at entry (probe fires); raising
        # needed_version first keeps the wait loop from blocking the
        # single-task test world
        res._set_needed_version(10**9)
        await res.resolve(req2)
        gone = await res.resolve(ResolveTransactionBatchRequest(
            prev_version=0, version=10, last_received_version=10,
            transactions=[], proxy_id="p0"))
        assert gone is None
        # tooOld: snapshot below the MVCC floor
        await res.resolve(ResolveTransactionBatchRequest(
            prev_version=20, version=TEST_CONFIG.window_versions + 500,
            last_received_version=20,
            transactions=[CommitTransaction(
                read_conflict_ranges=[(b"a", b"b")], read_snapshot=-5000)],
            proxy_id="p0"))

    drive(sched, resolver_paths())

    # -- coordination rare paths ----------------------------------------
    from foundationdb_tpu.cluster.coordination import (
        CoordinatedState,
        Coordinator,
        QuorumUnreachable,
        StaleGeneration,
    )

    coords = [Coordinator(f"c{i}") for i in range(3)]
    a = CoordinatedState(sched, coords, "a")
    b = CoordinatedState(sched, coords, "b")

    async def coordination_paths():
        await a.read()
        await b.read()
        await b.write("bv")  # commits between a's read and write
        try:
            await a.write("av")  # stale generation (b locked higher)
        except StaleGeneration:
            pass
        try:
            # retry with the adopted higher count: the lock now succeeds
            # but the replies reveal b's commit -> racing writer detected
            await a.write("av2")
        except StaleGeneration:
            pass
        coords[0].kill()
        coords[1].kill()
        try:
            await a.read()
        except (QuorumUnreachable, StaleGeneration):
            pass
        return True

    drive(sched, coordination_paths())

    # -- recovery under quorum loss -------------------------------------
    from foundationdb_tpu.cluster.database import ClusterConfig, open_cluster

    sched2, cluster, db = open_cluster(
        ClusterConfig(n_commit_proxies=1, n_storage=2)
    )

    async def recovery_paths():
        t = db.create_transaction()
        t.set(b"k", b"v")
        await t.commit()
        # lease is won by the CC watch loop; now drop the quorum and fail
        # the proxy: the epoch lock (and lease renewal) must fail loudly
        for _ in range(40):
            await sched2.delay(0.05)
            if cluster.controller.lease is not None:
                break
        cluster.kill_coordinator(0)
        cluster.kill_coordinator(1)
        cluster.commit_proxies[0].failed = RuntimeError("probe kill")
        await sched2.delay(2.0)  # recover() runs -> epoch lock fails
        # revive the quorum: the CC re-wins the lease...
        cluster.revive_coordinator(0)
        cluster.revive_coordinator(1)
        cluster.commit_proxies[0].failed = None
        for _ in range(200):
            await sched2.delay(0.05)
            if cluster.controller.lease is not None:
                break
        # ...then loses the quorum again with the lease HELD: the renewal
        # near expiry fails -> leadership_lost
        cluster.kill_coordinator(0)
        cluster.kill_coordinator(1)
        await sched2.delay(4.0)
        return True

    t = sched2.spawn(recovery_paths(), name="drive")
    sched2.run_until(t.done)
    assert t.done.get()
    cluster.stop()

    # -- min-combine abort across resolver shards -----------------------
    sched3, cluster3, db3 = open_cluster(
        ClusterConfig(n_commit_proxies=1, n_resolvers=2, n_storage=2)
    )

    async def min_combine():
        t = db3.create_transaction()
        # first byte >= 0x80: resolver shard 1 (2-way even split)
        t.set(b"\xf0-right-shard", b"v1")
        await t.commit()
        # stale read of the shard-1 key + a write in shard 0: resolver 0
        # commits locally, resolver 1 conflicts -> min-combine abort
        from foundationdb_tpu.cluster.commit_proxy import NotCommitted

        t2 = db3.create_transaction()
        t2._read_version = 1  # force a stale snapshot
        t2.add_read_conflict_range(b"\xf0-right-shard", b"\xf0-right-shard\x00")
        t2.set(b"aa-left-shard", b"v2")
        try:
            await t2.commit()
        except NotCommitted:
            pass
        return True

    t = sched3.spawn(min_combine(), name="drive")
    sched3.run_until(t.done)
    assert t.done.get()
    cluster3.stop()

    # -- disk stack: torn tail + recovery scan ---------------------------
    import numpy as np

    from foundationdb_tpu.sim.diskqueue import SimDiskQueue

    for s in range(32):  # the tear branch is coin-flipped per crash
        q = SimDiskQueue()
        q.push(b"durable")
        q.commit()
        for i in range(4):
            q.push(b"unsynced%d" % i)
        q.crash(np.random.default_rng(s))

    # -- tlog spill: tiny budget + lagging consumer ----------------------
    from foundationdb_tpu.cluster.tlog import TLog, TLogCommitRequest
    from foundationdb_tpu.utils.knobs import SERVER_KNOBS as _SK

    _old_budget = _SK.TLOG_SPILL_THRESHOLD
    _SK.set("TLOG_SPILL_THRESHOLD", 8)
    try:
        sched_sp = Scheduler(sim=True)
        spill_log = TLog(sched_sp, durable=SimDiskQueue())

        async def spill_drive():
            prev = 0
            for i in range(12):
                v = (i + 1) * 10
                await spill_log.commit(TLogCommitRequest(
                    prev_version=prev, version=v,
                    messages={0: [("set", b"sp%d" % i, b"v")]},
                ))
                prev = v
            entries, _v = await spill_log.peek(0, 0)  # reads from spill
            return len(entries)

        t = sched_sp.spawn(spill_drive())
        sched_sp.run_until(t.done)
        assert t.done.get() == 12
    finally:
        _SK.set("TLOG_SPILL_THRESHOLD", _old_budget)

    sched4, cluster4, db4 = open_cluster(
        ClusterConfig(n_storage=2, n_tlogs=2, n_satellite_logs=1)
    )

    from foundationdb_tpu.cluster.multiregion import RemoteDC

    remote = RemoteDC(sched4, cluster4.tlog, n_storage=1)
    remote.start()

    async def disk_and_rates():
        for i in range(4):
            txn = db4.create_transaction()
            txn.set(b"dq%d" % i, b"v")
            await txn.commit()
        cluster4.crash_reboot_tlog(1, np.random.default_rng(0))
        await remote.wait_caught_up()
        # wedge the router and commit past it: the failover must pull
        # the acked suffix back off the satellite log
        # (multiregion.satellite_recovery)
        remote.router._task.cancel()
        remote.router._task = None
        for i in range(2):
            txn = db4.create_transaction()
            txn.set(b"sat%d" % i, b"v")
            await txn.commit()
        await remote.failover()
        # ratekeeper law: tighten + slow storage
        rk = cluster4.ratekeeper
        rk.lag_target, rk.lag_limit, rk.interval = 30_000, 200_000, 0.05
        cluster4.storage_servers[0].slowdown = 0.1
        for i in range(8):
            txn = db4.create_transaction(tag="batch")
            await txn.get_read_version()
        # auto tag throttling: a dominant tag during stressed intervals
        # earns a busyness-derived quota (ratekeeper.auto_tag_throttled)
        for _ in range(8):
            for _ in range(50):
                rk.note_tag_admission("batch")
            await sched4.delay(rk.interval)
            if rk.auto_tag_quotas:
                break
        assert rk.auto_tag_quotas, "auto tag throttle never engaged"
        cluster4.storage_servers[0].slowdown = 0.0
        # failure monitor: a SILENT kill must be detected by the ping
        # loop (failmon.detected_by_ping), and a revived process must be
        # marked live by a ping again (failmon.recovered)
        cluster4.kill_storage_silent(1)
        for _ in range(40):
            await sched4.delay(0.05)
            if cluster4.failure_monitor.is_failed("storage1"):
                break
        assert cluster4.failure_monitor.is_failed("storage1")
        cluster4.storage_servers[1].start()  # back from the dead
        for _ in range(40):
            await sched4.delay(0.05)
            if not cluster4.failure_monitor.is_failed("storage1"):
                break
        assert not cluster4.failure_monitor.is_failed("storage1")
        return True

    cluster4.ratekeeper.set_tag_quota("batch", 3.0)
    t = sched4.spawn(disk_and_rates(), name="drive")
    sched4.run_until(t.done)
    assert t.done.get()
    cluster4.stop()

    # -- dynamic-knob quorum: write / race / restore ----------------------
    from foundationdb_tpu.cluster.config_db import (
        CONF_PREFIX,
        PaxosConfigStore,
        restore_broadcast,
        set_knob,
    )

    sched5, cluster5, db5 = open_cluster(ClusterConfig(n_storage=2))
    wa = PaxosConfigStore(sched5, cluster5.config_nodes, "probe-a")
    wb = PaxosConfigStore(sched5, cluster5.config_nodes, "probe-b")

    async def knob_paths():
        cluster5.kill_coordinator(0)  # minority: writes must still land
        ta = sched5.spawn(wa.set("KA", b"1"))  # race at the RMW yield
        tb = sched5.spawn(wb.set("KB", b"2"))
        await ta.done
        await tb.done
        # MAJORITY down mid-write: the store must back off through the
        # transient QuorumUnreachable (config.quorum_write_retried) and
        # land once the quorum returns — the round-5 crash shape
        cluster5.kill_coordinator(1)
        tc = sched5.spawn(wa.set("KD", b"4"))
        await sched5.delay(0.3)
        cluster5.revive_coordinator(1)
        await tc.done
        cluster5.revive_coordinator(0)
        await set_knob(db5, "KC", 3)
        txn = db5.create_transaction()
        txn.clear_range(CONF_PREFIX, CONF_PREFIX + b"\xff")
        await txn.commit()
        restored = await restore_broadcast(db5)
        assert restored["KC"] == 3
        return True

    t = sched5.spawn(knob_paths(), name="drive")
    sched5.run_until(t.done)
    assert t.done.get()
    cluster5.stop()

    # -- TSS divergence: corrupt the mirror, sampled read flags it --------
    from foundationdb_tpu.cluster.tss import TSS_SAMPLE_EVERY

    sched_t, cluster_t, db_t = open_cluster(
        ClusterConfig(n_commit_proxies=1, n_storage=2, n_tss=1)
    )

    async def tss_paths():
        txn = db_t.create_transaction()
        txn.set(b"div", b"truth")
        await txn.commit()
        await sched_t.delay(0.2)  # mirror converges
        tss = cluster_t.tss_servers[0]
        for hist in tss._hist.values():
            hist[:] = [(v, b"LIES") for v, _val in hist]
        txn = db_t.create_transaction()
        for _ in range(4 * TSS_SAMPLE_EVERY):
            assert await txn.get(b"div") == b"truth"
        await sched_t.delay(0.2)  # comparisons drain
        return db_t.tss.mismatches

    t = sched_t.spawn(tss_paths(), name="drive")
    sched_t.run_until(t.done)
    assert t.done.get() >= 1
    cluster_t.stop()

    # -- QueueModel load balancing: backup request / shun -----------------
    sched6, cluster6, db6 = open_cluster(
        ClusterConfig(n_storage=2, replication_factor=2)
    )

    async def lb_paths():
        txn = db6.create_transaction()
        txn.set(b"lb", b"v")
        await txn.commit()
        cluster6.storage_servers[0].read_slowdown = 0.05
        for _ in range(10):
            t = db6.create_transaction()
            assert await t.get(b"lb") == b"v"
        # let the duplicated slow request COMPLETE so its latency lands
        # in the model (0.2s < STALE_AFTER: no decay) — the shun probe
        # requires a genuinely slow estimate, not a cold-start artifact
        await sched6.delay(0.2)
        for _ in range(10):
            t = db6.create_transaction()
            assert await t.get(b"lb") == b"v"
        return True

    t = sched6.spawn(lb_paths(), name="drive")
    sched6.run_until(t.done)
    assert t.done.get()
    cluster6.stop()

    # -- blob granules: flush / resnapshot / split / time travel ----------
    from foundationdb_tpu.cluster.backup import BackupContainer
    from foundationdb_tpu.cluster.blob_granules import BlobManager, BlobWorker

    sched7, cluster7, db7 = open_cluster(ClusterConfig(n_storage=2))
    bw = BlobWorker(sched7, cluster7.tlog, BackupContainer())
    bw.start()
    bmgr = BlobManager(db7, [bw])

    async def blob_paths():
        await bmgr.blobbify(b"", b"", {}, 0)
        txn = db7.create_transaction()
        txn.set(b"bg-first", b"1")
        await txn.commit()
        v_past = cluster7.tlog.version.get()
        await sched7.delay(0.05)
        val = b"z" * 512
        for i in range(160):  # crosses flush, resnapshot AND split bars
            txn = db7.create_transaction()
            txn.set(b"bg%04d" % i, val)
            await txn.commit()
        await sched7.delay(0.3)
        past = bmgr.read(b"", b"", v_past)
        assert past.get(b"bg-first") == b"1" and b"bg0000" not in past
        return True

    t = sched7.spawn(blob_paths(), name="drive")
    sched7.run_until(t.done)
    assert t.done.get()
    bw.stop()
    cluster7.stop()

    # -- TaskBucket: claim race / lease expiry / dependency release -------
    from foundationdb_tpu.layers.taskbucket import TaskBucket

    sched9, cluster9, db9 = open_cluster(ClusterConfig())
    tb = TaskBucket(db9)

    async def taskbucket_paths():
        for i in range(3):
            await tb.add(b"t%d" % i, {})
        await tb.add(b"dep", {}, after=b"t0")
        # two claimers race the same head task -> one commits, the
        # other's claim conflicts and retries onto the next task
        c1 = sched9.spawn(tb.get_one())
        c2 = sched9.spawn(tb.get_one())
        t1 = await c1.done
        t2 = await c2.done
        assert t1.key != t2.key
        await tb.finish(t1)  # t0 finish releases the parked dependent
        await sched9.delay(TaskBucket.LEASE + 0.1)
        await tb.check_timeouts()  # t2's lease expired: requeued
        return True

    t = sched9.spawn(taskbucket_paths(), name="drive")
    sched9.run_until(t.done)
    assert t.done.get()

    # -- BackupWorker displacement (per-epoch handoff) --------------------
    from foundationdb_tpu.cluster.backup import BackupContainer
    from foundationdb_tpu.cluster.backup_worker import BackupWorker

    bw_cont = BackupContainer()
    bwk = BackupWorker(
        sched9, cluster9.tlog, bw_cont, epoch=cluster9.tlog.epoch
    )
    bwk.start()

    async def displace_paths():
        txn = db9.create_transaction()
        txn.set(b"bw-probe", b"1")
        await txn.commit()
        await sched9.delay(0.1)
        # recovery-style epoch bump: the worker drains and hands off
        cluster9.tlog.lock(
            cluster9.tlog.epoch + 1, cluster9.tlog.version.get() + 1000
        )
        await bwk.displaced.future
        return True

    t = sched9.spawn(displace_paths(), name="drive")
    sched9.run_until(t.done)
    assert t.done.get()
    bwk.stop()
    cluster9.stop()

    # -- api workload: an unknown-result commit resolved by marker --------
    # (workload.api_unknown_resolved: a commit the client saw as
    # commit_unknown_result but that really landed must be resolved to
    # COMMITTED by its versionstamped marker)
    from test_api_workload import run_api

    api = run_api(seed=11, sabotage_first_commit=True)
    assert api.stats["unknown_resolved"] >= 1

    # -- slow-task detection ----------------------------------------------
    import time as _t

    sched8 = Scheduler(sim=True)

    async def _blocker():
        _t.sleep(Scheduler.SLOW_TASK_THRESHOLD + 0.01)

    sched8.run_until(sched8.spawn(_blocker(), name="probe-blocker").done)
    assert sched8.slow_tasks

    # -- telemetry probes (ISSUE 5) ---------------------------------------
    # latency band overflow: a sample past every threshold hits the inf
    # bucket; counter flush: one trace_counters call; span-chain gate:
    # the checker over a deliberately broken chain must trip
    from foundationdb_tpu.utils import commit_debug as cdbg
    from foundationdb_tpu.utils.metrics import (
        CounterCollection,
        LatencyBands,
    )
    from foundationdb_tpu.utils.trace import TraceLog, trace_counters

    LatencyBands("probe", bands=(0.001,)).add(9.0)
    trace_counters(
        TraceLog(), "ProbeMetrics", "r0", CounterCollection("m", ["a"])
    )
    broken = cdbg.check_chains(cdbg.TraceIndex([
        {"Type": "CommitDebug", "ID": "tp", "Time": 0.0,
         "Location": cdbg.COMMIT_BEFORE},
        {"Type": "CommitDebug", "ID": "tp", "Time": 0.1,
         "Location": cdbg.COMMIT_AFTER},
    ]))
    assert broken  # committed txn never attached to a batch

    # -- perf-ledger probes (ISSUE 10) ------------------------------------
    # regression gate: a candidate whose structural metric doubled
    # against its own baseline must trip the comparator; compile-cache
    # miss: the monitoring listener's miss event path (the same hook
    # jax.monitoring drives on a persistent-cache miss)
    from foundationdb_tpu.utils import compile_cache, perf

    base = perf.make_record(
        "probe_drive",
        {"rows": perf.metric(100, "rows", "lower", tier="structural")},
    )
    cand = perf.make_record(
        "probe_drive",
        {"rows": perf.metric(200, "rows", "lower", tier="structural")},
    )
    rep = perf.compare(cand, [base], tier="structural")
    assert rep["regressions"] == ["rows"]
    compile_cache._on_event(compile_cache._MISS_EVENT)

    # -- range-path probes (ISSUE 14) -------------------------------------
    # the sorted-endpoint sweep dispatching and the pressure spill
    # folding delta into MAIN (delta sized so the conservative bound,
    # 2 x 8 write rows of the (8, 8, 8) rung these batches take,
    # trips on the second batch)
    import dataclasses as _dc

    from foundationdb_tpu.config import KernelConfig
    from foundationdb_tpu.models.conflict_set import TpuConflictSet
    from foundationdb_tpu.models.types import CommitTransaction

    sweep_cfg = _dc.replace(
        KernelConfig(
            max_key_bytes=8, max_txns=8, max_reads=16, max_writes=16,
            history_capacity=256, window_versions=1000,
        ),
        delta_capacity=24, compact_interval=0,
        range_sweep=True, delta_spill=True,
    )
    cs = TpuConflictSet(sweep_cfg)
    for i in range(3):
        cs.resolve(
            [
                CommitTransaction(
                    read_conflict_ranges=[(bytes([0, j]), bytes([0, j + 40]))],
                    write_conflict_ranges=[
                        (bytes([1, 8 * i + j]), bytes([1, 8 * i + j, 1]))
                    ],
                    read_snapshot=900,
                )
                for j in range(4)
            ],
            1000 + 100 * i,
        )

    # -- ycsb_d soak twin (ISSUE 15) --------------------------------------
    # the read-latest check fires on most rounds; the frontier-persisted
    # probe needs a read landing >= 5 rounds behind the frontier, which
    # the exponential access law makes common per seed
    run_seed(1, spec="ycsb_d")

    # -- elasticity trigger (ISSUE 15) ------------------------------------
    # a resolver_busy binding streak past the threshold, on a healthy
    # (non-stale) feed, flags the elastic recruit walk
    from foundationdb_tpu.cluster.multiprocess import ClusterControllerRole

    ctrl = ClusterControllerRole(
        {"resolvers": 1, "elastic": True, "elastic_streak": 2}
    )
    ctrl._needs_recovery = False
    ctrl._rk_qos = {
        "binding_streak": {"name": "resolver_busy", "intervals": 5},
        "budget_stale": False,
    }
    ctrl._elastic_check()
    assert ctrl.elastic_recruits == 1

    # ...and the OFF direction (ISSUE 19): the recruit left resolvers
    # above the declared baseline; a "workload"-binding streak (nothing
    # structural binds) past the scale-down gate retires it
    ctrl._needs_recovery = False
    ctrl._rk_qos = {
        "binding_streak": {"name": "workload",
                           "intervals": ctrl.elastic_scale_down_streak},
        "budget_stale": False,
    }
    ctrl._elastic_check()
    assert ctrl.elastic_scale_downs == 1

    # -- autotune probes (ISSUE 15) ---------------------------------------
    # cache_hit: the second sweep over the same ledger resumes every
    # trial; roofline_stop: a trial achieving the (tiny) target frac of
    # the bytes-bound ceiling stops the search early
    import tempfile as _tf

    from foundationdb_tpu.utils import autotune

    def _trial(knobs):
        rec = perf.make_record(
            "probe_drive",
            {"txn_s": perf.metric(1000.0, "txn/s", "higher",
                                  tier="structural")},
            knobs=knobs,
            fingerprint={
                "backend": "tpu", "device_kind": "TPU v5e",
                "device_count": 1, "jax_version": None,
                "jaxlib_version": None, "python_version": None,
                "machine": None,
            },
            git_sha="t", timestamp=0.0,
        )
        rec["extra"] = {"hlo_cost": {"bytes_accessed": 8.19e8}}
        return rec

    with _tf.TemporaryDirectory() as td:
        ledger = f"{td}/search.jsonl"
        space = autotune.SearchSpace({"fuse": (8, 16)})
        autotune.run_search("probe", space, _trial,
                            objective_metric="txn_s", ledger=ledger)
        autotune.run_search("probe", space, _trial,
                            objective_metric="txn_s", ledger=ledger)
        rep = autotune.run_search(
            "probe-roofline", space, _trial, objective_metric="txn_s",
            ledger=ledger, roofline_txns_per_dispatch=1024,
            roofline_frac=9e-4,
        )
        assert rep.stopped == "roofline"

    # -- sampling probes (ISSUE 20) ---------------------------------------
    from foundationdb_tpu.cluster import sampling

    # byte_sample_gc: factor=1/overhead=0 puts p >= 1 on every write, so
    # a tiny capacity overflows and the deterministic halving GC runs
    bs = sampling.ByteSample(seed=7, factor=1, overhead=0, capacity=8)
    for i in range(64):
        bs.note_write(b"gc/%03d" % i, b"v" * 64)
    assert bs.gc_rounds >= 1

    # tag_counter_rollover: a 5th distinct tag against a 4-slot table
    # evicts the cold half first
    vt = [0.0]
    tc = sampling.TagCounter(capacity=4, clock=lambda: vt[0])
    for i in range(5):
        tc.note(f"tag{i}", 100)
        vt[0] += 0.1
    assert tc.rollovers >= 1

    # hot_range_attributed: a dominant rolled-up tag names a hotspot
    attr = sampling.attribute_hotspot({"cluster": {
        "busiest_tags": [
            {"tag": "tenant0", "bytes_per_s": 9e4, "frac": 0.8}
        ],
        "hot_ranges": [],
    }})
    assert attr["attributed"]

    assert probes.missed() == [], (
        f"declared CODE_PROBEs never fired: {probes.missed()}\n"
        f"fired: { {k: v for k, v in probes.snapshot().items() if v} }"
    )

    # -- the canonical manifest pin (flowcheck probe accounting) ----------
    # every probe this run touched must be statically declared, i.e.
    # present in analysis/probe_manifest.json — a name outside it is
    # invisible to the coveragetool-style ledger
    from foundationdb_tpu.analysis.manifest import load_manifest

    manifest = set(load_manifest())
    runtime_names = set(probes.snapshot())
    assert runtime_names <= manifest, (
        f"probes fired at runtime but missing from the static manifest "
        f"(run `python -m foundationdb_tpu.analysis --write-manifest`): "
        f"{sorted(runtime_names - manifest)}"
    )

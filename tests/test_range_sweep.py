"""Device-native range-overlap resolve + spill-and-compact (ISSUE 14).

Two escape hatches closed, each pinned both directions:

* `range_sweep` — the tiered kernel's main-tier probe as ONE per-group
  sorted-endpoint sweep (ops/delta.sweep_read_ranks) instead of
  per-read binary searches with a bounded probe window. Decision
  parity vs the probe path, the oracle (testing/oracle.ConflictOracle,
  served by CpuConflictSet) and the multi-resolver oracle on range-heavy / mixed / window-edge streams,
  single-device, sharded (n=2) and through the pipelined stream.
* `delta_spill` — delta-capacity pressure folds delta into MAIN (the
  compaction program, dispatched asynchronously) instead of
  latch-and-raise: a stream sized past delta_capacity completes on
  device with ZERO host exact-kernel re-dispatches (counter pinned),
  mid-stream with the staging thread active, during a sharded group,
  and across a rebase; decisions are invariant vs compact_interval.

Runs in the kernel parity lane (8-device CPU mesh, -m kernel).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import TpuConflictSet
from foundationdb_tpu.models.conflict_base import (
    CpuConflictSet,
    HistoryOverflowError,
)
from foundationdb_tpu.models.types import CommitTransaction
from foundationdb_tpu.utils import packing
from foundationdb_tpu.utils.packing import stack_device_args

pytestmark = pytest.mark.kernel


def sweep_config(**kw):
    d = dict(
        max_key_bytes=8,
        max_txns=16,
        max_reads=32,
        max_writes=32,
        history_capacity=512,
        window_versions=1000,
        delta_capacity=256,
        compact_interval=2,
        range_sweep=True,
    )
    d.update(kw)
    return KernelConfig(**d)


def probe_config(cfg, **kw):
    return dataclasses.replace(cfg, range_sweep=False, **kw)


def oracle_verdicts(cfg, stream):
    """The reference verdicts per batch: testing/oracle.ConflictOracle,
    through the CPU backend that serves it (CpuConflictSet)."""
    oracle = CpuConflictSet(cfg)
    return [[int(v) for v in oracle.resolve(t, ver).verdicts]
            for t, ver in stream]


def ikey(v, width=4):
    return int(v).to_bytes(width, "big")


def range_txn(rng, *, snap_lo, snap_hi, keyspace=1 << 20, max_span=4000,
              blind_prob=0.1, report_prob=0.5):
    """Range-heavy shape: wide read scans vs point-ish writes (the
    YCSB-E / BASELINE config-3 regime, the profile the router exiled)."""
    def scan():
        b = int(rng.integers(0, keyspace))
        return (ikey(b), ikey(b + int(rng.integers(1, max_span))))

    def point():
        b = int(rng.integers(0, keyspace))
        return (ikey(b), ikey(b + int(rng.integers(1, 8))))

    reads = [] if rng.random() < blind_prob else [
        scan() for _ in range(1 + int(rng.integers(0, 2)))
    ]
    return CommitTransaction(
        read_conflict_ranges=reads,
        write_conflict_ranges=[
            point() for _ in range(1 + int(rng.integers(0, 2)))
        ],
        read_snapshot=int(rng.integers(snap_lo, snap_hi)),
        report_conflicting_keys=bool(rng.random() < report_prob),
    )


def mixed_txn(rng, **kw):
    """Mixed shape: scans, points and duplicates interleaved."""
    if rng.random() < 0.5:
        return range_txn(rng, max_span=64, **kw)
    return range_txn(rng, max_span=2, **kw)


def gen_stream(rng, n_batches, txn_fn=range_txn, *, base=1000, step=100,
               n_txns=10):
    out = []
    for i in range(n_batches):
        version = base + (i + 1) * step
        out.append((
            [
                txn_fn(rng, snap_lo=max(0, base - 2 * step),
                       snap_hi=version)
                for _ in range(n_txns)
            ],
            version,
        ))
    return out


def run_resolve(cs, stream):
    return [cs.resolve(txns, v) for txns, v in stream]


def assert_results_match(a, b, label=""):
    for i, (ra, rb) in enumerate(zip(a, b)):
        assert ra.verdicts == rb.verdicts, f"{label} verdicts batch {i}"
        assert ra.conflicting_key_ranges == rb.conflicting_key_ranges, (
            f"{label} conflicting ranges batch {i}"
        )


# ---------------------------------------------------------------------------
# sweep parity


@pytest.mark.parametrize("seed", range(4))
def test_sweep_matches_probe_and_classic_range_heavy(seed):
    rng = np.random.default_rng(seed)
    cfg = sweep_config()
    stream = gen_stream(rng, 8)
    res_s = run_resolve(TpuConflictSet(cfg), stream)
    res_p = run_resolve(TpuConflictSet(probe_config(cfg)), stream)
    res_o = run_resolve(CpuConflictSet(cfg), stream)
    assert_results_match(res_s, res_p, "sweep vs probe")
    assert_results_match(res_s, res_o, "sweep vs oracle")


@pytest.mark.parametrize("seed", range(3))
def test_sweep_matches_cpu_oracle_mixed(seed):
    rng = np.random.default_rng(50 + seed)
    cfg = sweep_config()
    stream = gen_stream(rng, 6, mixed_txn)
    res_s = run_resolve(TpuConflictSet(cfg), stream)
    res_o = run_resolve(CpuConflictSet(cfg), stream)
    assert_results_match(res_s, res_o, "sweep vs cpu oracle")
    # the sweep path actually ran (not silently the probe path)
    # — re-run on a fresh instance to read its counters
    cs = TpuConflictSet(cfg)
    run_resolve(cs, stream)
    assert cs.metrics.counters.get("sweepGroups") == len(stream)


def test_sweep_window_edge_versions():
    """Snapshots exactly at / beside the MVCC floor through the sweep
    probe: the too-old and GC boundaries must match the probe path."""
    cfg = sweep_config(window_versions=100)
    k = lambda i: bytes([i])
    streams = []
    for snap in (99, 100, 101, 199, 200):
        streams.append((
            [
                CommitTransaction([(k(1), k(9))], [(k(1), k(2))],
                                  read_snapshot=snap),
                CommitTransaction([], [(k(3), k(4))], read_snapshot=snap),
            ],
            200 + len(streams),
        ))
    res_s = run_resolve(TpuConflictSet(cfg), streams)
    res_p = run_resolve(TpuConflictSet(probe_config(cfg)), streams)
    assert_results_match(res_s, res_p, "sweep window edge")


def test_sweep_scan_straddles_many_boundaries():
    """A scan covering MANY main-tier boundaries (the regime the probe
    path's 4-wide window falls back to a second binary search for) must
    be exact through the sweep ranks."""
    cfg = sweep_config(compact_interval=1)  # every batch folds to main
    writers = [
        CommitTransaction([], [(ikey(10 * i), ikey(10 * i + 2))],
                          read_snapshot=900)
        for i in range(12)
    ]
    stream = [
        (writers, 1100),
        # one scan over ALL the boundaries, one beside them; stale
        # snapshots so the covered scan must conflict
        ([
            CommitTransaction([(ikey(0), ikey(500))], [(ikey(600), ikey(601))],
                              read_snapshot=1000),
            CommitTransaction([(ikey(700), ikey(900))],
                              [(ikey(910), ikey(911))], read_snapshot=1000),
        ], 1200),
    ]
    res_s = run_resolve(TpuConflictSet(cfg), stream)
    res_o = run_resolve(CpuConflictSet(cfg), stream)
    assert_results_match(res_s, res_o, "boundary straddle")
    assert res_s[1].verdicts[0].name == "CONFLICT"
    assert res_s[1].verdicts[1].name == "COMMITTED"


@pytest.mark.parametrize("seed", range(2))
def test_sweep_sharded_matches_multi_resolver_oracle(seed):
    from foundationdb_tpu.parallel.mesh import cpu_mesh
    from foundationdb_tpu.testing.oracle import MultiResolverOracle, OracleTxn

    rng = np.random.default_rng(70 + seed)
    cfg = sweep_config(n_shards=2)
    boundaries = [bytes([8])]  # interior split of the 20-bit keyspace
    stream = gen_stream(rng, 6)
    oracle = MultiResolverOracle(boundaries, window=cfg.window_versions)
    want = [
        oracle.resolve(
            [
                OracleTxn(t.read_conflict_ranges, t.write_conflict_ranges,
                          t.read_snapshot, t.report_conflicting_keys)
                for t in txns
            ],
            v,
        )
        for txns, v in stream
    ]
    cs = TpuConflictSet(cfg, mesh=cpu_mesh(2), shard_boundaries=boundaries)
    for i, (txns, v) in enumerate(stream):
        got = cs.resolve(txns, v)
        assert [int(x) for x in got.verdicts] == list(want[i].verdicts), (
            f"sharded sweep batch {i}"
        )
    assert cs.metrics.counters.get("sweepGroups") == len(stream)


def test_sweep_pipelined_stream_matches_sequential():
    rng = np.random.default_rng(9)
    cfg = sweep_config()
    stream = gen_stream(rng, 8, n_txns=8)
    batches = [packing.pack_batch(t, v, 0, cfg) for t, v in stream]
    want = oracle_verdicts(cfg, stream)

    cs = TpuConflictSet(cfg)
    outs = cs.resolve_stream_pipelined(batches, chunk=3)
    flat = [
        (g, k)
        for g in range(len(outs))
        for k in range(np.asarray(outs[g].verdict).shape[0])
    ]
    assert len(flat) == len(batches)
    for i, (g, k) in enumerate(flat):
        got = np.asarray(outs[g].verdict[k])[: len(want[i])].tolist()
        assert got == want[i], f"pipelined sweep batch {i}"


def test_sweep_excludes_dedup():
    with pytest.raises(ValueError, match="range_sweep and dedup_reads"):
        sweep_config(dedup_reads=8)
    # every config is tiered: an unset delta tier is sized by the rule,
    # an empty one is refused
    for knob in ("range_sweep", "delta_spill"):
        assert KernelConfig(**{knob: True}).delta_capacity == 2 * 4096 * 8
        with pytest.raises(ValueError, match="delta_capacity"):
            KernelConfig(**{knob: True}, delta_capacity=0)


# ---------------------------------------------------------------------------
# spill-and-compact


def spill_config(**kw):
    # delta holds ~1.5 batches' conservative bound (2*32 rows/batch), so
    # an 8-batch stream is sized well past delta_capacity
    d = dict(delta_capacity=96, compact_interval=0, delta_spill=True)
    d.update(kw)
    return sweep_config(**d)


def test_spill_stream_completes_with_zero_exact_fallbacks():
    """THE acceptance pin: a stream sized past delta_capacity completes
    on device with spill configured — no HistoryOverflowError, no host
    exact-kernel re-dispatch (counter pinned at zero) — and decisions
    match a delta tier big enough to never spill."""
    rng = np.random.default_rng(21)
    cfg = spill_config()
    stream = gen_stream(rng, 8)
    cs = TpuConflictSet(cfg)
    res = run_resolve(cs, stream)
    cs.check_overflow()  # no raise
    c = cs.metrics.counters
    assert c.get("spills") > 0, "stream was sized to spill"
    assert c.get("exactFallbacks") == 0
    assert c.get("latchTrips") == 0
    assert c.get("overflowRaised") == 0

    big = TpuConflictSet(
        dataclasses.replace(cfg, delta_capacity=4096, delta_spill=False)
    )
    assert_results_match(res, run_resolve(big, stream), "spill vs big delta")

    # the OFF direction: same stream, same capacity, spill off -> raises
    off = TpuConflictSet(dataclasses.replace(cfg, delta_spill=False))
    with pytest.raises(HistoryOverflowError):
        for txns, v in stream:
            off.resolve(txns, v)
        off.check_overflow()


def dup_txn(rng, *, snap_lo, snap_hi, **_kw):
    """Overlapping-write shape: every batch writes the SAME 8 keys, so
    the delta tier's REAL live boundary count stays ~constant while the
    conservative 2*max_writes-per-batch bound grows linearly."""
    k = int(rng.integers(0, 8)) * 16
    return CommitTransaction(
        read_conflict_ranges=[(ikey(k), ikey(k + 2))],
        write_conflict_ranges=[(ikey(k), ikey(k + 2)),
                               (ikey(k + 4), ikey(k + 6))],
        read_snapshot=int(rng.integers(snap_lo, snap_hi)),
    )


def test_spill_bound_anchors_to_live_occupancy(monkeypatch):
    """ISSUE 15 (ROADMAP PR-14 headroom (b)): the overflow-check sync's
    live boundary count re-anchors the host-side spill bound, so an
    overlapping-write stream spills strictly FEWER times than the
    conservative 2*max_writes accounting would — with decisions
    unchanged vs a never-spilling reference. The old accounting is
    replayed arithmetically here (that's all it was: host arithmetic)
    as the pinned worse-case."""
    from foundationdb_tpu.models import conflict_set as cs_mod

    monkeypatch.setattr(cs_mod, "OVERFLOW_CHECK_INTERVAL", 4)
    rng = np.random.default_rng(7)
    # capacity holds the REAL occupancy (~32 live rows) plus one
    # anchor interval's conservative accrual (4 * 2*max_writes = 256),
    # but NOT the unanchored linear accrual — exactly the regime the
    # measured count fixes
    cfg = spill_config(delta_capacity=320)
    n_batches = 16
    stream = gen_stream(rng, n_batches, dup_txn)
    cs = TpuConflictSet(cfg)
    res = run_resolve(cs, stream)
    c = cs.metrics.counters
    spills = c.get("spills")
    assert c.get("spillBoundAnchors") > 0, (
        "the overflow-check sync never tightened the bound"
    )
    # the conservative accounting this PR replaces, replayed exactly:
    # += 2*max_writes per batch, spill-and-reset when the next batch
    # could overflow
    bound = conservative_spills = 0
    for _ in range(n_batches):
        add = 2 * cfg.max_writes
        if bound + add > cfg.delta_capacity:
            conservative_spills += 1
            bound = 0
        bound += add
    assert conservative_spills >= 2 * max(1, spills), (
        f"tightened bound should spill ~2x less: measured {spills}, "
        f"conservative {conservative_spills}"
    )
    assert c.get("exactFallbacks") == 0
    assert c.get("overflowRaised") == 0

    ref = TpuConflictSet(
        dataclasses.replace(cfg, delta_capacity=4096, delta_spill=False)
    )
    assert_results_match(res, run_resolve(ref, stream),
                         "anchored spill vs big delta")


def test_spill_bound_anchor_never_loosens():
    """The re-anchor is min(bound, live): a live count ABOVE the
    accrued bound (impossible by construction, but the invariant is
    what keeps spill decisions conservative) must never raise it."""
    cfg = spill_config()
    cs = TpuConflictSet(cfg)
    cs._spill_bound_rows = 10
    cs._re_anchor_spill_bound(50.0)
    assert cs._spill_bound_rows == 10
    cs._re_anchor_spill_bound(3.0)
    assert cs._spill_bound_rows == 3


@pytest.mark.parametrize("interval", [0, 1, 4])
def test_spill_decisions_invariant_vs_compact_interval(interval):
    """Pressure spills interleave with (or replace) cadence compaction;
    decisions must not depend on either schedule."""
    rng = np.random.default_rng(33)
    stream = gen_stream(rng, 8, mixed_txn)
    res = run_resolve(
        TpuConflictSet(spill_config(compact_interval=interval)), stream
    )
    ref = run_resolve(
        TpuConflictSet(sweep_config(delta_capacity=4096, compact_interval=0)),
        stream,
    )
    assert_results_match(res, ref, f"spill interval={interval}")


def test_spill_mid_stream_with_staging_thread():
    """Overflow pressure mid-stream with the pipelined staging thread
    active: the spill compaction dispatches between chunk dispatches on
    the compute thread, the staging thread keeps feeding, nothing
    raises, and decisions match the oracle's."""
    import threading

    rng = np.random.default_rng(41)
    cfg = spill_config()
    stream = gen_stream(rng, 10, n_txns=8)
    batches = [packing.pack_batch(t, v, 0, cfg) for t, v in stream]
    cs = TpuConflictSet(cfg)
    outs = cs.resolve_stream_pipelined(batches, chunk=2)
    assert not any(
        t.name == "resolver-staging" for t in threading.enumerate()
    )
    assert cs.metrics.counters.get("spills") > 0
    assert cs.metrics.counters.get("exactFallbacks") == 0
    cs.check_overflow()

    want = oracle_verdicts(cfg, stream)
    vs = np.concatenate(
        [np.asarray(o.verdict).reshape(-1, cfg.max_txns) for o in outs]
    )
    for i in range(len(batches)):
        assert vs[i][: len(want[i])].tolist() == want[i], (
            f"mid-stream spill batch {i}"
        )


def test_spill_during_sharded_group():
    """Per-shard delta tiers spill independently under the conservative
    host bound; a sharded group stream past delta_capacity completes
    with zero fallbacks and oracle-identical decisions."""
    from foundationdb_tpu.parallel.mesh import cpu_mesh
    from foundationdb_tpu.testing.oracle import MultiResolverOracle, OracleTxn

    rng = np.random.default_rng(55)
    cfg = spill_config(n_shards=2)
    boundaries = [bytes([8])]
    stream = gen_stream(rng, 8, n_txns=8)
    batches = [packing.pack_batch(t, v, 0, cfg) for t, v in stream]
    cs = TpuConflictSet(cfg, mesh=cpu_mesh(2), shard_boundaries=boundaries)
    outs = [
        cs.resolve_group_args(stack_device_args(batches[lo : lo + 2]))
        for lo in range(0, 8, 2)
    ]
    cs.check_overflow()
    assert cs.metrics.counters.get("spills") > 0
    assert cs.metrics.counters.get("exactFallbacks") == 0

    oracle = MultiResolverOracle(boundaries, window=cfg.window_versions)
    for i, (txns, v) in enumerate(stream):
        want = oracle.resolve(
            [
                OracleTxn(t.read_conflict_ranges, t.write_conflict_ranges,
                          t.read_snapshot, t.report_conflicting_keys)
                for t in txns
            ],
            v,
        )
        g, k = divmod(i, 2)
        got = [int(x) for x in np.asarray(outs[g].verdict[k])[: len(txns)]]
        assert got == list(want.verdicts), f"sharded spill batch {i}"


def test_spill_then_rebase():
    """A spill (delta folded to MAIN) followed by the int32 offset
    rebase: spilled segments must shift with main and still conflict
    correctly on the far side of the jump."""
    from foundationdb_tpu.models.conflict_set import REBASE_THRESHOLD

    cfg = spill_config(window_versions=1 << 33, delta_capacity=96)
    k = lambda i: bytes([i])
    v0 = 1000
    # enough writers to trip the conservative spill bound twice
    writers = [
        ([CommitTransaction([], [(k(5), k(6))], read_snapshot=v0 - 1)]
         + [
             CommitTransaction([], [(k(20 + j), k(21 + j))],
                               read_snapshot=v0 - 1)
             for j in range(8)
         ], v0 + i)
        for i in range(3)
    ]
    far = v0 + REBASE_THRESHOLD + (1 << 21)
    r_stale = CommitTransaction([(k(5), k(6))], [(k(9), k(10))],
                                read_snapshot=v0 - 1)
    r_fresh = CommitTransaction([(k(5), k(6))], [(k(11), k(12))],
                                read_snapshot=far - 1)
    stream = writers + [([r_stale, r_fresh], far)]
    cs = TpuConflictSet(cfg)
    res = run_resolve(cs, stream)
    assert cs.metrics.counters.get("spills") > 0
    assert cs.metrics.counters.get("rebases") > 0
    assert res[-1].verdicts[0].name == "CONFLICT"
    assert res[-1].verdicts[1].name == "COMMITTED"
    ref = run_resolve(
        TpuConflictSet(
            dataclasses.replace(cfg, delta_capacity=4096, delta_spill=False)
        ),
        stream,
    )
    assert_results_match(res, ref, "spill then rebase")


def test_single_group_past_capacity_still_raises():
    """The backstop: ONE batch whose conservative bound exceeds
    delta_capacity cannot be spilled around — the latch+raise remains
    (a configuration error, never a silent truncation)."""
    cfg = sweep_config(delta_capacity=4, compact_interval=0,
                       delta_spill=True)
    k = lambda i: bytes([i])
    txns = [
        CommitTransaction([], [(k(2 * i), k(2 * i + 1))], read_snapshot=50)
        for i in range(8)
    ]
    cs = TpuConflictSet(cfg)
    with pytest.raises(HistoryOverflowError):
        cs.resolve(txns, 100)


def test_wire_resolver_role_runs_sweep_kernel():
    """The wire threading: a ResolverRole whose RESOLVER_KERNEL env
    carries a sweep+spill config must dispatch through the sweep path
    (sweepGroups counting) and produce oracle-identical decisions —
    the same mechanism the chaos/bench wire clusters use."""
    import asyncio
    import os

    from foundationdb_tpu.cluster import multiprocess as mp
    from foundationdb_tpu.models.types import (
        ResolveTransactionBatchRequest,
        TransactionResult,
    )

    cfg = sweep_config(delta_capacity=96, compact_interval=0,
                       delta_spill=True)
    os.environ["RESOLVER_KERNEL"] = (
        "KernelConfig(max_key_bytes=8, max_txns=16, max_reads=32, "
        "max_writes=32, history_capacity=512, window_versions=1000, "
        "delta_capacity=96, compact_interval=0, range_sweep=True, "
        "delta_spill=True)"
    )
    try:
        role = mp.ResolverRole(backend="tpu-force")
    finally:
        os.environ.pop("RESOLVER_KERNEL", None)
    rng = np.random.default_rng(77)
    stream = gen_stream(rng, 5)
    oracle = CpuConflictSet(cfg)

    async def wire():
        prev = -1
        for txns, v in stream:
            rep = await role.resolve(ResolveTransactionBatchRequest(
                prev_version=prev, version=v, last_received_version=prev,
                transactions=txns, proxy_id="p0",
            ))
            want = oracle.resolve(txns, v)
            assert [TransactionResult(c) for c in rep.committed] == (
                want.verdicts
            ), f"wire sweep divergence at version {v}"
            prev = v

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(wire())
    finally:
        loop.close()
    c = role._cs.metrics.counters
    assert c.get("sweepGroups") == len(stream)
    assert c.get("spills") > 0
    assert c.get("exactFallbacks") == 0

"""Tiered (delta + main) kernel parity: decisions identical to the oracle.

The tiered kernel (ops/delta.py — G-independent scan body, delta-tier
merges, periodic compaction, optional device-side read dedup) must be
decision-identical to the Python oracle (testing/oracle.ConflictOracle,
the reference's ConflictBatch semantics, served by CpuConflictSet), and
its fused groups to its own batch-at-a-time dispatch, on the adversarial
shapes the design introduces machinery for:

* duplicate/overlapping conflict ranges (the dedup sort+unique path),
* window-edge versions (snapshots at/beside the GC floor),
* compaction boundaries (delta folded into main mid-stream, at every
  cadence),
* latch/overflow trips (dedup latch: unconverged + state unchanged;
  delta capacity overflow: loud HistoryOverflowError, never silence).

Runs in the kernel parity lane (8-device CPU mesh, -m kernel).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import (
    OVERFLOW_CHECK_INTERVAL,
    TpuConflictSet,
)
from foundationdb_tpu.models.conflict_base import (
    CpuConflictSet,
    HistoryOverflowError,
)
from foundationdb_tpu.models.types import CommitTransaction
from foundationdb_tpu.ops import delta as D
from foundationdb_tpu.ops import history as H
from foundationdb_tpu.utils import packing
from foundationdb_tpu.utils.packing import stack_device_args

from conftest import random_range

# compile-heavy kernel tests: run with -m kernel (fast lane: -m 'not kernel')
pytestmark = pytest.mark.kernel


def tiered_config(**kw):
    d = dict(
        max_key_bytes=8,
        max_txns=16,
        max_reads=32,
        max_writes=32,
        history_capacity=512,
        window_versions=1000,
        delta_capacity=256,
        compact_interval=1,
    )
    d.update(kw)
    return KernelConfig(**d)


def run_oracle(cfg, stream):
    """The reference decisions: testing/oracle.ConflictOracle, through
    the CPU backend that serves it (CpuConflictSet)."""
    return run_resolve(CpuConflictSet(cfg), stream)


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


def run_resolve(cs, stream):
    return [cs.resolve(txns, v) for txns, v in stream]


def assert_results_match(a, b, label=""):
    for i, (ra, rb) in enumerate(zip(a, b)):
        assert ra.verdicts == rb.verdicts, f"{label} verdicts batch {i}"
        assert ra.conflicting_key_ranges == rb.conflicting_key_ranges, (
            f"{label} conflicting ranges batch {i}"
        )


def canonical_map(hist: H.VersionHistory):
    """(boundary key, version) pairs with redundant rows collapsed (the
    test_group_parity evaluation-equality form, for ONE tier)."""
    mk = np.asarray(hist.main_keys)
    mv = np.asarray(hist.main_ver)
    rows = []
    for j in range(mk.shape[0]):
        if all(x == 0xFFFFFFFF for x in mk[j]):
            continue
        rows.append((tuple(mk[j]), int(mv[j])))
    rows.sort()
    dedup = {}
    for k, v in rows:
        dedup[k] = v
    out = []
    for k in sorted(dedup):
        if not out or out[-1][1] != dedup[k]:
            out.append((k, dedup[k]))
    return out


@pytest.mark.parametrize("seed", range(6))
def test_tiered_matches_classic_random(seed):
    """Random streams: verdicts and conflicting-key reports equal the
    oracle's, batch for batch."""
    rng = np.random.default_rng(seed)
    cfg = tiered_config()
    stream = gen_stream(rng, 8)
    res_t = run_resolve(TpuConflictSet(cfg), stream)
    res_o = run_oracle(cfg, stream)
    assert_results_match(res_t, res_o, "tiered vs oracle")


@pytest.mark.parametrize("seed", range(4))
def test_tiered_matches_cpu_oracle(seed):
    """Full-stack parity against the CPU backend (the skiplist-semantics
    oracle behind the resolver_backend knob)."""
    rng = np.random.default_rng(100 + seed)
    cfg = tiered_config(dedup_reads=32)
    stream = gen_stream(rng, 6)
    res_t = run_resolve(TpuConflictSet(cfg), stream)
    res_o = run_resolve(CpuConflictSet(cfg), stream)
    assert_results_match(res_t, res_o, "tiered vs cpu oracle")


@pytest.mark.parametrize("seed", range(4))
def test_duplicate_and_overlapping_ranges_dedup_parity(seed):
    """Hot-key adversarial: most ranges drawn from a small duplicate
    pool (plus overlapping random ones). The dedup path must be
    decision-identical to dedup-off and to the oracle."""
    rng = np.random.default_rng(200 + seed)
    pool = [random_range(rng) for _ in range(4)]
    stream = gen_stream(rng, 6, dup_pool=pool)
    cfg_dedup = tiered_config(dedup_reads=16)
    res_d = run_resolve(TpuConflictSet(cfg_dedup), stream)
    res_p = run_resolve(TpuConflictSet(tiered_config()), stream)
    res_o = run_oracle(cfg_dedup, stream)
    assert_results_match(res_d, res_p, "dedup vs plain tiered")
    assert_results_match(res_d, res_o, "dedup vs oracle")


def test_window_edge_versions():
    """Snapshots exactly at / one beside the MVCC floor: the too-old
    boundary and the GC boundary must match the oracle."""
    cfg = tiered_config(window_versions=100)
    k = lambda i: bytes([i])
    streams = []
    for snap in (99, 100, 101, 199, 200):
        streams.append((
            [
                CommitTransaction([(k(1), k(2))], [(k(1), k(2))],
                                  read_snapshot=snap),
                CommitTransaction([], [(k(3), k(4))], read_snapshot=snap),
            ],
            200 + len(streams),  # versions ascend; floor = version - 100
        ))
    res_t = run_resolve(TpuConflictSet(cfg), streams)
    res_o = run_oracle(cfg, streams)
    assert_results_match(res_t, res_o, "window edge")


@pytest.mark.parametrize("interval", [1, 2, 4, 0])
def test_compaction_cadence_invariance(interval):
    """Decisions must not depend on WHEN delta folds into main: every
    compaction cadence (incl. never) gives the oracle's verdicts, and
    the combined key->version map after an explicit final compaction
    matches that of a run that folds delta into main only at the end."""
    rng = np.random.default_rng(42)
    stream = gen_stream(rng, 8)
    cfg = tiered_config(compact_interval=interval, delta_capacity=512)
    cs = TpuConflictSet(cfg)
    res = run_resolve(cs, stream)
    assert_results_match(res, run_oracle(cfg, stream), f"interval={interval}")
    once = TpuConflictSet(dataclasses.replace(cfg, compact_interval=0))
    run_resolve(once, stream)

    for c in (cs, once):
        c.compact_history()
        assert not bool(np.asarray(H.boundary_count(c.state.delta)))
    got = canonical_map(cs.state.main)
    want = canonical_map(once.state.main)
    assert got == want, "post-compaction combined map diverges"


def test_compaction_boundary_mid_group_stream():
    """Group-path compaction boundaries: groups resolved through
    resolve_group_args with auto-compaction between them must match the
    batch-at-a-time path batch-for-batch, and the oracle's verdicts."""
    rng = np.random.default_rng(7)
    cfg = tiered_config(compact_interval=1)
    stream = gen_stream(rng, 9, n_txns=8)
    batches = [
        packing.pack_batch(txns, v, 0, cfg) for txns, v in stream
    ]
    single = TpuConflictSet(cfg)
    seq = [single.resolve_args(b.device_args()) for b in batches]
    want = run_oracle(cfg, stream)

    cs = TpuConflictSet(cfg)
    outs = [
        cs.resolve_group_args(stack_device_args(batches[lo : lo + 3]))
        for lo in (0, 3, 6)
    ]
    for i in range(9):
        g, k = divmod(i, 3)
        np.testing.assert_array_equal(
            np.asarray(outs[g].verdict[k]), np.asarray(seq[i].verdict),
            err_msg=f"verdict batch {i}",
        )
        np.testing.assert_array_equal(
            np.asarray(outs[g].hist_conflict_read[k]),
            np.asarray(seq[i].hist_conflict_read),
            err_msg=f"hist_conflict_read batch {i}",
        )
        np.testing.assert_array_equal(
            np.asarray(outs[g].intra_first_range[k]),
            np.asarray(seq[i].intra_first_range),
            err_msg=f"intra_first_range batch {i}",
        )
        assert np.asarray(outs[g].verdict[k])[:8].tolist() == [
            int(v) for v in want[i].verdicts
        ], f"oracle verdict batch {i}"


def test_dedup_latch_trips_state_unchanged_and_fallback():
    """More distinct live read ranges than dedup_reads: the raw kernel
    must refuse (unconverged, BOTH tiers unchanged); the default host
    path must auto-redispatch the exact kernel and serve decisions
    identical to dedup-off."""
    rng = np.random.default_rng(3)
    cfg = tiered_config(dedup_reads=2, compact_interval=0)
    stream = gen_stream(rng, 3)
    batches = [packing.pack_batch(t, v, 0, cfg) for t, v in stream]
    stacked = stack_device_args(batches)

    cs_raw = TpuConflictSet(cfg)
    before = jax.tree.map(lambda x: np.asarray(x).copy(), cs_raw.state)
    outs_raw = cs_raw.resolve_group_args(stacked, check_latch=False)
    assert bool(np.asarray(outs_raw.unconverged).all())
    for a, b in zip(
        jax.tree_util.tree_leaves(before),
        jax.tree_util.tree_leaves(cs_raw.state),
    ):
        np.testing.assert_array_equal(a, np.asarray(b))

    cs = TpuConflictSet(cfg)
    outs = cs.resolve_group_args(stacked)
    assert not bool(np.asarray(outs.unconverged).any())
    ref = TpuConflictSet(tiered_config(compact_interval=0)).resolve_group_args(
        stacked
    )
    np.testing.assert_array_equal(
        np.asarray(outs.verdict), np.asarray(ref.verdict)
    )


def test_delta_overflow_raises_loudly():
    """A delta tier too small for the write load must latch overflow and
    raise HistoryOverflowError at the next check — never truncate."""
    cfg = tiered_config(delta_capacity=4, compact_interval=0)
    k = lambda i: bytes([i])
    txns = [
        CommitTransaction([], [(k(2 * i), k(2 * i + 1))], read_snapshot=50)
        for i in range(8)
    ]
    cs = TpuConflictSet(cfg)
    with pytest.raises(HistoryOverflowError):
        cs.resolve(txns, 100)


def test_compaction_overflow_folds_into_main():
    """A latched delta overflow must survive compaction (folded into
    main.overflow) so the raise can never be skipped by a compact."""
    cfg = tiered_config(delta_capacity=4, compact_interval=0)
    k = lambda i: bytes([i])
    txns = [
        CommitTransaction([], [(k(2 * i), k(2 * i + 1))], read_snapshot=50)
        for i in range(8)
    ]
    cs = TpuConflictSet(cfg)
    batch = packing.pack_batch(txns, 100, 0, cfg)
    cs.resolve_group_args(stack_device_args([batch]), check_latch=False)
    cs.compact_history()
    assert not bool(np.asarray(cs.state.delta.overflow))
    with pytest.raises(HistoryOverflowError):
        cs.check_overflow()


def test_pipelined_stream_matches_sequential():
    """resolve_stream_pipelined (staging-thread pack->copy->compute)
    must produce the batch-at-a-time decisions, chunk by chunk, and
    the oracle's verdicts."""
    rng = np.random.default_rng(11)
    cfg = tiered_config()
    stream = gen_stream(rng, 8, n_txns=8)
    batches = [packing.pack_batch(t, v, 0, cfg) for t, v in stream]
    single = TpuConflictSet(cfg)
    seq = [single.resolve_args(b.device_args()) for b in batches]
    want = run_oracle(cfg, stream)

    cs = TpuConflictSet(cfg)
    outs = cs.resolve_stream_pipelined(batches, chunk=3)
    flat = [
        (g, k)
        for g in range(len(outs))
        for k in range(np.asarray(outs[g].verdict).shape[0])
    ]
    assert len(flat) == len(batches)
    for i, (g, k) in enumerate(flat):
        np.testing.assert_array_equal(
            np.asarray(outs[g].verdict[k]), np.asarray(seq[i].verdict),
            err_msg=f"pipelined batch {i}",
        )
        assert np.asarray(outs[g].verdict[k])[:8].tolist() == [
            int(v) for v in want[i].verdicts
        ], f"oracle verdict batch {i}"


def test_pipelined_stream_overflow_joins_staging_thread():
    """A mid-stream HistoryOverflowError must not strand the staging
    thread on the bounded queue (it holds staged device buffers)."""
    import threading

    cfg = tiered_config(
        delta_capacity=8, compact_interval=0, window_versions=100000
    )
    k = lambda i: bytes([i % 250])
    batches = []
    for i in range(3 * OVERFLOW_CHECK_INTERVAL):
        txns = [
            CommitTransaction(
                [], [(k(3 * j + i), k(3 * j + i) + b"\x01")],
                read_snapshot=50,
            )
            for j in range(8)
        ]
        batches.append(packing.pack_batch(txns, 100 + i, 0, cfg))
    cs = TpuConflictSet(cfg)
    with pytest.raises(HistoryOverflowError):
        cs.resolve_stream_pipelined(batches, chunk=1, check_latch=False)
    assert not any(
        t.name == "resolver-staging" for t in threading.enumerate()
    )


def test_tiered_rebase_matches_classic():
    """The int32 offset rebase must shift BOTH tiers (a delta-tier
    segment surviving a rebase still conflicts correctly)."""
    from foundationdb_tpu.models.conflict_set import REBASE_THRESHOLD

    # window wider than the rebase jump so the old-snapshot reader is
    # judged on staleness (CONFLICT), not the too-old floor
    cfg = tiered_config(window_versions=1 << 33, compact_interval=0)
    k = lambda i: bytes([i])
    v0 = 1000
    w = CommitTransaction([], [(k(5), k(6))], read_snapshot=v0 - 1)
    far = v0 + REBASE_THRESHOLD + (1 << 21)
    r = CommitTransaction([(k(5), k(6))], [(k(9), k(10))],
                          read_snapshot=v0 - 1)  # stale: must conflict
    r2 = CommitTransaction([(k(5), k(6))], [(k(11), k(12))],
                           read_snapshot=far - 1)  # fresh: commits
    stream = [([w], v0), ([r, r2], far)]
    res_t = run_resolve(TpuConflictSet(cfg), stream)
    res_o = run_oracle(cfg, stream)
    assert_results_match(res_t, res_o, "rebase")
    assert res_t[1].verdicts[0].name == "CONFLICT"
    assert res_t[1].verdicts[1].name == "COMMITTED"

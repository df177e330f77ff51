"""Contention-profile routing picks the measured winner per regime
(VERDICT r4 task 2): the r5 device runs measured uniform ~2-3x FOR the
TPU kernel, zipf 0.68x and range-heavy 0.28x AGAINST it — so the router
must send hot-key and range-heavy streams to the CPU skiplist and
large-batch uniform streams to the device."""

import numpy as np

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import (
    backend_for_profile,
    profile_batch,
    route_stream,
)
from foundationdb_tpu.testing.benchgen import skiplist_style_batch
from foundationdb_tpu.utils.knobs import SERVER_KNOBS


def cfg(cap=65536):
    return KernelConfig(
        max_key_bytes=8, max_txns=cap, max_reads=cap, max_writes=cap,
        history_capacity=12 * cap, window_versions=1_000_000,
    )


def gen(mode, n=65536, config=None):
    config = config or cfg()
    rng = np.random.default_rng(3)
    kw = {
        "uniform": dict(keyspace=1_000_000),
        "zipf": dict(zipf=1.1, keyspace=10_000_000),
        "range": dict(range_len=500, keyspace=1_000_000),
    }[mode]
    return [
        skiplist_style_batch(
            rng, config, n, version=(i + 1) * 200_000, key_bytes=8,
            snapshot_lag=400_000, **kw,
        )
        for i in range(2)
    ]


def test_profiles_match_bench_configs():
    assert profile_batch(gen("uniform")[0]) == "uniform"
    assert profile_batch(gen("zipf")[0]) == "hot_key"
    assert profile_batch(gen("range")[0]) == "range_heavy"


def test_router_picks_measured_winner():
    SERVER_KNOBS.reset()
    assert route_stream(gen("uniform"), cfg()) == "tpu"
    assert route_stream(gen("zipf"), cfg()) == "cpu"     # 0.68x measured
    assert route_stream(gen("range"), cfg()) == "cpu"    # 0.28x measured
    # small-batch uniform still routes CPU (the capacity gate)
    small = cfg(4096)
    assert route_stream(gen("uniform", 4096, small), small) == "cpu"


def test_backend_for_profile_table():
    assert backend_for_profile("uniform") == "tpu"
    assert backend_for_profile("hot_key") == "cpu"
    assert backend_for_profile("range_heavy") == "cpu"


def test_backend_for_profile_narrowed_by_kernel_config():
    """The router narrows as the kernel grows the structure each regime
    needs (ISSUE 14: nothing left to route away): tiered+dedup keeps
    hot_key on device, tiered+sweep keeps range_heavy on device; an
    unconfigured kernel still routes both away."""
    import dataclasses

    from foundationdb_tpu.models.conflict_set import fallback_free

    base = cfg()
    dedup = dataclasses.replace(base, delta_capacity=1024, dedup_reads=256)
    sweep = dataclasses.replace(base, delta_capacity=1024, range_sweep=True)
    assert backend_for_profile("hot_key", dedup) == "tpu"
    assert backend_for_profile("hot_key", sweep) == "cpu"
    assert backend_for_profile("range_heavy", sweep) == "tpu"
    assert backend_for_profile("range_heavy", dedup) == "cpu"
    assert backend_for_profile("range_heavy", base) == "cpu"
    # route_stream end-to-end: a range stream stays on device with the
    # sweep configured (the ISSUE-14 acceptance direction), and still
    # routes away without it (the measured-0.28x direction above)
    SERVER_KNOBS.reset()
    assert route_stream(gen("range"), sweep) == "tpu"
    assert not fallback_free(base)
    assert fallback_free(
        dataclasses.replace(sweep, delta_spill=True)
    )


def test_profile_classifiers_agree_on_shared_fixtures():
    """ISSUE 14 satellite bugfix: profile_batch (packed words) and
    profile_transactions (raw key bytes) must classify the SAME
    workload identically — including keyspaces with a LONG common
    prefix, where the old byte-granularity commonprefix strip put the
    two classifiers' 8-byte windows at different offsets (one folded
    the first varying WORD, the other stripped bytes), diverging the
    span/dup thresholds."""
    from foundationdb_tpu.models.conflict_set import (
        profile_transactions,
    )
    from foundationdb_tpu.models.types import CommitTransaction
    from foundationdb_tpu.utils.packing import pack_batch

    rng = np.random.default_rng(17)
    # 11-byte shared prefix + zero-heavy high int bytes: the byte-level
    # commonprefix is NOT word aligned (the old divergence trigger); a
    # 9-byte int keeps the whole key word-aligned so the low word is
    # pure key data (a trailing pad byte would scale every span by 256
    # — identically in both classifiers, but the fixture wants natural
    # spans)
    prefix = b"tenant/ab/\xff"

    def key(v):
        return prefix + int(v).to_bytes(9, "big")

    def txns(mode, n=256):
        out = []
        for i in range(n):
            if mode == "range":
                b = int(rng.integers(0, 1 << 20))
                reads = [(key(b), key(b + 500))]
                writes = [(key(int(rng.integers(0, 1 << 20))),
                           key(int(rng.integers(0, 1 << 20))) + b"\x00")]
            elif mode == "hot":
                hot = int(rng.integers(0, 4))
                reads = [(key(hot), key(hot) + b"\x00")]
                writes = [(key(hot), key(hot) + b"\x00")]
            else:  # uniform points
                b = int(rng.integers(0, 1 << 20)) * 7
                reads = [(key(b), key(b + 1))]
                writes = [(key(b + 1), key(b + 2))]
            out.append(CommitTransaction(
                read_conflict_ranges=reads,
                write_conflict_ranges=writes,
                read_snapshot=50,
            ))
        return out

    config = KernelConfig(
        max_key_bytes=20, max_txns=256, max_reads=256, max_writes=256,
        history_capacity=1 << 12, window_versions=1_000_000,
    )
    want = {"range": "range_heavy", "hot": "hot_key", "uniform": "uniform"}
    for mode, expect in want.items():
        t = txns(mode)
        from_txns = profile_transactions(t)
        from_batch = profile_batch(pack_batch(t, 100, 0, config))
        assert from_txns == from_batch == expect, (
            f"{mode}: txns={from_txns} batch={from_batch} want={expect}"
        )
    # and on the bench generator's zero-padded short keys (the packed
    # representation is WIDER than the raw keys — the other historical
    # divergence class: a constant zero successor word scaled spans)
    for mode in ("uniform", "zipf", "range"):
        b = gen(mode)[0]
        t = _batch_to_txns(b)
        assert profile_transactions(t) == profile_batch(b), mode


def test_dup_detection_is_exact_not_fold_windowed():
    """Keys shaped (few-valued word, constant word, unique word): a
    fold-window dup check collapses them to the few leading values and
    mis-fires hot_key; duplicate detection must compare FULL key rows
    (review finding r14) — and still agree across both classifiers."""
    from foundationdb_tpu.models.conflict_set import profile_transactions
    from foundationdb_tpu.models.types import CommitTransaction
    from foundationdb_tpu.utils.packing import pack_batch

    rng = np.random.default_rng(2)

    def key(i):
        region = int(rng.integers(0, 3))
        return (region.to_bytes(4, "big") + b"\x00\x00\x00\x00"
                + int(i).to_bytes(4, "big"))

    txns = [
        CommitTransaction(
            read_conflict_ranges=[(key(i), key(i) + b"\x00")],
            write_conflict_ranges=[(key(1000 + i), key(1000 + i) + b"\x00")],
            read_snapshot=50,
        )
        for i in range(256)
    ]
    config = KernelConfig(
        max_key_bytes=12, max_txns=256, max_reads=256, max_writes=256,
        history_capacity=1 << 12, window_versions=1_000_000,
    )
    pt = profile_transactions(txns)
    pb = profile_batch(pack_batch(txns, 100, 0, config))
    assert pt == pb == "uniform", (pt, pb)


def _batch_to_txns(batch):
    """Reconstruct CommitTransactions from a benchgen PackedBatch (keys
    unpack from the big-endian words + length word)."""
    from foundationdb_tpu.models.types import CommitTransaction

    def unpack(arr, r):
        row = arr[r]
        length = int(row[-1])
        raw = b"".join(int(w).to_bytes(4, "big") for w in row[:-1])
        return raw[:length]

    txns = {}
    for r in range(batch.n_reads):
        t = int(batch.read_txn[r])
        txns.setdefault(t, ([], []))[0].append(
            (unpack(batch.read_begin, r), unpack(batch.read_end, r))
        )
    for r in range(batch.n_writes):
        t = int(batch.write_txn[r])
        txns.setdefault(t, ([], []))[1].append(
            (unpack(batch.write_begin, r), unpack(batch.write_end, r))
        )
    return [
        CommitTransaction(
            read_conflict_ranges=txns[t][0],
            write_conflict_ranges=txns[t][1],
            read_snapshot=int(batch.snapshot[t]),
        )
        for t in sorted(txns)
    ]


def test_resolver_routes_on_first_batch():
    """The wiring: a Resolver with the tpu knob chooses its backend from
    the FIRST batch's contention profile (one-shot — switching later
    would discard MVCC history; drift only warns)."""
    from foundationdb_tpu.models.conflict_set import TpuConflictSet
    from foundationdb_tpu.models.conflict_base import CpuConflictSet
    from foundationdb_tpu.models.types import (
        CommitTransaction,
        ResolveTransactionBatchRequest,
    )
    from foundationdb_tpu.resolver import Resolver
    from foundationdb_tpu.runtime.flow import Scheduler

    def hot_txns(n=64):
        return [
            CommitTransaction(
                read_conflict_ranges=[(b"hot", b"hot\x00")],
                write_conflict_ranges=[(b"hot", b"hot\x00")],
                read_snapshot=50,
            )
            for _ in range(n)
        ]

    def uni_txns(n=64):
        return [
            CommitTransaction(
                read_conflict_ranges=[
                    (b"u%06d" % (i * 7), b"u%06d\x00" % (i * 7))
                ],
                write_conflict_ranges=[
                    (b"u%06d" % (i * 7 + 1), b"u%06d\x00" % (i * 7 + 1))
                ],
                read_snapshot=50,
            )
            for i in range(n)
        ]

    def drive(txns):
        sched = Scheduler(sim=True)
        r = Resolver(sched, cfg(65536), backend="tpu")
        assert r.conflict_set is None  # lazily routed
        req = ResolveTransactionBatchRequest(
            prev_version=-1, version=100, last_received_version=-1,
            transactions=txns, proxy_id="p0",
        )
        t = sched.spawn(r.resolve(req))
        sched.run_until(t.done)
        t.done.get()
        return r.conflict_set

    assert isinstance(drive(hot_txns()), CpuConflictSet)
    assert isinstance(drive(uni_txns()), TpuConflictSet)

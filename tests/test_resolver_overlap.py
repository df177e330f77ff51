"""The Resolver role overlaps batches on the device and decides exactly
as it does one batch at a time.

A device resolver role (the jitted conflict set on the CPU backend, at
64-txn shapes) served over a real RpcServer to two closed-loop
connections: a batch passes the version chain once it is issued, so the
next one is packed and dispatched while the device runs it. Every
verdict and conflicting-key report equals the CPU oracle's across
compactions, an overflow check and a rebase; a retry of a batch still
on the device gets that batch's reply; a history overflow refuses its
batch and every later one without hanging; occupancy stays a busy
fraction. The native skip list serves one batch at a time, as before.
"""

from __future__ import annotations

import asyncio
import os
import random
import sys
import tempfile
import threading
import time

import pytest

from foundationdb_tpu.cluster import multiprocess as mp
from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_base import CpuConflictSet
from foundationdb_tpu.models.conflict_set import (
    OVERFLOW_CHECK_INTERVAL,
    REBASE_THRESHOLD,
)
from foundationdb_tpu.models.types import CommitTransaction, TransactionResult
from foundationdb_tpu.utils import packing
from foundationdb_tpu.wire import codec, transport

KERNEL = ("KernelConfig(max_key_bytes=16, max_txns=64, max_reads=256, "
          "max_writes=256, history_capacity=4096, window_versions=5000000, "
          "delta_capacity=2048, compact_interval=2)")
#: no compaction, and a delta tier that 60 disjoint writes a batch fill
#: within a few batches
OVERFLOWING = ("KernelConfig(max_key_bytes=16, max_txns=64, max_reads=256, "
               "max_writes=256, history_capacity=4096, "
               "window_versions=5000000, delta_capacity=512, "
               "compact_interval=0)")
#: past one overflow check (every 32 batches)
BATCHES = 44
STEP = 1000
#: the versions cross the rebase threshold half way
FIRST_VERSION = REBASE_THRESHOLD - (BATCHES // 2) * STEP


def _batches(n: int, seed: int, keys: int = 500, first=FIRST_VERSION):
    """n columnar resolve requests of 60 txns, one point read and one
    point write each; every fifth txn asks for its conflicting keys and
    every seventeenth reads below the MVCC window."""
    rng = random.Random(seed)
    reqs, prev = [], -1
    for i in range(n):
        version = first + i * STEP
        txns = []
        for j in range(60):
            r, w = b"k%d" % rng.randrange(keys), b"k%d" % rng.randrange(keys)
            snapshot = version - (6_000_000 if j % 17 == 3 else 1500)
            txns.append(CommitTransaction(
                read_conflict_ranges=[(r, r + b"\0")],
                write_conflict_ranges=[(w, w + b"\0")],
                read_snapshot=max(0, snapshot),
                report_conflicting_keys=j % 5 == 0,
            ))
        reqs.append((txns, codec.ResolveBatchColumnar(
            prev_version=prev, version=version, last_received_version=prev,
            cols=packing.pack_columnar(txns),
        )))
        prev = version
    return reqs


def _role(backend: str, kernel: str = KERNEL) -> mp.ResolverRole:
    with pytest.MonkeyPatch.context() as mpatch:
        mpatch.setenv("RESOLVER_KERNEL", kernel)
        return mp.ResolverRole(backend)


def _serve(role, drive, connections: int = 2):
    """Run `drive(connections)` against `role` behind a real RpcServer."""
    async def go(addr):
        server = transport.RpcServer(addr)
        server.register(mp.TOKEN_RESOLVE, role.resolve,
                        stages=role.wire_stages())
        await server.start()
        conns = [transport.RpcConnection(addr) for _ in range(connections)]
        try:
            for c in conns:
                await c.connect()
            return await asyncio.wait_for(drive(conns), timeout=120)
        finally:
            for c in conns:
                await c.close()
            await server.close()

    with tempfile.TemporaryDirectory(prefix="fdbov") as d:
        return asyncio.run(go(os.path.join(d, "resolver.sock")))


def _closed_loop(reqs):
    """Each connection sends the next unsent request and awaits its
    reply (the benchmark's proxies); outcome per request: the reply, or
    the error it raised."""
    async def drive(conns):
        out, nxt = {}, iter(range(len(reqs)))

        async def sender(conn):
            for i in nxt:
                try:
                    out[i] = await conn.call(mp.TOKEN_RESOLVE, reqs[i][1])
                except transport.RemoteError as e:
                    out[i] = e

        await asyncio.gather(*(sender(c) for c in conns))
        return [out[i] for i in range(len(reqs))]

    return drive


def _oracle(reqs):
    cs = CpuConflictSet(KernelConfig(max_txns=64, max_reads=256,
                                     max_writes=256,
                                     window_versions=5_000_000))
    return [cs.resolve(txns, req.version) for txns, req in reqs]


@pytest.mark.parametrize("backend,connections", [
    ("tpu-force", 2), ("native", 2),
    # more requests in flight than the depth and the host's cores, with
    # the threads switching as often as the interpreter allows
    ("tpu-force", 16),
])
def test_overlapped_decisions_equal_the_oracle(backend, connections):
    reqs = _batches(BATCHES, seed=11)
    role = _role(backend)
    t0 = time.monotonic()
    switch = sys.getswitchinterval()
    if connections > 2:
        sys.setswitchinterval(1e-6)
    try:
        replies = _serve(role, _closed_loop(reqs), connections)
    finally:
        sys.setswitchinterval(switch)
    elapsed = time.monotonic() - t0
    for i, (rep, want) in enumerate(zip(replies, _oracle(reqs))):
        assert not isinstance(rep, Exception), (i, rep)
        assert [TransactionResult(v) for v in rep.committed] == \
            want.verdicts, f"batch {i}"
        if backend == "tpu-force":
            assert rep.conflicting_key_range_map == \
                want.conflicting_key_ranges, f"batch {i}"
    qos = role.status()["qos"]
    overlap = qos["stages"]["overlap"]
    assert overlap["count"] == BATCHES
    # occupancy is the union of the busy intervals: a busy fraction
    assert role.occupancy.total <= elapsed
    assert qos["occupancy"] <= 1.0
    if backend == "native":
        assert overlap["mean"] == 0.0
        return
    assert (role._in_flight, role._busy_count) == (0, 0)
    counters = role._cs.metrics.counters
    assert counters.get("compactions") >= BATCHES // 2 - 1
    assert counters.get("rebases") == 1
    assert qos["stages"]["overflow_check"]["count"] == \
        BATCHES // OVERFLOW_CHECK_INTERVAL
    assert overlap["mean"] > 0.5


def test_retry_of_a_batch_on_the_device_gets_its_reply():
    reqs = _batches(3, seed=12)
    role = _role("tpu-force")
    gate = threading.Event()
    read_back = role._cs.read_back

    def held(pending):
        gate.wait(60)
        read_back(pending)

    async def drive(conns):
        first = await conns[0].call(mp.TOKEN_RESOLVE, reqs[0][1])
        role._cs.read_back = held
        req = reqs[1][1]
        sent = asyncio.ensure_future(conns[0].call(mp.TOKEN_RESOLVE, req))
        while role._issued < req.version:
            await asyncio.sleep(0.005)
        retry = asyncio.ensure_future(conns[1].call(mp.TOKEN_RESOLVE, req))
        await asyncio.sleep(0.1)
        assert not retry.done()  # it waits for the batch, not an error
        gate.set()
        last = await conns[1].call(mp.TOKEN_RESOLVE, reqs[2][1])
        return [first, await sent, await retry, last]

    first, sent, retry, last = _serve(role, drive)
    want = _oracle(reqs)
    assert [TransactionResult(v) for v in sent.committed] == want[1].verdicts
    assert retry.committed == sent.committed
    assert retry.conflicting_key_range_map == sent.conflicting_key_range_map
    assert [TransactionResult(v) for v in last.committed] == want[2].verdicts


def test_occupancy_is_the_union_of_overlapping_batches():
    """Each batch waits 0.2 s for its columns, two at a time: the busy
    intervals overlap, and occupancy counts the time they cover once."""
    reqs = _batches(8, seed=14)
    role = _role("tpu-force")
    read_back = role._cs.read_back

    def slow(pending):
        time.sleep(0.2)
        read_back(pending)

    role._cs.read_back = slow
    t0 = time.monotonic()
    replies = _serve(role, _closed_loop(reqs))
    elapsed = time.monotonic() - t0
    assert not any(isinstance(r, Exception) for r in replies)
    qos = role.status()["qos"]
    busy = qos["compute_time_dist"]["count"] * qos["compute_time_dist"]["mean"]
    assert busy > 1.3 * elapsed  # the batches did overlap
    assert 0.8 * 0.2 * len(reqs) / 2 < role.occupancy.total <= elapsed
    assert qos["occupancy"] <= 1.0


def test_overflow_refuses_its_batch_and_every_later_one():
    reqs = _batches(12, seed=13, keys=1_000_000, first=1000)
    role = _role("tpu-force", OVERFLOWING)
    replies = _serve(role, _closed_loop(reqs))
    failed = [i for i, r in enumerate(replies) if isinstance(r, Exception)]
    assert failed, "the delta tier never overflowed"
    k = failed[0]
    assert failed == list(range(k, len(reqs)))
    assert all("HistoryOverflowError" in str(replies[i]) for i in failed)
    for i, want in enumerate(_oracle(reqs)[:k]):
        assert [TransactionResult(v) for v in replies[i].committed] == \
            want.verdicts

"""The kernel's count of intra-batch fixpoint applications.

`BatchVerdict.fixpoint_iters` is the applications of the alternating
fixpoint a batch ran: the `fixpoint_unroll` straight-line ones, plus
the residual while loop's iterations. Against an empty history it
equals an independent count (numpy, below) of applications from the
all-ok start until one leaves the committed set unchanged, or the
unroll where that comes sooner. A conflict chain runs past the unroll.
On a 4-shard mesh (virtual CPU devices) each shard runs the fixpoint on
its own partition's keys and the batch's count is the deepest shard's.
"""

from __future__ import annotations

import numpy as np
import pytest

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import TpuConflictSet, _stack_one
from foundationdb_tpu.models.types import CommitTransaction
from foundationdb_tpu.parallel.mesh import cpu_mesh
from foundationdb_tpu.parallel.sharding import default_boundaries
from foundationdb_tpu.utils import packing

pytestmark = pytest.mark.kernel

VERSION = 5000
UNROLL = 3


def config(**kw) -> KernelConfig:
    return KernelConfig(max_key_bytes=8, max_txns=64, max_reads=256,
                        max_writes=256, history_capacity=2048,
                        window_versions=100000, delta_capacity=1024, **kw)


def txn(reads, writes) -> CommitTransaction:
    return CommitTransaction(
        read_conflict_ranges=[(k, k + b"\0") for k in reads],
        write_conflict_ranges=[(k, k + b"\0") for k in writes],
        read_snapshot=VERSION - 1,
    )


def hot_batch(seed: int, n: int = 60) -> list:
    """n txns of 1-3 point reads and 0-2 point writes over 10 keys,
    skewed towards the first, spread over the key's first byte so every
    partition of a 4-way split gets some."""
    rng = np.random.default_rng(seed)
    keys = [bytes([b, 7]) for b in (0x10, 0x50, 0x90, 0xd0, 0x11, 0x51,
                                    0x91, 0xd1, 0x12, 0x52)]
    p = 1.0 / np.arange(1, len(keys) + 1)
    p /= p.sum()

    def draw(k):
        return [keys[i] for i in rng.choice(len(keys), k, p=p)]

    return [txn(draw(int(rng.integers(1, 4))), draw(int(rng.integers(0, 3))))
            for _ in range(n)]


def chain(n: int, first: int = 0x10) -> list:
    """txn i reads txn i-1's key and writes its own: each verdict waits
    on the one before it, so the fixpoint takes about n applications."""
    key = [bytes([first, i]) for i in range(n)]
    return [txn(key[i - 1:i], key[i:i + 1]) for i in range(n)]


def applications(txns, partition=None) -> int:
    """Alternating-fixpoint applications from the all-ok start against an
    empty history, counting the one that changed nothing; with
    `partition`, over only the keys it holds (a shard's clipped batch)."""
    def local(ranges):
        return [k for k, _e in ranges if partition is None or partition(k)]

    reads = [(t, k) for t, x in enumerate(txns)
             for k in local(x.read_conflict_ranges)]
    writes = [(t, k) for t, x in enumerate(txns)
              for k in local(x.write_conflict_ranges)]
    committed = np.ones(len(txns), bool)
    n = 0
    while True:
        first_writer: dict[bytes, int] = {}
        for t, k in writes:
            if committed[t]:
                first_writer[k] = min(first_writer.get(k, t), t)
        nxt = np.ones(len(txns), bool)
        for t, k in reads:
            if first_writer.get(k, t) < t:
                nxt[t] = False
        n += 1
        if (nxt == committed).all():
            return n
        committed = nxt


def run(cs: TpuConflictSet, txns) -> int:
    """The batch's count as the kernel returns it; the completion samples
    the same value into the conflict set's `fixpoint_iters`."""
    pending = cs.issue(txns, VERSION)
    got = int(pending.out.fixpoint_iters)
    sample = cs.metrics.fixpoint_iters
    count, total = sample.count, sample.total
    cs.complete(pending)
    assert (sample.count, sample.total) == (count + 1, total + got)
    return got


@pytest.fixture(scope="module")
def one_device():
    return lambda: TpuConflictSet(config())


@pytest.fixture(scope="module")
def four_shards():
    return lambda: TpuConflictSet(config(n_shards=4), mesh=cpu_mesh(4))


@pytest.mark.parametrize("seed", range(6))
def test_count_matches_an_independent_count(one_device, seed):
    txns = hot_batch(seed)
    assert run(one_device(), txns) == max(UNROLL, applications(txns))


def test_a_conflict_chain_runs_past_the_unroll(one_device):
    txns = chain(12)
    want = applications(txns)
    assert want > UNROLL
    assert run(one_device(), txns) == want


def test_the_latched_kernel_counts_its_unroll_only():
    """With the fixpoint latch the kernel runs no loop: on a deeper
    chain it counts the unroll and trips the latch, and the exact kernel
    the host falls back to counts every application."""
    cs = TpuConflictSet(config(fixpoint_latch=True))
    txns = chain(12)
    batch = packing.pack_batch(txns, VERSION, cs.base_version, cs.config,
                               cs.rungs)
    args = cs._transfer(_stack_one(batch.device_args()))
    _, latched = cs._tiered_jit(0, UNROLL, True, 0)(cs.state, args)
    assert bool(latched.unconverged[0])
    assert int(latched.fixpoint_iters[0]) == UNROLL
    pending = cs.issue(txns, VERSION)
    assert cs.metrics.counters.get("latchTrips") == 1
    assert int(pending.out.fixpoint_iters) == applications(txns)


def test_one_partition_on_four_shards_counts_as_one_device(four_shards):
    txns = chain(12, first=0x90)
    assert run(four_shards(), txns) == applications(txns)


@pytest.mark.parametrize("seed", range(3))
def test_four_shards_count_the_deepest_shard(four_shards, seed):
    txns = hot_batch(seed)
    bounds = [b[0] for b in default_boundaries(4)]
    depth = [applications(txns, lambda k, s=s: (
        np.searchsorted(bounds, k[0], "right") == s)) for s in range(4)]
    assert run(four_shards(), txns) == max(UNROLL, *depth)

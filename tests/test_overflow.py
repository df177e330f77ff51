"""History-capacity overflow must fail the SAME resolve() that overflows.

ADVICE r1 (medium): the interval-based check let up to 32 batches of
verdicts computed against a truncated history escape to clients. The
contract (HistoryOverflowError docstring: "never silent wrong answers")
requires the sync path to refuse on the spot; BatchVerdict now carries
the overflow latch so resolve() checks it on the verdict sync it already
pays.
"""

import numpy as np
import pytest

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import TpuConflictSet
from foundationdb_tpu.models.conflict_base import HistoryOverflowError
from foundationdb_tpu.models.types import CommitTransaction

# compile-heavy kernel tests: run with -m kernel (fast lane: -m 'not kernel')
pytestmark = pytest.mark.kernel


def k(i: int) -> bytes:
    return int(i).to_bytes(4, "big")


def make_cfg(capacity: int) -> KernelConfig:
    return KernelConfig(
        max_key_bytes=8,
        max_txns=16,
        max_reads=16,
        max_writes=16,
        history_capacity=capacity,
        window_versions=10_000_000,  # no GC relief inside the test
        # fold delta into main after every batch: main (the capacity
        # under test) takes each batch's writes before the next resolve
        compact_interval=1,
    )


def disjoint_write_batch(base: int, n: int):
    # n disjoint, non-adjacent single-key ranges -> 2n new boundaries.
    return [
        CommitTransaction(write_conflict_ranges=[(k(base + 10 * i), k(base + 10 * i + 1))])
        for i in range(n)
    ]


def test_overflow_raises_on_the_overflowing_batch():
    cs = TpuConflictSet(make_cfg(capacity=24))
    version = 0
    raised_at = None
    for step in range(12):
        version += 100
        try:
            cs.resolve(disjoint_write_batch(100_000 * step, 8), version)
        except HistoryOverflowError:
            raised_at = step
            break
    assert raised_at is not None, "capacity 24 never overflowed after 96 ranges"
    # 8 ranges x 2 boundaries per batch: capacity 24 must blow within the
    # first 2-3 batches, not OVERFLOW_CHECK_INTERVAL (32) batches later.
    assert raised_at <= 3


def test_no_overflow_below_capacity():
    cs = TpuConflictSet(make_cfg(capacity=256))
    version = 0
    for step in range(6):
        version += 100
        res = cs.resolve(disjoint_write_batch(100_000 * step, 8), version)
        assert len(res.verdicts) == 8
    cs.check_overflow()  # explicit check also clean

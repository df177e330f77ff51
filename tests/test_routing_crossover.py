"""RESOLVER_TPU_MIN_BATCH is the MEASURED routing crossover, not a guess.

VERDICT r4 task 3. The round-5 sweep (scripts/sweep_small.py on a v5e,
classic kernel) measured single-dispatch throughput per batch
size; the device first beats the CPU skiplist at n=65536 (347K vs 338K
txn/s device-resident; below that the CPU wins by 2-40x). This test
pins (a) the knob default to that measurement and (b) the
make_conflict_set routing decision on both sides of it.
"""

from __future__ import annotations

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import (
    CpuConflictSet,
    TpuConflictSet,
    make_conflict_set,
)
from foundationdb_tpu.utils.knobs import SERVER_KNOBS

MEASURED_CROSSOVER = 65536  # scripts/sweep_small.py, r5 device run


def cfg(cap):
    return KernelConfig(
        max_key_bytes=8, max_txns=cap, max_reads=cap, max_writes=cap,
        history_capacity=12 * cap, window_versions=1_000_000,
    )


def test_knob_default_matches_measurement():
    SERVER_KNOBS.reset()
    assert SERVER_KNOBS.RESOLVER_TPU_MIN_BATCH == MEASURED_CROSSOVER


def test_routing_below_crossover_is_cpu():
    SERVER_KNOBS.reset()
    cs = make_conflict_set(cfg(MEASURED_CROSSOVER // 2), backend="tpu")
    assert isinstance(cs, CpuConflictSet)


def test_routing_at_crossover_is_tpu():
    SERVER_KNOBS.reset()
    cs = make_conflict_set(cfg(MEASURED_CROSSOVER), backend="tpu")
    assert isinstance(cs, TpuConflictSet)


def test_force_overrides_measurement():
    SERVER_KNOBS.reset()
    cs = make_conflict_set(cfg(1024), backend="tpu-force")
    assert isinstance(cs, TpuConflictSet)

#!/usr/bin/env python
"""Tiny-shape kernel-parity smoke for the fast CI lane (seconds).

Drives the device kernel (ops/delta.py, with dedup + per-group
compaction) against the Python oracle (testing.oracle.ConflictOracle,
served through CpuConflictSet) on a seeded random stream, plus one
dedup-latch trip with the exact-kernel fallback. Shapes are tiny so the whole run is XLA-compile-bound at a
few seconds on JAX_PLATFORMS=cpu: kernel refactors cannot silently
change commit/abort decisions in the fast lane (scripts/check.sh);
the deep adversarial coverage lives in the kernel parity lane
(pytest -m kernel).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--perf-out", default=None,
        help="emit the run's STRUCTURAL perf-ledger row (decision "
             "counts, kernel counters, merge-row capacities — all "
             "deterministic on any host) to this JSONL; the check.sh "
             "perf lane feeds it to scripts/perfcheck.py",
    )
    args = ap.parse_args()
    t_start = time.perf_counter()
    from foundationdb_tpu.config import KernelConfig
    from foundationdb_tpu.models.conflict_set import TpuConflictSet
    from foundationdb_tpu.models.conflict_base import CpuConflictSet
    from foundationdb_tpu.models.types import CommitTransaction

    base_cfg = dict(
        max_key_bytes=8, max_txns=8, max_reads=16, max_writes=16,
        history_capacity=128, window_versions=500,
    )
    oracle_cfg = KernelConfig(**base_cfg)
    tiered = KernelConfig(
        **base_cfg, delta_capacity=64, dedup_reads=8, compact_interval=1
    )
    tripwire = KernelConfig(
        **base_cfg, delta_capacity=64, dedup_reads=2, compact_interval=1
    )

    rng = np.random.default_rng(0x52)

    def key():
        return bytes(rng.integers(0, 8, size=int(rng.integers(1, 4)),
                                  dtype=np.uint8))

    def rrange():
        a, b = sorted([key(), key()])
        return (a, b) if a != b else (a, a + b"\x00")

    def txn(lo, hi):
        return CommitTransaction(
            read_conflict_ranges=[
                rrange() for _ in range(int(rng.integers(0, 3)))
            ],
            write_conflict_ranges=[
                rrange() for _ in range(1 + int(rng.integers(0, 2)))
            ],
            read_snapshot=int(rng.integers(lo, hi)),
            report_conflicting_keys=bool(rng.random() < 0.5),
        )

    base, step = 1000, 100
    stream = []
    for i in range(6):
        v = base + (i + 1) * step
        stream.append(([txn(base - 150, v) for _ in range(6)], v))

    oracle = CpuConflictSet(oracle_cfg)
    sets = {
        "tiered+dedup": TpuConflictSet(tiered),
        "tiered(dedup-latch-fallback)": TpuConflictSet(tripwire),
    }
    want = [oracle.resolve(txns, v) for txns, v in stream]
    failures = 0
    for name, cs in sets.items():
        for i, (txns, v) in enumerate(stream):
            got = cs.resolve(txns, v)
            if got.verdicts != want[i].verdicts:
                print(f"FAIL {name} batch {i}: verdicts "
                      f"{got.verdicts} != {want[i].verdicts}")
                failures += 1
            if got.conflicting_key_ranges != want[i].conflicting_key_ranges:
                print(f"FAIL {name} batch {i}: conflicting ranges "
                      f"{got.conflicting_key_ranges} != "
                      f"{want[i].conflicting_key_ranges}")
                failures += 1

    # ---- range-heavy oracle case (ISSUE 14): the sorted-endpoint -----
    # sweep probe ON and OFF against the oracle on wide-scan shapes,
    # with spill-and-compact exercised mid-stream (delta sized to trip
    # the pressure fold). A regression in the sweep ranks, the spill
    # fold, or the no-fallback contract (exactFallbacks must stay 0)
    # fails the fast lane before any hardware run.
    sweep_cfg = KernelConfig(
        **base_cfg, delta_capacity=64, compact_interval=0,
        range_sweep=True, delta_spill=True,
    )
    probe_cfg = KernelConfig(
        **base_cfg, delta_capacity=64, compact_interval=2,
    )

    def scan_txn(lo, hi):
        b = int(rng.integers(0, 200))
        span = int(rng.integers(8, 64))
        wb = int(rng.integers(0, 200))
        return CommitTransaction(
            read_conflict_ranges=[(bytes([b // 256, b % 256]),
                                   bytes([(b + span) // 256,
                                          (b + span) % 256]))],
            write_conflict_ranges=[(bytes([wb // 256, wb % 256]),
                                    bytes([wb // 256, wb % 256, 1]))],
            read_snapshot=int(rng.integers(lo, hi)),
            report_conflicting_keys=bool(rng.random() < 0.5),
        )

    rstream = []
    for i in range(6):
        v = base + (i + 1) * step
        rstream.append(([scan_txn(base - 150, v) for _ in range(6)], v))
    r_oracle = CpuConflictSet(oracle_cfg)
    r_want = [r_oracle.resolve(txns, v) for txns, v in rstream]
    range_sets = {
        "sweep+spill": TpuConflictSet(sweep_cfg),
        "sweep-off": TpuConflictSet(probe_cfg),
    }
    for name, cs in range_sets.items():
        for i, (txns, v) in enumerate(rstream):
            got = cs.resolve(txns, v)
            if got.verdicts != r_want[i].verdicts:
                print(f"FAIL range/{name} batch {i}: verdicts "
                      f"{got.verdicts} != {r_want[i].verdicts}")
                failures += 1
            if got.conflicting_key_ranges != r_want[i].conflicting_key_ranges:
                print(f"FAIL range/{name} batch {i}: conflicting ranges "
                      f"{got.conflicting_key_ranges} != "
                      f"{r_want[i].conflicting_key_ranges}")
                failures += 1
    sweep_counters = range_sets["sweep+spill"].metrics.counters
    if sweep_counters.get("sweepGroups") != len(rstream):
        print(f"FAIL range/sweep+spill: sweepGroups "
              f"{sweep_counters.get('sweepGroups')} != {len(rstream)}")
        failures += 1
    if sweep_counters.get("spills") == 0:
        print("FAIL range/sweep+spill: stream was sized to spill but "
              "spills == 0")
        failures += 1
    if sweep_counters.get("exactFallbacks") != 0:
        print(f"FAIL range/sweep+spill: exactFallbacks "
              f"{sweep_counters.get('exactFallbacks')} != 0 — the "
              "no-host-re-dispatch contract")
        failures += 1

    n = len(stream)
    if failures:
        print(f"kernel smoke: {failures} FAILURES")
        return 1
    if args.perf_out:
        _emit_perf_row(args.perf_out, sets, want, tiered,
                       range_sets=range_sets, r_want=r_want)
    print(f"kernel smoke: OK — {len(sets)} kernel paths x {n} batches "
          f"+ {len(range_sets)} range-heavy paths x {len(rstream)} "
          f"batches decision-identical to the oracle "
          f"({time.perf_counter() - t_start:.1f}s)")
    return 0


def _emit_perf_row(path: str, sets: dict, want, tiered_cfg, *,
                   range_sets=None, r_want=None) -> None:
    """The structural ledger row the check.sh perf lane gates on: every
    value is deterministic given the seeded stream and tiny shapes —
    decision counts protect commit/abort parity, the kernel counters
    protect the dispatch/compaction/fallback structure, and the
    merge-row capacities protect the r6 tiered design's working-set
    math. A doubled merge-row count or a flipped verdict here fails
    scripts/perfcheck.py before any hardware ever re-measures."""
    from foundationdb_tpu.models.types import TransactionResult
    from foundationdb_tpu.utils import perf

    nrw = tiered_cfg.max_reads + tiered_cfg.max_writes
    committed = sum(
        sum(1 for v in r.verdicts if v == TransactionResult.COMMITTED)
        for r in want
    )
    conflicted = sum(
        sum(1 for v in r.verdicts if v == TransactionResult.CONFLICT)
        for r in want
    )
    metrics = {
        "committed": perf.metric(committed, "txns", "higher",
                                 tier="structural"),
        "conflicted": perf.metric(conflicted, "txns", "lower",
                                  tier="structural"),
        "merge_rows_tiered_cap": perf.metric(
            tiered_cfg.delta_capacity + 2 * nrw, "rows", "lower",
            tier="structural",
        ),
    }
    tags = {"tiered+dedup": "tiered_dedup",
            "tiered(dedup-latch-fallback)": "dedup_latch"}
    for name, cs in sets.items():
        tag = tags.get(name, name.split("(")[0].replace("+", "_"))
        c = cs.metrics.counters
        metrics[f"{tag}_batches"] = perf.metric(
            c.get("resolveBatches"), "count", "higher", tier="structural"
        )
        metrics[f"{tag}_compactions"] = perf.metric(
            c.get("compactions"), "count", "lower", tier="structural"
        )
        metrics[f"{tag}_fallbacks"] = perf.metric(
            c.get("latchTrips") + c.get("exactFallbacks"), "count",
            "lower", tier="structural",
        )
    paths = sorted(sets)
    if range_sets:
        # ISSUE 14 range-heavy structural row half: oracle decision
        # counts for the wide-scan stream plus the sweep/spill/
        # no-fallback counters — a re-routed probe path or a lost spill
        # fails the exact compare
        from foundationdb_tpu.models.types import TransactionResult as _TR

        metrics["range_committed"] = perf.metric(
            sum(sum(1 for v in r.verdicts if v == _TR.COMMITTED)
                for r in r_want),
            "txns", "higher", tier="structural",
        )
        metrics["range_conflicted"] = perf.metric(
            sum(sum(1 for v in r.verdicts if v == _TR.CONFLICT)
                for r in r_want),
            "txns", "lower", tier="structural",
        )
        c = range_sets["sweep+spill"].metrics.counters
        metrics["range_sweep_groups"] = perf.metric(
            c.get("sweepGroups"), "count", "higher", tier="structural"
        )
        metrics["range_spills"] = perf.metric(
            c.get("spills"), "count", "higher", tier="structural"
        )
        metrics["range_exact_fallbacks"] = perf.metric(
            c.get("exactFallbacks"), "count", "lower", tier="structural"
        )
        paths = paths + [f"range:{n}" for n in sorted(range_sets)]
    rec = perf.make_record(
        "kernel_smoke", metrics,
        workload={"batches": len(want), "txns_per_batch": 6,
                  "paths": paths},
        knobs={"delta_capacity": tiered_cfg.delta_capacity,
               "dedup_reads": tiered_cfg.dedup_reads,
               "compact_interval": tiered_cfg.compact_interval},
        # structural rows compare across hosts by design: the
        # fingerprint records WHERE the row came from, the comparator
        # keys on (source, workload, knobs) only
    )
    perf.append(rec, path=path)
    print(f"kernel smoke: structural perf row -> {path}")


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Batch-size sweep: where does the TPU resolver actually beat the CPU?

VERDICT r4 task 3: the RESOLVER_TPU_MIN_BATCH routing knob was a guess
(8192) that the build's own small-batch numbers contradicted. This
sweep measures, per batch size 512..65536: device p50 (inputs resident),
device p50 including the host->device transfer, and the CPU skiplist
p50 on identical batches — then prints the measured crossover. The knob
default derives from THIS table (see utils/knobs.py), and
tests/test_routing_crossover.py pins the decision.

Run on the real device: `python scripts/sweep_small.py`.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from foundationdb_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

import jax  # noqa: E402

from foundationdb_tpu.config import KernelConfig  # noqa: E402
from foundationdb_tpu.models.conflict_set import TpuConflictSet  # noqa: E402
from foundationdb_tpu.native import NativeSkipListConflictSet  # noqa: E402
from foundationdb_tpu.testing.benchgen import (  # noqa: E402
    flatten_for_native,
    skiplist_style_batch,
)




SIZES = [int(x) for x in os.environ.get('SWEEP_SIZES', '512,2048,8192,16384,32768,65536').split(',')]
WINDOW = 1_000_000
VERSION_STEP = 200_000


def main():
    print(f"devices: {jax.devices()}", file=sys.stderr, flush=True)
    rows = []
    for n in SIZES:
        cap = max(4096, 1 << (n - 1).bit_length())
        # history sizing: 12*cap, EXCEPT m=393216 (12*32768) — that
        # exact shape trips the flat-gather miscompile guard on this
        # libtpu (the selftest correctly refuses); the next known-good
        # size 786432 is used instead (larger history never hurts)
        hist = 12 * cap if 12 * cap != 393216 else 786432
        cfg = KernelConfig(
            max_key_bytes=8, max_txns=cap, max_reads=cap, max_writes=cap,
            history_capacity=hist, window_versions=WINDOW,
        )
        rng = np.random.default_rng(1)
        batches = [
            skiplist_style_batch(
                rng, cfg, n, version=(i + 1) * VERSION_STEP, key_bytes=8,
                snapshot_lag=2 * VERSION_STEP, keyspace=1_000_000,
            )
            for i in range(10)
        ]
        m_ = lambda xs: sorted(xs[1:])[len(xs[1:]) // 2]

        # device, inputs resident
        cs = TpuConflictSet(cfg)
        dev = [jax.device_put(b.device_args()) for b in batches]
        jax.block_until_ready(dev)
        lat_d = []
        for db in dev:
            t0 = time.perf_counter()
            np.asarray(cs.resolve_args(db).verdict)  # honest fence
            lat_d.append(time.perf_counter() - t0)

        # device, transfer included
        cs2 = TpuConflictSet(cfg)
        lat_t = []
        for b in batches:
            t0 = time.perf_counter()
            np.asarray(cs2.resolve_packed(b).verdict)
            lat_t.append(time.perf_counter() - t0)

        # CPU skiplist
        cpu = NativeSkipListConflictSet(window=WINDOW)
        flats = [(flatten_for_native(b, "r"), flatten_for_native(b, "w"))
                 for b in batches]
        lat_c = []
        for b, ((rk, ro, rt), (wk, wo, wt)) in zip(batches, flats):
            t0 = time.perf_counter()
            cpu.resolve_raw(
                int(b.version), b.snapshot[:n].astype(np.int64),
                rk, ro, rt, wk, wo, wt,
            )
            lat_c.append(time.perf_counter() - t0)

        row = {
            "n": n,
            "device_p50_ms": round(m_(lat_d) * 1e3, 2),
            "device_incl_transfer_p50_ms": round(m_(lat_t) * 1e3, 2),
            "cpu_skiplist_p50_ms": round(m_(lat_c) * 1e3, 2),
        }
        row["device_txn_s"] = round(n / (row["device_p50_ms"] / 1e3))
        row["device_incl_transfer_txn_s"] = round(
            n / (row["device_incl_transfer_p50_ms"] / 1e3))
        row["cpu_txn_s"] = round(n / (row["cpu_skiplist_p50_ms"] / 1e3))
        rows.append(row)
        print(json.dumps(row), flush=True)

    cross = next(
        (r["n"] for r in rows if r["device_txn_s"] > r["cpu_txn_s"]), None
    )
    # Both crossovers print; the knob (utils/knobs.py) pins the
    # RESIDENT one deliberately: (a) the TPU resolver's operating mode
    # is GROUPED dispatch with double-buffered staging
    # (TpuConflictSet.resolve_group_stream), which overlaps the copy
    # with compute, and (b) a single-shot host->device hop is a bound a
    # grouped, staged resolver does not pay per batch. The
    # transfer-inclusive number is that single-shot bound and ships in
    # the log for exactly that comparison.
    cross_t = next(
        (r["n"] for r in rows
         if r["device_incl_transfer_txn_s"] > r["cpu_txn_s"]), None
    )
    print(json.dumps({
        "crossover_n_resident": cross,
        "crossover_n_incl_transfer": cross_t,
        "rows": rows,
    }))


if __name__ == "__main__":
    main()

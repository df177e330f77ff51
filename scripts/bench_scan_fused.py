#!/usr/bin/env python
"""Scan-fused vs sequential dispatch at bench shapes, on the real device.

(Previously misnamed scripts/probe_scan.py — that name now belongs to
the CODE_PROBE accounting CLI over foundationdb_tpu/analysis.)
"""

import time

import jax
import numpy as np

from foundationdb_tpu.utils import compile_cache

compile_cache.enable()

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models.conflict_set import TpuConflictSet
from foundationdb_tpu.testing.benchgen import skiplist_style_batch

N = 65536
cap = N
config = KernelConfig(
    max_key_bytes=8, max_txns=cap, max_reads=cap, max_writes=cap,
    history_capacity=12 * cap, window_versions=1_000_000,
)
rng = np.random.default_rng(0)
batches = [
    skiplist_style_batch(
        rng, config, N, version=(i + 1) * 200_000, keyspace=1_000_000,
        key_bytes=8, snapshot_lag=400_000,
    )
    for i in range(8)
]
print("generated", flush=True)

dev = [jax.device_put(b.device_args()) for b in batches]
jax.block_until_ready(dev)

# sequential
cs = TpuConflictSet(config)
outs = [cs.resolve_args(d) for d in dev[:2]]  # warm
jax.block_until_ready(outs[-1].verdict)
cs = TpuConflictSet(config)
t0 = time.perf_counter()
outs = [cs.resolve_args(d) for d in dev]
jax.block_until_ready(outs[-1].verdict)
seq = time.perf_counter() - t0
print(f"sequential: {seq*1e3:.0f}ms total, {seq/8*1e3:.0f}ms/batch, "
      f"{N*8/seq:,.0f} txn/s", flush=True)

# fused groups of 4
from foundationdb_tpu.utils.packing import stack_device_args

groups = [
    jax.device_put(stack_device_args(batches[g:g + 4]))
    for g in range(0, 8, 4)
]
jax.block_until_ready(groups)
warm = TpuConflictSet(config)
warm.resolve_group_args(groups[0])
jax.block_until_ready(warm.state)
cs2 = TpuConflictSet(config)
t0 = time.perf_counter()
fouts = [cs2.resolve_group_args(g) for g in groups]
jax.block_until_ready(fouts[-1].verdict)
fus = time.perf_counter() - t0
print(f"fused x4:   {fus*1e3:.0f}ms total, {fus/8*1e3:.0f}ms/batch, "
      f"{N*8/fus:,.0f} txn/s", flush=True)

for i in (0, 3, 7):
    a = np.asarray(outs[i].verdict)
    b = np.asarray(fouts[i // 4].verdict[i % 4])
    assert (a == b).all(), i
print("parity ok", flush=True)

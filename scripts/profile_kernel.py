#!/usr/bin/env python
"""Stage-by-stage timing of the v2 conflict kernel at bench shapes.

Times each stage of ops.conflict.resolve_batch in isolation on the
current default device:
  full kernel | sort_ranks | history query | merge_writes |
  intra iteration (sparse cover + rmq build + query)

Note: single-op timings of small ops are dominated by the dispatch round
trip — treat sub-10ms readings as suspect and re-check with
serialized-in-jit timing (scripts/experiments.py style).
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.ops import conflict as C
from foundationdb_tpu.ops import history as H
from foundationdb_tpu.ops import keys as K
from foundationdb_tpu.ops import rangemax, segtree
from foundationdb_tpu.ops.rangemax import INT32_POS
from foundationdb_tpu.testing.benchgen import skiplist_style_batch

N = int(sys.argv[1]) if len(sys.argv) > 1 else 65536
REPS = 5


def timeit(name, fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / REPS
    print(f"{name:38s} {dt * 1e3:9.2f} ms   (compile {compile_s:5.1f}s)",
          flush=True)
    return out


def main():
    print("device:", jax.devices()[0])
    cap = 1 << (N - 1).bit_length()
    config = KernelConfig(
        max_key_bytes=8, max_txns=cap, max_reads=cap, max_writes=cap,
        history_capacity=12 * cap, window_versions=1_000_000,
    )
    rng = np.random.default_rng(0)
    batch = skiplist_style_batch(
        rng, config, N, version=1_200_000, keyspace=1_000_000, key_bytes=8,
        snapshot_lag=400_000,
    ).device_args()
    batch = jax.device_put(batch)
    state = jax.device_put(H.init(config))
    step = jax.jit(C.resolve_batch)
    for i in range(5):  # reach steady-state history
        b2 = skiplist_style_batch(
            rng, config, N, version=200_000 * (i + 1), keyspace=1_000_000,
            key_bytes=8, snapshot_lag=400_000,
        ).device_args()
        state, _ = step(state, b2)
    jax.block_until_ready(state)

    nr = batch["read_valid"].shape[0]
    nw = batch["write_valid"].shape[0]

    st2 = jax.tree.map(jnp.copy, state)
    timeit("FULL resolve_batch", step, st2, batch)

    points = jnp.concatenate(
        [batch["read_begin"], batch["read_end"],
         batch["write_begin"], batch["write_end"]], axis=0)
    pt_valid = jnp.concatenate(
        [batch["read_valid"], batch["read_valid"],
         batch["write_valid"], batch["write_valid"]])
    ranks, ukeys, _ = timeit(
        "sort_ranks", jax.jit(K.sort_ranks), points, pt_valid
    )

    snap = batch["snapshot"][batch["read_txn"]]
    timeit("history query", jax.jit(H.query_reads),
           state, batch["read_begin"], batch["read_end"], snap)

    run_bounds = K.sentinel_like(2 * nw, config.key_words)
    timeit("merge_writes", jax.jit(H.merge_writes),
           jax.tree.map(jnp.copy, state), run_bounds,
           jnp.int32(1_200_000), jnp.int32(200_000))

    leaves = 1 << int(np.ceil(np.log2(points.shape[0])))
    rb_rank, re_rank = ranks[:nr], ranks[nr:2 * nr]
    wb_rank = ranks[2 * nr:2 * nr + nw]
    we_rank = ranks[2 * nr + nw:]
    wl = batch["write_valid"]
    write_txn = batch["write_txn"]
    read_txn = batch["read_txn"]

    def intra_once(committed):
        writer = jnp.where(committed[write_txn] & wl, write_txn, INT32_POS)
        mw = segtree.min_cover(leaves, jnp.where(wl, wb_rank, 0),
                               jnp.where(wl, we_rank, 0), writer)
        mintab = rangemax.build(mw, op="min")
        min_writer = rangemax.query(mintab, rb_rank, re_rank, op="min")
        return (min_writer < read_txn) & batch["read_valid"]

    timeit("intra iteration", jax.jit(intra_once), batch["txn_valid"])


if __name__ == "__main__":
    main()

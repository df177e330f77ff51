#!/usr/bin/env python
"""True per-stage costs via serialized-in-jit chaining.

Single-op block_until_ready timings of small ops are dominated by the
dispatch round trip. This harness times each stage by
running it K times inside ONE jit with a forced data dependency between
iterations (lax.fori_loop carry), so device time dominates and the
per-iteration cost is total/K.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from foundationdb_tpu.utils import compile_cache

compile_cache.enable()

from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.ops import conflict as C
from foundationdb_tpu.ops import history as H
from foundationdb_tpu.ops import keys as K
from foundationdb_tpu.ops import rangemax, segtree
from foundationdb_tpu.ops.rangemax import INT32_POS
from foundationdb_tpu.testing.benchgen import skiplist_style_batch

N = int(sys.argv[1]) if len(sys.argv) > 1 else 65536
REPS = int(sys.argv[2]) if len(sys.argv) > 2 else 8


def timeit(name, fn, *args):
    f = jax.jit(fn)
    t0 = time.perf_counter()
    out = f(*args)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = f(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / REPS
    print(f"{name:44s} {dt * 1e3:9.2f} ms/iter  (first+compile {compile_s:5.1f}s)",
          flush=True)


def main():
    print(f"device: {jax.devices()[0]}  N={N}  REPS={REPS}", flush=True)
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
    for i in range(5):
        b2 = skiplist_style_batch(
            rng, config, N, version=200_000 * (i + 1), keyspace=1_000_000,
            key_bytes=8, snapshot_lag=400_000,
        ).device_args()
        state, _ = step(state, b2)
    jax.block_until_ready(state)

    nr = batch["read_valid"].shape[0]
    nw = batch["write_valid"].shape[0]
    w = config.key_words

    # ---- full kernel chained REPS times --------------------------------
    def full_chain(state, batch):
        def body(i, st):
            st2, out = C.resolve_batch(st, batch)
            # dependency: fold a verdict bit into the carry so nothing DCEs
            return st2._replace(oldest=st2.oldest | (out.verdict[0] & 1))
        return jax.lax.fori_loop(0, REPS, body, state)

    timeit("FULL resolve_batch", full_chain,
           jax.tree.map(jnp.copy, state), batch)

    points = jnp.concatenate(
        [batch["read_begin"], batch["read_end"],
         batch["write_begin"], batch["write_end"]], axis=0)
    pt_valid = jnp.concatenate(
        [batch["read_valid"], batch["read_valid"],
         batch["write_valid"], batch["write_valid"]])

    # ---- sort_ranks chained --------------------------------------------
    def sort_chain(points, pt_valid):
        def body(i, pts):
            ranks, ukeys, ucount = K.sort_ranks(pts, pt_valid)
            # feed ranks back into the low word so the next sort depends
            return pts.at[:, w - 1].set(
                pts[:, w - 1] ^ (ranks.astype(jnp.uint32) & 1))
        return jax.lax.fori_loop(0, REPS, body, points)

    timeit("sort_ranks (262K x w keys)", sort_chain, points, pt_valid)

    # ---- history query chained -----------------------------------------
    snap = batch["snapshot"][batch["read_txn"]]

    def query_chain(state, rb, re, snap):
        def body(i, carry):
            rb_, acc = carry
            hit = H.query_reads(state, rb_, re, snap)
            rb2 = rb_.at[:, w - 1].set(rb_[:, w - 1] ^ hit.astype(jnp.uint32))
            return rb2, acc + jnp.sum(hit)
        out = jax.lax.fori_loop(
            0, REPS, body, (rb, jnp.int32(0)))
        return out[1]

    timeit("history.query_reads (64K q, 655K m)", query_chain,
           state, batch["read_begin"], batch["read_end"], snap)

    # ---- merge_writes chained ------------------------------------------
    run_bounds = jnp.concatenate(
        [batch["write_begin"][: 2 * nw // 2], batch["write_end"][: 2 * nw // 2]]
    )

    def merge_chain(state, run_bounds):
        def body(i, st):
            return H.merge_writes(
                st, run_bounds, jnp.int32(1_200_000) + i, jnp.int32(200_000) + i)
        return jax.lax.fori_loop(0, REPS, body, state)

    timeit("history.merge_writes (655K+131K)", merge_chain,
           jax.tree.map(jnp.copy, state), run_bounds)

    # ---- one intra iteration chained -----------------------------------
    ranks, _uk, _uc = K.sort_ranks(points, pt_valid)
    rb_rank, re_rank = ranks[:nr], ranks[nr:2 * nr]
    wb_rank = ranks[2 * nr:2 * nr + nw]
    we_rank = ranks[2 * nr + nw:]
    leaves = 1 << int(np.ceil(np.log2(points.shape[0])))
    wl = batch["write_valid"]
    write_txn = batch["write_txn"]
    read_txn = batch["read_txn"]
    b = batch["txn_valid"].shape[0]

    def intra_chain(committed0):
        def body(i, committed):
            writer = jnp.where(committed[write_txn] & wl, write_txn, INT32_POS)
            mw = segtree.min_cover(
                leaves, jnp.where(wl, wb_rank, 0), jnp.where(wl, we_rank, 0),
                writer)
            mintab = rangemax.build(mw, op="min")
            min_writer = rangemax.query(mintab, rb_rank, re_rank, op="min")
            hits = (min_writer < read_txn) & batch["read_valid"]
            per_txn = (
                jnp.zeros((b + 1,), jnp.int32)
                .at[jnp.where(batch["read_valid"], read_txn, b)]
                .max(hits.astype(jnp.int32))[:b]) > 0
            return committed & ~per_txn | (i % 7 == 6)  # live use, non-CSE
        return jax.lax.fori_loop(0, REPS, body, batch["txn_valid"])

    timeit("intra iteration (cover+build+query)", intra_chain,
           batch["txn_valid"])

    # ---- micro: the three pieces of an intra iteration -----------------
    writer0 = jnp.where(wl, write_txn, INT32_POS)

    def cover_chain(val):
        def body(i, v):
            mw = segtree.min_cover(
                leaves, jnp.where(wl, wb_rank, 0), jnp.where(wl, we_rank, 0), v)
            return v ^ (mw[:nw] & 1)

        return jax.lax.fori_loop(0, REPS, body, val)

    timeit("  segtree.min_cover (131K upd, 262K lv)", cover_chain, writer0)

    ver = state.main_ver

    def build_chain(v):
        def body(i, x):
            tab = rangemax.build(x, op="max")
            return x ^ (tab[-1] & 1)
        return jax.lax.fori_loop(0, REPS, body, ver)

    timeit("  rangemax.build (655K)", build_chain, ver)

    def build_chain_262(v):
        def body(i, x):
            tab = rangemax.build(x, op="min")
            return x ^ (tab[-1] & 1)
        return jax.lax.fori_loop(0, REPS, body, ver[: leaves])

    timeit("  rangemax.build (262K)", build_chain_262, ver)

    def rquery_chain(tab, a, bq):
        def body(i, carry):
            a_, acc = carry
            r = rangemax.query(tab, a_, bq, op="max")
            return a_ ^ (r & 1), acc + jnp.sum(r)
        return jax.lax.fori_loop(0, REPS, body, (a, jnp.int32(0)))[1]

    tab = rangemax.build(ver, op="max")
    ql = jnp.asarray(np.random.default_rng(1).integers(
        0, 655000, size=nr), jnp.int32)
    timeit("  rangemax.query (64K q over 655K)", rquery_chain, tab, ql,
           ql + 50)

    # ---- micro: searchsorted alone -------------------------------------
    def ss_chain(mk, q):
        def body(i, carry):
            q_, acc = carry
            r = K.searchsorted(mk, q_, side="right")
            q2 = q_.at[:, w - 1].set(q_[:, w - 1] ^ (r.astype(jnp.uint32) & 1))
            return q2, acc + jnp.sum(r)
        return jax.lax.fori_loop(0, REPS, body, (q, jnp.int32(0)))[1]

    timeit("  searchsorted (64K q over 655K)", ss_chain,
           state.main_keys, batch["read_begin"])


if __name__ == "__main__":
    main()

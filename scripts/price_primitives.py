#!/usr/bin/env python
"""Price candidate kernel primitives at bench shapes on the live TPU.

Methodology: each primitive is chained R times inside ONE jitted
fori_loop with data dependencies between iterations, so XLA cannot
dead-code or overlap the work, and the per-call dispatch cost amortizes
out. Report
(total - baseline_dispatch) / R.

Shapes priced for the round-3 kernel redesign decision:
  - lax.sort at merge/group shapes x operand counts
  - searchsorted: queries vs a large sorted array, argument vs donated
  - the [reads x G] grid probe (every read binary-searches G slot arrays)
  - segtree.min_cover at group leaf counts
  - rangemax.build at group sizes
  - cumsum / associative scan at merge sizes
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")
from foundationdb_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

from foundationdb_tpu.ops import keys as K  # noqa: E402
from foundationdb_tpu.ops import rangemax, segtree  # noqa: E402

REPS = 16


def _force(out):
    """Fence on a device->host transfer of the tiny carry."""
    return np.asarray(jax.tree_util.tree_leaves(out)[0])


def timed(name, fn, *args):
    jfn = jax.jit(fn)
    _force(jfn(*args))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _force(jfn(*args))
        best = min(best, time.perf_counter() - t0)
    per = (best * 1e3) / REPS
    print(f"{name:55s} {per:8.3f} ms/rep  ({best*1e3:7.1f} ms total)",
          flush=True)
    return per


def chain(fn):
    """Wrap fn(x, salt) -> x' in a REPS-long fori_loop chain."""

    def run(x0, *rest):
        def body(i, x):
            return fn(x, i, *rest)

        return jax.lax.fori_loop(0, REPS, body, x0)

    return run


def main():
    rng = np.random.default_rng(0)
    print(f"devices: {jax.devices()}", flush=True)

    # ---- dispatch baseline (empty chain) ----
    def nop(x, i):
        return x + i

    timed("dispatch+trivial chain", chain(nop), jnp.zeros((8,), jnp.int32))

    # ---- lax.sort at candidate shapes ----
    for rows, ops_n in [(917_504, 4), (1_835_008, 4), (2_097_152, 3),
                        (2_097_152, 4), (3_145_728, 4)]:
        cols = [jnp.array(rng.integers(0, 2**31, rows, dtype=np.int64),
                          jnp.uint32) for _ in range(ops_n)]

        def dosort(x, i, *cols):
            # salt the first key column with the carry so iterations chain
            c0 = cols[0] ^ x[0]
            s = jax.lax.sort([c0] + list(cols[1:]), num_keys=2)
            return x.at[0].set(s[0][0] ^ s[1][rows // 2])

        timed(f"lax.sort rows={rows} ops={ops_n}", chain(dosort),
              jnp.zeros((8,), jnp.uint32), *cols)

    # ---- searchsorted: Q queries vs sorted M rows (argument) ----
    w = 3
    m = 786_432
    sorted_keys = np.sort(
        rng.integers(0, 2**31, (m,), dtype=np.int64).astype(np.uint32))
    main_keys = np.zeros((m, w), np.uint32)
    main_keys[:, 0] = sorted_keys
    main_keys[:, 2] = 8
    for q in (131_072, 524_288):
        queries = np.zeros((q, w), np.uint32)
        queries[:, 0] = rng.integers(0, 2**31, (q,)).astype(np.uint32)
        queries[:, 2] = 8
        mk, qk = jnp.asarray(main_keys), jnp.asarray(queries)

        def dosearch(x, i, mk, qk):
            qq = qk.at[:, 1].set(x[0] + i)
            r = K.searchsorted(mk, qq, side="right")
            return x.at[0].set(r[0] + r[q // 2])

        timed(f"searchsorted Q={q} M={m} (argument)", chain(dosearch),
              jnp.zeros((8,), jnp.int32), mk, qk)

    # donated variant: state-style buffer donated through the chain
    q = 524_288
    queries = np.zeros((q, w), np.uint32)
    queries[:, 0] = rng.integers(0, 2**31, (q,)).astype(np.uint32)
    qk = jnp.asarray(queries)

    def dosearch_carried(carry, i, qk):
        mk, acc = carry
        qq = qk.at[:, 1].set(acc[0] + i)
        r = K.searchsorted(mk, qq, side="right")
        # touch mk so it stays in the carry
        mk = mk.at[0, 1].set(r[0].astype(jnp.uint32))
        return (mk, acc.at[0].set(r[q // 2]))

    def run_carried(mk, acc, qk):
        def body(i, c):
            return dosearch_carried(c, i, qk)

        return jax.lax.fori_loop(0, REPS, body, (mk, acc))

    timed(f"searchsorted Q={q} M={m} (scan-carried state)", run_carried,
          jnp.asarray(main_keys), jnp.zeros((8,), jnp.int32), qk)

    # ---- grid probe: Q reads x G slots, binary search each slot ----
    g_slots = 8
    slot_m = 131_072
    slots = np.sort(
        rng.integers(0, 2**31, (g_slots, slot_m), dtype=np.int64)
        .astype(np.uint32), axis=1)
    slots3 = np.zeros((g_slots, slot_m, w), np.uint32)
    slots3[:, :, 0] = slots
    slots3[:, :, 2] = 8
    for q in (524_288,):
        queries = np.zeros((q, w), np.uint32)
        queries[:, 0] = rng.integers(0, 2**31, (q,)).astype(np.uint32)
        queries[:, 2] = 8
        sl, qk = jnp.asarray(slots3), jnp.asarray(queries)

        def dogrid(x, i, sl, qk):
            qq = qk.at[:, 1].set(x[0] + i)
            tot = jnp.zeros((q,), jnp.int32)
            for j in range(g_slots):
                tot = tot + K.searchsorted(sl[j], qq, side="right")
            return x.at[0].set(tot[0] + tot[q // 2])

        timed(f"grid probe Q={q} x {g_slots} slots of {slot_m}",
              chain(dogrid), jnp.zeros((8,), jnp.int32), sl, qk)

    # ---- min_cover at group leaves ----
    for leaves, n_upd in [(524_288, 131_072), (4_194_304, 1_048_576)]:
        lo = rng.integers(0, leaves - 1, (n_upd,)).astype(np.int32)
        ln = rng.integers(1, 16, (n_upd,)).astype(np.int32)
        hi = np.minimum(lo + ln, leaves).astype(np.int32)
        val = rng.integers(0, 2**20, (n_upd,)).astype(np.int32)
        lo_, hi_, val_ = map(jnp.asarray, (lo, hi, val))

        def docover(x, i, lo_, hi_, val_):
            out = segtree.min_cover(leaves, lo_, hi_, val_ + x[0])
            return x.at[0].set(out[0] + out[leaves // 2])

        timed(f"min_cover leaves={leaves} n={n_upd}", chain(docover),
              jnp.zeros((8,), jnp.int32), lo_, hi_, val_)

    # ---- rangemax.build ----
    for mm in (786_432, 2_097_152, 4_194_304):
        vals = jnp.asarray(rng.integers(0, 2**20, (mm,)).astype(np.int32))

        def dobuild(x, i, vals):
            t = rangemax.build(vals + x[0], op="max")
            return x.at[0].set(t[0, 0] + t[-1, mm // 2])

        timed(f"rangemax.build M={mm}", chain(dobuild),
              jnp.zeros((8,), jnp.int32), vals)

    # ---- cumsum at merge sizes ----
    for mm in (917_504, 1_835_008, 4_194_304):
        vals = jnp.asarray(rng.integers(0, 3, (mm,)).astype(np.int32))

        def docum(x, i, vals):
            c = jnp.cumsum(vals + x[0])
            return x.at[0].set(c[0] + c[mm - 1])

        timed(f"cumsum M={mm}", chain(docum),
              jnp.zeros((8,), jnp.int32), vals)


if __name__ == "__main__":
    main()

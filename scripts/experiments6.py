#!/usr/bin/env python
"""Round-5 pricing: per-op fixed overhead, radix-4 table variants,
batched gathers/scatters, and the per-dispatch cost.

Hypothesis under test (from the r4/r5 ablation ledgers): the fixpoint's
~45ms/group per application is FIXED PER-OP OVERHEAD x ~55 small ops,
not bandwidth — in which case the lever is op COUNT (higher-radix
doubling structures, single batched gathers/scatters), not array size.

Methodology: scripts/price_primitives.py — every candidate chained R
times inside one jitted fori_loop with data dependencies, honest
device->host fence, (total - baseline) / R.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")
from foundationdb_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

from foundationdb_tpu.ops import rangemax, segtree  # noqa: E402
from foundationdb_tpu.ops.rangemax import INT32_POS, _floor_log2  # noqa: E402

REPS = 16


def _force(out):
    return np.asarray(jax.tree_util.tree_leaves(out)[0])


def timed(name, fn, *args):
    jfn = jax.jit(fn)
    _force(jfn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _force(jfn(*args))
        best = min(best, time.perf_counter() - t0)
    per = (best * 1e3) / REPS
    print(f"{name:58s} {per:8.3f} ms/rep  ({best*1e3:7.1f} ms total)",
          flush=True)
    return per


def chain(step):
    """step(x, i) -> x, chained REPS times in a fori_loop."""

    def run(x0, *rest):
        def body(i, x):
            return step(x, i, *rest)

        return jax.lax.fori_loop(0, REPS, body, x0)

    return run


# ---------------------------------------------------------------------------
# radix-4 prototypes

def build4(values, *, op="max"):
    fn = rangemax._OPS[op][0]
    m = values.shape[0]
    levels = [values]
    k = 1
    while (1 << (2 * (k - 1))) < m:  # span 4^(k-1) < m
        prev = levels[-1]
        s = min(1 << (2 * (k - 1)), m - 1)
        parts = [prev]
        for j in (1, 2, 3):
            sh = min(j * s, m - 1)
            parts.append(jnp.concatenate(
                [prev[sh:], jnp.broadcast_to(prev[-1:], (sh,))]))
        out = parts[0]
        for p in parts[1:]:
            out = fn(out, p)
        levels.append(out)
        k += 1
    return jnp.stack(levels)


def query4(table, lo, hi, *, op="max"):
    levels, m = table.shape
    fn, ident_v = rangemax._OPS[op]
    ident = jnp.int32(ident_v)
    loc = jnp.clip(lo, 0, m)
    hic = jnp.clip(hi, 0, m)
    length = jnp.maximum(hic - loc, 1)
    k2 = _floor_log2(length, 2 * levels)
    k = k2 >> 1                      # floor(log4)
    s = jnp.left_shift(jnp.int32(1), 2 * k)
    flat = table.reshape(-1)
    idxs = []
    for j in range(4):
        p = jnp.minimum(loc + j * s, hic - s)
        idxs.append(k * m + jnp.clip(p, 0, m - 1))
    g = flat[jnp.concatenate(idxs)].reshape(4, -1)
    out = fn(fn(g[0], g[1]), fn(g[2], g[3]))
    return jnp.where(hic > loc, out, ident)


def query2_batched(table, lo, hi, *, op="max"):
    """radix-2 query with the two gathers fused into one."""
    levels, m = table.shape
    fn, ident_v = rangemax._OPS[op]
    ident = jnp.int32(ident_v)
    loc = jnp.clip(lo, 0, m)
    hic = jnp.clip(hi, 0, m)
    length = jnp.maximum(hic - loc, 1)
    k = _floor_log2(length, levels)
    a = jnp.clip(loc, 0, m - 1)
    b = jnp.clip(hic - (1 << k), 0, m - 1)
    flat = table.reshape(-1)
    g = flat[jnp.concatenate([k * m + a, k * m + b])].reshape(2, -1)
    return jnp.where(hic > loc, fn(g[0], g[1]), ident)


def min_cover4(leaves, lo, hi, val):
    assert leaves & (leaves - 1) == 0
    log2l = leaves.bit_length() - 1
    nlev = (log2l + 1) // 2 + 1      # spans 4^0 .. 4^floor(log2/2)
    lo = jnp.clip(lo, 0, leaves)
    hi = jnp.clip(hi, 0, leaves)
    length = hi - lo
    k2 = _floor_log2(jnp.maximum(length, 1), 2 * nlev)
    k = jnp.minimum(k2 >> 1, nlev - 1)
    s = jnp.left_shift(jnp.int32(1), 2 * k)
    valid = length > 0
    k_idx = jnp.where(valid, k, nlev)
    idxs = []
    for j in range(4):
        p = jnp.minimum(lo + j * s, hi - s)
        idxs.append(k_idx * leaves + jnp.where(valid, p, 0))
    table = (
        jnp.full(((nlev + 1) * leaves,), INT32_POS, jnp.int32)
        .at[jnp.concatenate(idxs)].min(jnp.tile(val, 4))
        .reshape(nlev + 1, leaves)
    )
    t = table[:nlev]
    out = t[nlev - 1]
    for j in range(nlev - 1, 0, -1):
        s_ = 1 << (2 * (j - 1))
        acc = jnp.minimum(t[j - 1], out)
        for c in (1, 2, 3):
            sh = c * s_
            acc = jnp.minimum(acc, jnp.concatenate(
                [jnp.full((sh,), INT32_POS, jnp.int32), out[:-sh]]))
        out = acc
    return out


def main():
    print(f"devices: {jax.devices()}", flush=True)

    # ---- 1. per-op fixed overhead at widths -------------------------------
    for width in (8192, 65536, 262144, 786432, 2883584):
        x = jnp.arange(width, dtype=jnp.int32)

        def step(x, i):
            return x * 3 + i.astype(jnp.int32)

        base = timed(f"1 elementwise op @ {width}", chain(step), x)

        def step8(x, i):
            for _ in range(8):
                x = x * 3 + i.astype(jnp.int32)
            return x

        t8 = timed(f"8 elementwise ops @ {width}", chain(step8), x)
        print(f"  -> marginal per op @ {width}: {(t8 - base) / 7:.4f} ms",
              flush=True)

    # ---- 1b. unfused ops (shift-concat pattern, defeats fusion) -----------
    for width in (262144, 2883584):
        x = jnp.arange(width, dtype=jnp.int32)

        def stepc(x, i):
            for sh in (1, 2, 4, 8, 16, 32, 64, 128):
                x = jnp.minimum(x, jnp.concatenate(
                    [x[sh:], jnp.full((sh,), INT32_POS, jnp.int32)]))
            return x + i.astype(jnp.int32)

        t = timed(f"8 shift-concat-min passes @ {width}", chain(stepc), x)
        print(f"  -> per pass @ {width}: {t / 8:.4f} ms", flush=True)

    # ---- 2. build variants @ 262144 --------------------------------------
    leaves = 262144
    vals = jnp.asarray(
        np.random.default_rng(0).integers(0, 1 << 30, leaves), jnp.int32)

    def b2(x, i):
        t = rangemax.build(x, op="max")
        return t[0] + i.astype(jnp.int32)

    def b4(x, i):
        t = build4(x, op="max")
        return t[0] + i.astype(jnp.int32)

    def b22(x, i):
        f, c = rangemax.build2(x, op="max")
        return f[0][:leaves] + c[0][: 0] .sum() + i.astype(jnp.int32)

    timed("rangemax.build  radix-2 @ 262144 (19 lvls)", chain(b2), vals)
    timed("build4          radix-4 @ 262144 (10 lvls)", chain(b4), vals)
    timed("rangemax.build2 chunked @ 262144", chain(b22), vals)

    # ---- 3. query variants: 65536 queries over 262144 ---------------------
    rng = np.random.default_rng(1)
    q = 65536
    qlo = jnp.asarray(rng.integers(0, leaves - 1, q), jnp.int32)
    qlen = jnp.asarray(rng.integers(1, 64, q), jnp.int32)
    qhi = jnp.minimum(qlo + qlen, leaves)
    tab2 = jax.jit(lambda v: rangemax.build(v, op="max"))(vals)
    tab4 = jax.jit(lambda v: build4(v, op="max"))(vals)

    def mk_query(tab, qfn):
        def step(x, i, lo, hi):
            r = qfn(tab, lo + 0 * x[:1], hi, op="max")
            return x + r[:1]

        return step

    timed("query  radix-2 (2 gathers) 64K q", chain(mk_query(tab2, rangemax.query)), jnp.zeros((q,), jnp.int32), qlo, qhi)
    timed("query  radix-2 BATCHED (1 gather) 64K q", chain(mk_query(tab2, query2_batched)), jnp.zeros((q,), jnp.int32), qlo, qhi)
    timed("query4 radix-4 BATCHED (1 gather) 64K q", chain(mk_query(tab4, query4)), jnp.zeros((q,), jnp.int32), qlo, qhi)

    # ---- 4. min_cover variants @ 262144 leaves, 65536 intervals -----------
    ilo = jnp.asarray(rng.integers(0, leaves - 64, q), jnp.int32)
    ilen = jnp.asarray(rng.integers(1, 64, q), jnp.int32)
    ihi = jnp.minimum(ilo + ilen, leaves)
    ival = jnp.asarray(rng.integers(0, q, q), jnp.int32)

    def mc2(x, i, lo, hi, v):
        out = segtree.min_cover(leaves, lo + 0 * x[:1], hi, v)
        return x + out[:1]

    def mc4(x, i, lo, hi, v):
        out = min_cover4(leaves, lo + 0 * x[:1], hi, v)
        return x + out[:1]

    timed("min_cover  radix-2 @ 262144", chain(mc2), jnp.zeros((q,), jnp.int32), ilo, ihi, ival)
    timed("min_cover4 radix-4 @ 262144", chain(mc4), jnp.zeros((q,), jnp.int32), ilo, ihi, ival)

    # parity spot-check of the radix-4 prototypes
    got2 = np.asarray(jax.jit(
        lambda lo, hi: rangemax.query(tab2, lo, hi, op="max"))(qlo, qhi))
    got4 = np.asarray(jax.jit(
        lambda lo, hi: query4(tab4, lo, hi, op="max"))(qlo, qhi))
    assert (got2 == got4).all(), "query4 parity FAILED"
    c2 = np.asarray(jax.jit(
        lambda lo, hi, v: segtree.min_cover(leaves, lo, hi, v))(ilo, ihi, ival))
    c4 = np.asarray(jax.jit(
        lambda lo, hi, v: min_cover4(leaves, lo, hi, v))(ilo, ihi, ival))
    assert (c2 == c4).all(), "min_cover4 parity FAILED"
    print("radix-4 parity: OK", flush=True)

    # ---- 5. full same_hits pipeline: current vs radix-4 -------------------
    def pipe2(x, i, wlo, whi, wval, rlo, rhi):
        mw = segtree.min_cover(leaves, wlo + 0 * x[:1], whi, wval)
        t = rangemax.build(mw, op="min")
        minw = rangemax.query(t, rlo, rhi, op="min")
        return x + minw[:1]

    def pipe4(x, i, wlo, whi, wval, rlo, rhi):
        mw = min_cover4(leaves, wlo + 0 * x[:1], whi, wval)
        t = build4(mw, op="min")
        minw = query4(t, rlo, rhi, op="min")
        return x + minw[:1]

    z = jnp.zeros((q,), jnp.int32)
    timed("same_hits pipeline radix-2", chain(pipe2), z, ilo, ihi, ival, qlo, qhi)
    timed("same_hits pipeline radix-4", chain(pipe4), z, ilo, ihi, ival, qlo, qhi)

    # ---- 6. per-dispatch cost --------------------------------------
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(1024, dtype=jnp.int32)
    _force(f(x))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        y = x
        for _ in range(16):
            y = f(y)
        _force(y)
        best = min(best, time.perf_counter() - t0)
    print(f"16 chained tiny dispatches: {best*1e3:.1f} ms "
          f"-> {best*1e3/16:.2f} ms/dispatch", flush=True)

    def scan16(x):
        return jax.lax.fori_loop(0, 16, lambda i, v: f(v), x)

    js = jax.jit(scan16)
    _force(js(x))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _force(js(x))
        best = min(best, time.perf_counter() - t0)
    print(f"same 16 ops in ONE dispatch: {best*1e3:.1f} ms", flush=True)


if __name__ == "__main__":
    main()

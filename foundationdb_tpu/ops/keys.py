"""Packed-key primitives: lexicographic compare, searchsorted, sort-ranks.

Keys are fixed-width rows of uint32: `ceil(max_key_bytes/4)` big-endian byte
words followed by one length word. Comparing rows word-by-word reproduces
FDB's key ordering contract exactly — byte-lexicographic with
shorter-before-longer at equal prefixes (the ordering the reference encodes
in KeyInfo::operator< and its radix sort, fdbserver/SkipList.cpp:100-139):
zero-padded byte words compare equal for prefix-equal keys and the length
word breaks the tie.

The all-ones row is reserved as the +inf sentinel (no real key reaches it
because the length word of a real key is <= max_key_bytes).

Everything here is pure JAX with static shapes; `vmap`-free formulations are
chosen so XLA sees plain vectorized gathers/compares.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# a host scalar with jnp.uint32's promotion: a jnp constant here would
# initialise the default backend (and claim the chip) at import time
SENTINEL_WORD = np.uint32(0xFFFFFFFF)  # flowcheck: ignore[jax.host-numpy]


def sentinel_like(n: int, key_words: int) -> jnp.ndarray:
    """[n, W] array of +inf sentinel keys."""
    return jnp.full((n, key_words), SENTINEL_WORD, dtype=jnp.uint32)


def lex_less(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Elementwise a < b for packed keys; compares trailing axis W.

    a, b: [..., W] uint32 (broadcastable). Returns [...] bool.
    """
    w = a.shape[-1]
    res = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]), bool)
    # Scan from least-significant word: a later (more-significant) unequal
    # word overrides the verdict from the less-significant words.
    for i in range(w - 1, -1, -1):
        ai, bi = a[..., i], b[..., i]
        res = jnp.where(ai < bi, True, jnp.where(ai > bi, False, res))
    return res


def lex_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(a == b, axis=-1)


def searchsorted(keys: jnp.ndarray, queries: jnp.ndarray, *, side: str) -> jnp.ndarray:
    """Vectorized binary search over a sorted packed-key array.

    keys: [M, W] sorted ascending (invalid tail padded with sentinel).
    queries: [Q, W].
    Returns [Q] int32 insertion indices (numpy.searchsorted semantics).

    Perf note (measured, v5e): gathers from LOOP-CARRIED/donated buffers
    (which `keys` is, inside the resolver state) cost ~6-15ns/element vs
    ~0.1ns from plain arguments — a column-split + fusion-barrier variant
    of this routine measured 3-4x SLOWER in-kernel despite being free in
    isolation. Keep the probe simple; the real lever is minimizing
    searchsorted traffic against carried state.
    """
    if side not in ("left", "right"):
        raise ValueError(side)
    m = keys.shape[0]
    q = queries.shape[0]
    lo = jnp.zeros((q,), jnp.int32)
    hi = jnp.full((q,), m, jnp.int32)
    steps = max(1, m.bit_length())
    for _ in range(steps):
        active = lo < hi
        mid = (lo + hi) >> 1
        mid_keys = keys[jnp.clip(mid, 0, m - 1)]
        if side == "left":
            go_right = lex_less(mid_keys, queries)  # keys[mid] < q
        else:
            go_right = ~lex_less(queries, mid_keys)  # keys[mid] <= q
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return lo


def sort_ranks(points: jnp.ndarray, valid: jnp.ndarray):
    """Dense-rank all points in one lexicographic sort.

    points: [P, W] packed keys; valid: [P] bool — invalid points are
    replaced by the sentinel so they sort to the end and collapse into a
    single trailing rank.

    Returns (ranks, unique_keys, unique_count):
      ranks:       [P] int32 — dense rank of each original point among the
                   distinct valid keys (invalid points get the rank just
                   past the last valid one; callers mask them anyway).
      unique_keys: [P, W] uint32 — distinct keys in ascending order, tail
                   padded with sentinel.
      unique_count:[] int32 — number of distinct valid keys.
    """
    p, w = points.shape
    pts = jnp.where(valid[:, None], points, sentinel_like(p, w))
    iota = jnp.arange(p, dtype=jnp.int32)
    ops = [pts[:, i] for i in range(w)] + [iota]
    sorted_ops = jax.lax.sort(ops, num_keys=w)
    skeys = jnp.stack(sorted_ops[:w], axis=-1)  # [P, W] sorted
    perm = sorted_ops[w]  # [P]
    is_new = jnp.concatenate(
        [jnp.ones((1,), bool), jnp.any(skeys[1:] != skeys[:-1], axis=-1)]
    )
    # Don't count the sentinel block as a real key.
    sorted_valid = ~jnp.all(skeys == SENTINEL_WORD, axis=-1)
    rank_sorted = jnp.cumsum(is_new.astype(jnp.int32)) - 1  # [P]
    unique_count = jnp.sum((is_new & sorted_valid).astype(jnp.int32))
    ranks = jnp.zeros((p,), jnp.int32).at[perm].set(rank_sorted)
    unique_keys = sentinel_like(p, w).at[rank_sorted].set(skeys)
    return ranks, unique_keys, unique_count

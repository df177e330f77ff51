"""The hot-key resolver stream: YCSB's scrambled zipfian, vectorised.

`rmw_batch` is the generator of the traffic files whose `generator` is
"rmw_batch": one columnar resolve frame of a seeded stream, batch by
batch, as `gen.resolver_batch` makes the uniform stream's. Each
transaction is `ops_per_txn` YCSB CoreWorkload operations on keys drawn
from ScrambledZipfianGenerator(0, keyspace - 1) at constant 0.99. An
operation is a read, or with probability `update_proportion` an update:
a read-modify-write, so its key gets a read and a write conflict range.
Every transaction therefore reads `ops_per_txn` point ranges, in
operation order, and writes the keys of its updates, in the same order.

The zipfian is `gen.ScrambledZipfian` over uint64 arrays: YCSB's
ZipfianGenerator.nextLong in float64, then Utils.fnvhash64 in wrapping
uint64 arithmetic, so draw for draw it gives the scalar generator's ids.
Keys, versions and snapshots are as in the uniform stream
(`gen.point_keys`, `gen.batch_version`, a lag of 1..snapshot_lag).
"""

from __future__ import annotations

import numpy as np

import gen


class ScrambledZipfianArray:
    """`gen.ScrambledZipfian(lo, hi)` for arrays of uniform draws."""

    def __init__(self, lo: int, hi: int, theta: float = 0.99):
        self.scalar = gen.ScrambledZipfian(lo, hi, theta)

    def zipfian(self, u: np.ndarray) -> np.ndarray:
        """ZipfianGenerator.nextLong (min 0) for each draw u in [0, 1)."""
        z = self.scalar.gen
        u = np.asarray(u, np.float64)
        uz = u * z.zetan
        tail = z.items * np.power(z.eta * u - z.eta + 1, z.alpha)
        return np.where(uz < 1.0, 0, np.where(
            uz < 1.0 + z.half_pow, 1, tail.astype(np.int64)))

    def value(self, u: np.ndarray) -> np.ndarray:
        """ids in [lo, hi]: lo + fnvhash64(nextLong) % itemcount."""
        v = self.zipfian(u).astype(np.uint64)
        h = np.full(v.shape, gen.FNV_OFFSET_BASIS_64, np.uint64)
        prime = np.uint64(gen.FNV_PRIME_64)
        with np.errstate(over="ignore"):
            for _ in range(8):
                h ^= v & np.uint64(0xFF)
                h *= prime
                v >>= np.uint64(8)
        # Math.abs of the signed long; Long.MIN_VALUE stays negative, and
        # numpy's % then takes the divisor's sign as Python's does
        signed = np.abs(h.view(np.int64))
        return self.scalar.lo + signed % self.scalar.itemcount


def rmw_batch(traffic: dict, config: dict, seed: int, index: int):
    """Batch `index` of the seeded hot-key stream as (version,
    ColumnarBatch). The same (seed, index) gives the same frame."""
    from foundationdb_tpu.utils.packing import ColumnarBatch

    if traffic["key_distribution"] != "scrambled_zipfian":
        raise ValueError(f"unknown key distribution "
                         f"{traffic['key_distribution']!r}")
    n, ops = traffic["txns_per_batch"], traffic["ops_per_txn"]
    keys = ScrambledZipfianArray(0, config["keyspace"] - 1,
                                 traffic["zipfian_constant"])
    rng = np.random.default_rng([int(seed) & gen.MASK64, index])
    version = gen.batch_version(config, index)
    snaps = version - rng.integers(1, config["snapshot_lag"] + 1, n,
                                   dtype=np.int64)
    rk = gen.point_keys(keys.value(rng.random(n * ops)))
    # CoreWorkload's operation chooser: a read below readproportion
    update = rng.random(n * ops) >= 1.0 - traffic["update_proportion"]
    wk = rk[update]
    zero_r = np.zeros((len(rk), 1), np.uint8)
    zero_w = np.zeros((len(wk), 1), np.uint8)
    blob = b"".join((
        rk.tobytes(), np.hstack([rk, zero_r]).tobytes(),
        wk.tobytes(), np.hstack([wk, zero_w]).tobytes(),
    ))
    key_lens = np.concatenate([
        np.full(len(rk), 8, np.uint32), np.full(len(rk), 9, np.uint32),
        np.full(len(wk), 8, np.uint32), np.full(len(wk), 9, np.uint32),
    ])
    cols = ColumnarBatch(
        n_txns=n, n_reads=len(rk), n_writes=len(wk), snapshots=snaps,
        read_counts=np.full(n, ops, np.uint32),
        write_counts=update.reshape(n, ops).sum(1, dtype=np.uint32),
        flags=np.zeros(n, np.uint8), key_lens=key_lens, key_blob=blob,
    )
    return version, cols

"""The hot-key stream's generator (`benchmark/gen_zipf.py`): YCSB's
scrambled zipfian draw for draw, seeded frames of read-modify-write
transactions, and the driver's frame size and program check."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from tiny import BENCH_DIR  # puts benchmark/ on the path

import gen  # noqa: E402
import gen_zipf  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

SEED = 2**31 + 4321


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH_DIR, kind, name + ".json")) as f:
        return json.load(f)


CFG = _load("configs", "resolver_zipf_8r")
SMALL = dict(_load("traffic", "hot_rmw"), txns_per_batch=2048)


@pytest.mark.parametrize("lo,hi", [(0, 10_000_000 - 1), (0, 1000), (7, 99)])
def test_vectorised_zipfian_equals_the_scalar_draw_for_draw(lo, hi):
    u = np.random.default_rng(lo + hi).random(4000)
    # both head branches and the tail, at and around their edges
    z = gen.ScrambledZipfian(lo, hi)
    edge = [0.0, 1e-300, 1 / z.gen.zetan, 1 / z.gen.zetan * 0.999999,
            (1 + z.gen.half_pow) / z.gen.zetan, 0.5, 1 - 1e-16]
    u = np.concatenate([edge, u])
    got = gen_zipf.ScrambledZipfianArray(lo, hi).value(u)
    assert got.tolist() == [z.value(float(x)) for x in u]


def test_same_seed_same_frames_other_seed_other_frames():
    a = gen_zipf.rmw_batch(SMALL, CFG, SEED, 3)
    assert a[0] == gen.batch_version(CFG, 3)
    assert a[1] == gen_zipf.rmw_batch(SMALL, CFG, SEED, 3)[1]
    assert a[1] != gen_zipf.rmw_batch(SMALL, CFG, SEED + 1, 3)[1]
    assert a[1] != gen_zipf.rmw_batch(SMALL, CFG, SEED, 4)[1]


def test_each_txn_reads_its_ops_and_writes_its_updates():
    version, cols = gen_zipf.rmw_batch(SMALL, CFG, SEED, 0)
    n, ops = SMALL["txns_per_batch"], SMALL["ops_per_txn"]
    reads, writes = reference.batch_keys(cols)  # raises on a non-point
    assert cols.n_txns == n and len(reads) == cols.n_reads == n * ops
    assert (cols.read_counts == ops).all()
    assert len(writes) == cols.n_writes == int(cols.write_counts.sum())
    # every write is an update of one of the txn's own reads, in order
    r_txn = reference.per_txn(reads, cols.read_counts)
    w_txn = reference.per_txn(writes, cols.write_counts)
    for rk, wk in zip(r_txn, w_txn):
        it = iter(rk)
        assert all(any(k == r for r in it) for k in wk)
    share = cols.n_writes / cols.n_reads
    assert abs(share - SMALL["update_proportion"]) < 0.02
    lag = version - cols.snapshots
    assert lag.min() >= 1 and lag.max() <= CFG["snapshot_lag"]


def test_the_stream_is_hot():
    """Zipfian 0.99: the hottest key takes a few percent of the reads,
    and some transactions read one key twice."""
    _v, cols = gen_zipf.rmw_batch(SMALL, CFG, SEED, 1)
    reads, _w = reference.batch_keys(cols)
    _keys, counts = np.unique(reads, return_counts=True)
    assert 0.02 < counts.max() / len(reads) < 0.1
    per = reference.per_txn(reads, cols.read_counts)
    assert any(len(set(k)) < len(k) for k in per)


def test_frames_hold_what_the_kernel_holds():
    driver = harness.load_module(
        os.path.join(BENCH_DIR, "drivers", "resolver_zipf.py"))

    def cell(**kernel):
        return harness.Cell(name="c", config={**CFG, "kernel": {
            **CFG["kernel"], **kernel}}, traffic=_load("traffic", "hot_rmw"),
            chips=1, seed=SEED, seconds=1, trace=False, root="",
            t_start=0.0)

    assert driver.batch_txns(cell()) == 65536
    assert driver.batch_txns(cell(max_txns=64, max_reads=256)) == 32
    assert driver.batch_txns(cell(max_txns=64, max_reads=1024)) == 64
    assert driver.counts_fixpoint()

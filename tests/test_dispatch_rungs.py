"""Row rungs: a batch dispatches at the smallest compiled row capacity
(txns, reads, writes) that holds it, and nothing it decides depends on
which.

* Every rung gives the verdicts, the conflicting-key reports and the
  tiered history state that the config's full shape gives, batch for
  batch, on one device and on the 4-shard mesh (virtual CPU devices),
  over a stream that crosses every rung edge, compacts between rungs,
  holds too-old transactions and an empty batch.
* Both packers pad to the same rung with the same bytes: the full-size
  pack cut to the rung, padding rows carrying the rung's txn capacity.
* The resolver role's warm-up compiles one program per rung, so no
  served batch traces anew; the delta_spill bound grows by the rung's
  write rows, not the config's.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from foundationdb_tpu.cluster import multiprocess as mp
from foundationdb_tpu.config import KernelConfig
from foundationdb_tpu.models import conflict_set as CS
from foundationdb_tpu.models.conflict_base import CpuConflictSet
from foundationdb_tpu.models.conflict_set import TpuConflictSet
from foundationdb_tpu.models.types import CommitTransaction, TransactionResult
from foundationdb_tpu.parallel import sharding
from foundationdb_tpu.parallel.mesh import cpu_mesh
from foundationdb_tpu.utils import packing

from conftest import random_range

# compile-heavy kernel tests: run with -m kernel (fast lane: -m 'not kernel')
pytestmark = pytest.mark.kernel

RUNGS = ((64, 64, 64), (128, 128, 128), (128, 256, 256))

#: (txns, reads, writes) per batch, and the rung each must take: both
#: sides of every edge, exactly R reads, an empty batch, and with
#: compaction every 3 batches, compactions between batches of every rung
STREAM = [
    ((10, 10, 10), 0), ((64, 64, 64), 0), ((65, 65, 65), 1),
    ((0, 0, 0), 0), ((30, 64, 20), 0), ((30, 65, 20), 1),
    ((128, 128, 128), 1), ((100, 129, 50), 2), ((128, 256, 256), 2),
    ((5, 5, 5), 0), ((64, 40, 64), 0), ((20, 20, 65), 1),
]


def config(**kw) -> KernelConfig:
    d = dict(max_key_bytes=8, max_txns=128, max_reads=256, max_writes=256,
             history_capacity=8192, window_versions=1000,
             delta_capacity=2048, compact_interval=3)
    d.update(kw)
    return KernelConfig(**d)


def make_txns(rng, version: int, n: int, nr: int, nw: int) -> list:
    """n txns holding nr reads and nw writes between them, spread at
    random (some txns blind or read-only); one in ten reads from below
    the MVCC window, half ask for the conflicting-key report."""
    r_counts = np.bincount(rng.integers(0, n, nr), minlength=n) if n else []
    w_counts = np.bincount(rng.integers(0, n, nw), minlength=n) if n else []
    return [
        CommitTransaction(
            read_conflict_ranges=[random_range(rng) for _ in range(rc)],
            write_conflict_ranges=[random_range(rng) for _ in range(wc)],
            read_snapshot=(version - 1500 if rng.random() < 0.1
                           else version - int(rng.integers(50, 400))),
            report_conflicting_keys=bool(rng.random() < 0.5),
        )
        for rc, wc in zip(r_counts, w_counts)
    ]


def stream(seed: int = 3):
    rng = np.random.default_rng(seed)
    return [(1000 + 100 * i, make_txns(rng, 1000 + 100 * i, *counts), rung)
            for i, (counts, rung) in enumerate(STREAM)]


def full_shape_only(cs: TpuConflictSet) -> TpuConflictSet:
    """The same conflict set held to the config's full shape, as every
    batch was dispatched before the rungs."""
    cs.rungs = cs.rungs[-1:]
    return cs


def assert_same_state(a, b) -> None:
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_row_rungs_of_the_64k_configs():
    cfg = KernelConfig(max_key_bytes=16, max_txns=65536, max_reads=262144,
                       max_writes=262144, history_capacity=1 << 20,
                       delta_capacity=1 << 20)
    assert packing.row_rungs(cfg) == (
        (1024, 1024, 1024), (65536, 65536, 65536), (65536, 262144, 262144))


@pytest.mark.parametrize("caps,rungs", [
    ((128, 256, 256), RUNGS),
    ((64, 256, 256), ((64, 64, 64), (64, 256, 256))),
    ((16, 32, 32), ((16, 16, 16), (16, 32, 32))),
    ((4096, 256, 256), ((64, 64, 64), (4096, 256, 256))),
    ((1024, 1024, 1024), ((64, 64, 64), (1024, 1024, 1024))),
], ids=str)
def test_row_rungs_grow_in_every_coordinate(caps, rungs):
    cfg = KernelConfig(max_txns=caps[0], max_reads=caps[1],
                       max_writes=caps[2])
    got = packing.row_rungs(cfg)
    assert got == rungs
    assert got[-1] == caps
    for lo, hi in zip(got, got[1:]):
        assert all(a <= b for a, b in zip(lo, hi)) and lo != hi


@pytest.mark.parametrize("i", range(len(STREAM)))
def test_packers_agree_at_the_rung(i):
    cfg = config()
    version, txns, want = stream()[i]
    rungs = packing.row_rungs(cfg)
    obj = packing.pack_batch(txns, version, 0, cfg, rungs)
    col = packing.pack_batch_columnar(
        packing.pack_columnar(txns), version, 0, cfg, rungs)
    full = packing.pack_batch(txns, version, 0, cfg)
    b, nr, nw = RUNGS[want]
    assert (obj.txn_valid.shape[0], obj.read_valid.shape[0],
            obj.write_valid.shape[0]) == (b, nr, nw)
    for f in dataclasses.fields(packing.PackedBatch):
        x, y = getattr(obj, f.name), getattr(col, f.name)
        assert np.asarray(x).dtype == np.asarray(y).dtype, f.name
        np.testing.assert_array_equal(x, y, err_msg=f.name)
        if isinstance(x, np.ndarray):
            cap = {"txn": b, "read": nr, "write": nw}[
                "txn" if f.name in ("txn_valid", "snapshot", "has_reads")
                else f.name.split("_")[0]]
            cut = getattr(full, f.name)[:cap]
            if f.name in ("read_txn", "write_txn"):
                cut = np.where(cut == cfg.max_txns, b, cut)
            np.testing.assert_array_equal(x, cut, err_msg=f.name)
    assert (obj.read_txn[obj.n_reads:] == b).all()
    assert (obj.write_txn[obj.n_writes:] == b).all()


def test_packers_refuse_past_the_config_caps():
    cfg = config()
    rungs = packing.row_rungs(cfg)
    rng = np.random.default_rng(0)
    for counts in ((129, 1, 1), (1, 257, 1), (1, 1, 257)):
        txns = make_txns(rng, 1000, *counts)
        with pytest.raises(ValueError):
            packing.pack_batch(txns, 1000, 0, cfg, rungs)
        with pytest.raises(ValueError):
            packing.pack_batch_columnar(
                packing.pack_columnar(txns), 1000, 0, cfg, rungs)


def _sets(shards: int):
    if shards:
        cfg = config(n_shards=shards)
        make = lambda: TpuConflictSet(cfg, mesh=cpu_mesh(shards))  # noqa: E731
    else:
        cfg = config()
        make = lambda: TpuConflictSet(cfg)  # noqa: E731
    return cfg, make(), full_shape_only(make())


@pytest.mark.parametrize("shards", [0, 4], ids=["one_device", "four_shards"])
def test_every_rung_matches_the_full_shape(shards):
    cfg, cs, ref = _sets(shards)
    oracle = None if shards else CpuConflictSet(cfg)
    taken, verdicts = [], set()
    for version, txns, want in stream():
        cols = packing.pack_columnar(txns)
        batch = cs.pack_columnar_batch(cols, version)
        taken.append(RUNGS.index((batch.txn_valid.shape[0],
                                  batch.read_valid.shape[0],
                                  batch.write_valid.shape[0])))
        got = cs.resolve_columnar_packed(cols, batch)
        exp = ref.resolve(txns, version)
        assert got.verdicts == exp.verdicts, version
        assert got.conflicting_key_ranges == exp.conflicting_key_ranges
        assert_same_state(cs.state, ref.state)
        if oracle is not None:
            assert got.verdicts == oracle.resolve(txns, version).verdicts
        verdicts |= set(got.verdicts)
    assert taken == [rung for _c, rung in STREAM]
    assert verdicts == {TransactionResult.CONFLICT, TransactionResult.TOO_OLD,
                        TransactionResult.COMMITTED}
    assert cs.metrics.counters.get("compactions") == len(STREAM) // 3
    rows = cs.metrics.dispatch_rows
    assert rows.count == len(STREAM)
    assert rows.total == sum(RUNGS[r][1] + RUNGS[r][2] for _c, r in STREAM)


def _kernel_jit(cfg: KernelConfig, mesh=None):
    if mesh is None:
        return CS._resolve_tiered_jit(0, cfg.fixpoint_unroll, False, 0, False)
    return sharding.tiered_sharded_jit(mesh, 0, cfg.fixpoint_unroll, False,
                                       0, range_sweep=False)


@pytest.mark.parametrize("shards", [0, 4], ids=["one_device", "four_shards"])
def test_warm_up_compiles_every_rung(shards, monkeypatch):
    # a history capacity no other test uses, so its programs are new here
    spec = ("KernelConfig(max_key_bytes=8, max_txns=128, max_reads=256, "
            "max_writes=256, history_capacity=3000, window_versions=5000000,"
            f" delta_capacity=1500, compact_interval=2, n_shards={shards})")
    cfg = eval(spec, {"KernelConfig": KernelConfig})  # noqa: S307
    fn = _kernel_jit(cfg, cpu_mesh(shards) if shards else None)
    compact = (sharding.compact_sharded_jit(cpu_mesh(shards)) if shards
               else CS._COMPACT)
    before = fn._cache_size()
    monkeypatch.setenv("RESOLVER_KERNEL", spec)
    role = mp.ResolverRole("tpu-force")
    assert role._cs.rungs == RUNGS
    assert fn._cache_size() == before + len(RUNGS)
    warmed, compact_warmed = fn._cache_size(), compact._cache_size()

    rng = np.random.default_rng(1)
    version = 1000
    for counts, rung in STREAM * 2:
        version += 100
        role._cs.resolve(make_txns(rng, version, *counts), version)
    assert role._cs.metrics.counters.get("compactions") == len(STREAM)
    assert fn._cache_size() == warmed
    assert compact._cache_size() == compact_warmed


@pytest.mark.parametrize("rungs", [RUNGS, packing.row_rungs(KernelConfig(
    max_txns=65536, max_reads=262144, max_writes=262144))], ids=str)
def test_each_warm_batch_takes_its_own_rung(rungs):
    cfg = KernelConfig(max_txns=rungs[-1][0], max_reads=rungs[-1][1],
                       max_writes=rungs[-1][2])
    for rung, txns in zip(rungs, mp._rung_warm_batches(rungs)):
        counts = (len(txns),
                  sum(len(t.read_conflict_ranges) for t in txns),
                  sum(len(t.write_conflict_ranges) for t in txns))
        assert packing._rung(cfg, rungs, *counts) == rung


def test_spill_bound_counts_the_rung_write_rows():
    # a delta tier of one full-shape batch's bound: at the config's full
    # shape every batch after the first spills, at the rungs only the
    # full-shape batch after four small ones
    cfg = config(delta_capacity=512, delta_spill=True, compact_interval=0)
    cs, ref = TpuConflictSet(cfg), full_shape_only(TpuConflictSet(cfg))
    rng = np.random.default_rng(2)
    bounds = []
    for i, counts in enumerate([(10, 10, 10)] * 4 + [(100, 129, 129)]):
        version = 1000 + 100 * i
        txns = make_txns(rng, version, *counts)
        assert cs.resolve(txns, version).verdicts == \
            ref.resolve(txns, version).verdicts
        bounds.append(cs._spill_bound_rows)
    assert bounds == [128, 256, 384, 512, 512]
    assert cs.metrics.counters.get("spills") == 1
    assert ref.metrics.counters.get("spills") == 4

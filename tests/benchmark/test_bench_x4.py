"""resolver_64k_x4.uniform on the CPU, tiny sizes, over 4 virtual devices:
the role serves from a 4-shard conflict set; its reference (four
resolvers) tells the deployment apart from one resolver; and the
partition skew the sharded conflict set counts in its pack stage, with
the reader of it."""

from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from runs import TINY
from tiny import KNOBS, REPO, bench, cell

import gen  # noqa: E402  (benchmark/, on the path through tiny)
import harness  # noqa: E402
import reference  # noqa: E402
from foundationdb_tpu.models.types import CommitTransaction
from foundationdb_tpu.parallel import sharding as SH
from foundationdb_tpu.utils import packing

CELL = "resolver_64k_x4.uniform"

#: one tiny run in a process of its own (the harness's parent stays off
#: JAX), printing its result line and the role's kernel status block
_SERVE = f"""
import json, sys
sys.argv = [{TINY!r}]
sys.path.insert(0, {os.path.dirname(TINY)!r})
import tiny, harness, run
seen = {{}}
report = harness.setup_report
def keep(cell, status, t_ready):
    seen.update(status["qos"]["kernel"])
    report(cell, status, t_ready)
harness.setup_report = keep
run.PLATFORM = "cpu"
line = run.run_cell(tiny.bench(), tiny.cell({CELL!r}))
print(json.dumps({{"line": line, "kernel": seen}}))
"""


def test_tiny_role_serves_from_four_shards():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "FDBTPU_KNOB_OVERRIDES": KNOBS}
    p = subprocess.run([sys.executable, "-c", _SERVE], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["kernel"]["shards"] == 4
    assert out["kernel"]["conflict_set"] == "tpu"
    assert out["line"]["correct"] is True
    assert {w["name"]: w["chips"] for w in bench()["workloads"]}[CELL] == 4


def test_four_resolvers_disagree_with_one_on_the_tiny_stream():
    """Phantom commits: a transaction aborted on one partition still has
    its writes merged on the others, so 4 resolvers abort some that 1
    commits. The cell's `correct` therefore holds the program to the
    deployment, not to one resolver."""
    c = cell(CELL)
    cfg, tr = c.config, c.traffic
    four = reference.Reference(cfg["window_versions"],
                               reference.default_boundaries(4))
    one = reference.Reference(cfg["window_versions"],
                              reference.default_boundaries(1))
    differ = 0
    for i in range(60):
        version, cols = gen.resolver_batch(tr, cfg, c.seed, i)
        differ += int(np.count_nonzero(four.resolve(version, cols)
                                       != one.resolve(version, cols)))
    assert differ > 0


def _ranges(rng, n, keys):
    out = []
    for _ in range(n):
        a, b = sorted(rng.choice(keys, 2, replace=False))
        out.append((a, b))
    return out


def test_partition_loads_match_the_split_range_by_range():
    """partition_loads against a plain loop over ResolutionRequestBuilder's
    split, on keys at, around and between the split keys (first words
    that tie a split key's included), ranges that straddle them too."""
    cfg = cell(CELL).config
    from foundationdb_tpu.config import KernelConfig

    kcfg = KernelConfig(**{**cfg["kernel"], "max_txns": 64,
                           "max_reads": 256, "max_writes": 256})
    splits = [b"\x40", b"\x80\x00\x01", b"\xc0"]
    keys = [b"", b"\x00", b"\x3f\xff", b"\x40", b"\x40\x00", b"\x41",
            b"\x80", b"\x80\x00", b"\x80\x00\x01", b"\x80\x00\x01\x00",
            b"\x80\x00\x02", b"\xbf", b"\xc0", b"\xc0\x00\x00\x00\x01",
            b"\xff\xff"]
    rng = np.random.default_rng(7)
    txns = [CommitTransaction(read_conflict_ranges=_ranges(rng, 2, keys),
                              write_conflict_ranges=_ranges(rng, 1, keys),
                              read_snapshot=0) for _ in range(40)]
    batch = packing.pack_batch(txns, 1, 0, kcfg)
    bounds = SH.make_partition(splits, kcfg)[0][1:]
    lo = [b""] + splits
    hi = splits + [None]
    want = [0] * 4
    for t in txns:
        for b, e in itertools.chain(t.read_conflict_ranges,
                                    t.write_conflict_ranges):
            for s in range(4):
                want[s] += (hi[s] is None or b < hi[s]) and e > lo[s]
    assert SH.partition_loads(batch, bounds).tolist() == want


@pytest.fixture
def sharded():
    """A 4-shard conflict set over 4 virtual CPU devices, at 4096 txns:
    only its pack stage runs, so nothing compiles."""
    from foundationdb_tpu.config import KernelConfig
    from foundationdb_tpu.models.conflict_set import TpuConflictSet
    from foundationdb_tpu.parallel.mesh import cpu_mesh

    kcfg = KernelConfig(max_key_bytes=16, max_txns=4096, max_reads=4096,
                        max_writes=4096, history_capacity=16384,
                        delta_capacity=16384, n_shards=4)
    return TpuConflictSet(kcfg, mesh=cpu_mesh(4))


def _skew(cs) -> dict:
    return cs.metrics.stages()["shard_skew"]


def test_shard_skew_reads_400_when_one_partition_holds_every_key(sharded):
    txns = [CommitTransaction(
        read_conflict_ranges=[(b"\x10r%d" % i, b"\x10r%d\x00" % i)],
        write_conflict_ranges=[(b"\x10w%d" % i, b"\x10w%d\x00" % i)],
        read_snapshot=0) for i in range(1000)]
    sharded.pack_columnar_batch(packing.pack_columnar(txns), 1)
    assert _skew(sharded)["count"] == 1
    assert _skew(sharded)["mean"] == pytest.approx(400.0)


def test_shard_skew_reads_near_100_on_the_uniform_stream(sharded):
    c = cell(CELL)
    tr = dict(c.traffic, txns_per_batch=4096)
    for i in range(3):
        version, cols = gen.resolver_batch(tr, c.config, c.seed, i)
        sharded.pack_columnar_batch(cols, version)
    assert _skew(sharded)["count"] == 3
    assert 100.0 <= _skew(sharded)["mean"] < 110.0


def test_one_shard_reports_no_skew():
    from foundationdb_tpu.models.conflict_base import KernelStageMetrics

    assert "shard_skew" not in KernelStageMetrics().stages()


def _window(stages: dict | None):
    status = {"qos": {} if stages is None else {"stages": stages}}
    return harness.Window(status0=status, status1=status, batches=4,
                          txns=0, least_bytes=0, chips=4, peaks={},
                          trace=None)


def _reader():
    return harness.load_module(os.path.join(
        os.path.dirname(harness.__file__), "metrics", "shard_skew_pct.py"))


@pytest.mark.parametrize("stages", [None, {"pack": {"count": 3,
                                                    "mean": 0.01}}])
def test_shard_skew_pct_is_nothing_without_the_counter(stages):
    assert _reader().read(_window(stages)) is None


def test_shard_skew_pct_is_the_window_mean():
    w = _window(None)
    w.status0 = {"qos": {"stages": {"shard_skew": {"count": 2,
                                                   "mean": 300.0}}}}
    w.status1 = {"qos": {"stages": {"shard_skew": {"count": 6,
                                                   "mean": 150.0}}}}
    # (6 x 150 - 2 x 300) / 4 batches
    assert _reader().read(w) == pytest.approx(75.0)

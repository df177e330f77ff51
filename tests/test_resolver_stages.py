"""The Resolver role's per-batch stage spans and byte counters.

A device resolver role (the device conflict set on the CPU backend, at
64-txn shapes) served over a real RpcServer: every per-batch stage is
sampled once a batch, the stages tile the role's compute time, the
byte counters read the kernel arguments, and a profiler session sees
the stages as sibling host events. The stage timer is cheap without a
session and never loads JAX into the JAX-free resolvers.
"""

from __future__ import annotations

import asyncio
import glob
import os
import random
import subprocess
import sys
import tempfile
import time

import pytest

from foundationdb_tpu.cluster import multiprocess as mp
from foundationdb_tpu.models.conflict_base import STAGE_NAMES
from foundationdb_tpu.models.types import CommitTransaction
from foundationdb_tpu.utils import packing
from foundationdb_tpu.utils.metrics import LatencySample, stage
from foundationdb_tpu.wire import codec, transport

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_KERNEL = (
    "KernelConfig(max_key_bytes=16, max_txns=64, max_reads=256, "
    "max_writes=256, history_capacity=2048, window_versions=5000000, "
    "delta_capacity=2048, compact_interval=4)"
)
#: one overflow check (every 32 batches) and eight compactions
BATCHES = 34
PER_BATCH = ("decode", "key_sample", "pack", "transfer", "dispatch",
             "readback", "assemble", "encode", "arg_bytes",
             "device_arg_bytes", "dispatch_rows", "fixpoint_iters")
#: the stages inside ResolverRole._resolve_now (computeTime)
IN_COMPUTE = ("key_sample", "pack", "transfer", "dispatch", "compact",
              "overflow_check", "readback", "assemble")


def _batches(n: int, seed: int = 5):
    """n columnar resolve requests of 60 point txns over 500 keys."""
    rng = random.Random(seed)
    reqs, prev = [], -1
    for i in range(n):
        version = (i + 1) * 1000
        txns = []
        for _ in range(60):
            r, w = b"k%d" % rng.randrange(500), b"k%d" % rng.randrange(500)
            txns.append(CommitTransaction(
                read_conflict_ranges=[(r, r + b"\0")],
                write_conflict_ranges=[(w, w + b"\0")],
                read_snapshot=max(0, version - 1500),
            ))
        reqs.append(codec.ResolveBatchColumnar(
            prev_version=prev, version=version, last_received_version=prev,
            cols=packing.pack_columnar(txns),
        ))
        prev = version
    return reqs


def _serve(reqs, profile_last_in: str | None = None) -> mp.ResolverRole:
    """A device resolver role behind a real RpcServer, sent `reqs` in
    order over one connection; with `profile_last_in`, the last request
    is served inside a profiler session writing there."""
    with pytest.MonkeyPatch.context() as mpatch:
        mpatch.setenv("RESOLVER_KERNEL", TINY_KERNEL)
        role = mp.ResolverRole("tpu-force")

    async def go(addr):
        server = transport.RpcServer(addr)
        server.register(mp.TOKEN_RESOLVE, role.resolve,
                        stages=role.wire_stages())
        await server.start()
        try:
            conn = transport.RpcConnection(addr)
            await conn.connect()
            for i, req in enumerate(reqs):
                last = i == len(reqs) - 1
                if last and profile_last_in:
                    import jax

                    jax.profiler.start_trace(profile_last_in)
                try:
                    rep = await conn.call(mp.TOKEN_RESOLVE, req)
                finally:
                    if last and profile_last_in:
                        jax.profiler.stop_trace()
                assert len(rep.committed) == req.cols.n_txns
            await conn.close()
        finally:
            await server.close()

    with tempfile.TemporaryDirectory(prefix="fdbst") as d:
        asyncio.run(go(os.path.join(d, "resolver.sock")))
    return role


@pytest.fixture(scope="module")
def served():
    reqs = _batches(BATCHES)
    role = _serve(reqs)
    return role, role.status()["qos"], reqs


def test_status_lists_the_stages_in_batch_order(served):
    _role, qos, _reqs = served
    assert list(qos["stages"]) == [*STAGE_NAMES, "arg_bytes",
                                   "device_arg_bytes", "dispatch_rows",
                                   "fixpoint_iters", "overlap"]


@pytest.mark.parametrize("key", PER_BATCH)
def test_per_batch_stage_is_sampled_once_a_batch(served, key):
    _role, qos, _reqs = served
    assert qos["stages"][key]["count"] == BATCHES
    assert qos["stages"][key]["mean"] > 0


def test_periodic_stages_count_their_events(served):
    _role, qos, _reqs = served
    stages = qos["stages"]
    assert stages["compact"]["count"] == qos["kernel"]["compactions"] == 8
    assert stages["overflow_check"]["count"] == BATCHES // 32


def test_stages_tile_the_role_compute_time(served):
    _role, qos, _reqs = served
    compute = qos["compute_time_dist"]
    assert compute["count"] == BATCHES
    staged = sum(qos["stages"][k]["count"] * qos["stages"][k]["mean"]
                 for k in IN_COMPUTE)
    total = compute["count"] * compute["mean"]
    assert abs(staged - total) <= 0.1 * total, (staged, total)


def test_conflict_set_total_keeps_its_stages(served):
    """kernel_seconds_per_batch sums pack + transfer + dispatch + fence,
    and the fence holds the readback and the assembly."""
    role, qos, _reqs = served
    m = role._kernel_metrics
    assert qos["kernel"]["kernel_seconds_per_batch"] == pytest.approx(
        (m.pack.total + m.transfer.total + m.kernel.total + m.fence.total)
        / BATCHES)
    assert m.fence.total >= m.readback.total + m.assemble.total


def test_arg_bytes_are_the_kernel_arguments(served):
    role, qos, reqs = served
    batch = packing.pack_batch_columnar(reqs[0].cols, reqs[0].version, 0,
                                        role._cs.config, role._cs.rungs)
    want = sum(v.nbytes for v in batch.device_args().values())
    assert qos["stages"]["arg_bytes"]["mean"] == want
    # the CPU backend keeps an array in its host bytes, unpadded
    assert qos["stages"]["device_arg_bytes"]["mean"] == want
    # 60 txns of one read and one write take the (64, 64, 64) rung of
    # the (64, 256, 256) config
    assert batch.read_valid.shape == batch.write_valid.shape == (64,)
    assert qos["stages"]["dispatch_rows"]["mean"] == 64 + 64


def test_profiler_records_the_stages_as_sibling_host_events(tmp_path):
    import jax

    _serve(_batches(3, seed=9), profile_last_in=str(tmp_path))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name)
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("Resolver.resolveBatch.")
    )
    names = {n for _s, _e, n in events}
    assert {STAGE_NAMES[k] for k in PER_BATCH[:8]} <= names
    assert names <= set(STAGE_NAMES.values())
    for (_s0, e0, n0), (s1, _e1, n1) in zip(events, events[1:]):
        assert e0 <= s1, f"{n0} overlaps {n1}"


def test_stage_timer_is_cheap_without_a_profiler_session(monkeypatch):
    import contextlib

    import jax  # (the annotation path is taken once loaded)

    # outside a profiler session no trace annotation is made at all
    real = jax.profiler.TraceAnnotation
    made = []

    class Spy:
        is_enabled = staticmethod(real.is_enabled)

        def __init__(self, name):
            made.append(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    sample = LatencySample("x")

    def one_round() -> tuple[float, float]:
        t0 = time.perf_counter()
        for _ in range(10_000):
            with stage(sample, "Resolver.resolveBatch.Pack"):
                pass
        t1 = time.perf_counter()
        for _ in range(10_000):
            with contextlib.nullcontext():
                pass
        return t1 - t0, time.perf_counter() - t1

    # the least of five rounds of each, timed in the same rounds: a
    # loaded host slows both loops, not their ratio. A span costs a few
    # bare context managers (its clock reads and the sample's update:
    # 3-6 on an idle or a loaded 8-core host).
    rounds = [one_round() for _ in range(5)]
    span = min(r[0] for r in rounds)
    bare = min(r[1] for r in rounds)
    assert span < 10 * bare, f"a span costs {span / bare:.1f} bare ones"
    assert made == []
    assert sample.count == 50_000


def _point_frame(prev, version, reads, writes):
    """A columnar resolve frame of point-range txns, txn i reading
    reads[i] and writing writes[i]."""
    txns = [
        CommitTransaction(read_conflict_ranges=[(r, r + b"\0")],
                          write_conflict_ranges=[(w, w + b"\0")],
                          read_snapshot=max(0, version - 1))
        for r, w in zip(reads, writes)
    ]
    return codec.ResolveBatchColumnar(
        prev_version=prev, version=version, last_received_version=prev,
        cols=packing.pack_columnar(txns),
    )


def test_key_sample_counts_full_batches_in_numpy():
    """A full 64K-txn frame of 8-byte keys is counted in numpy, a 4-txn
    YCSB frame by the loop; the status block is the loop's either way."""
    from foundationdb_tpu.cluster import sampling

    rng = random.Random(28)
    keys = [rng.randrange(20_000_000).to_bytes(8, "big")
            for _ in range(2 * 65536)]
    users = [b"user%d" % rng.randrange(1000) for _ in range(8)]
    frames = [
        (-1, 10, keys[:65536], keys[65536:], 1),
        (10, 20, users[:4], users[4:], 1),
    ]
    role = mp.ResolverRole("native")
    loop: dict = {}
    for prev, version, reads, writes, fast_batches in frames:
        asyncio.run(role.resolve(_point_frame(prev, version, reads, writes)))
        # a columnar frame's order: every read begin, then every write's
        sampling.note_begin_keys(loop, reads + writes)
        qos = role.status()["qos"]
        assert qos["resolve_path"]["key_sample_fast_batches"] == fast_batches
        assert qos["key_sample"] == sampling.key_sample_qos(loop)
        assert list(role._key_sample.items()) == list(loop.items())


_NATIVE_ROLE = """
import asyncio, sys
from foundationdb_tpu.cluster import multiprocess as mp
from foundationdb_tpu.models.types import CommitTransaction
from foundationdb_tpu.utils import packing
from foundationdb_tpu.wire import codec
role = mp.ResolverRole("native")
txn = CommitTransaction(read_conflict_ranges=[(b"a", b"b")],
                        write_conflict_ranges=[(b"a", b"b")])
asyncio.run(role.resolve(codec.ResolveBatchColumnar(
    prev_version=-1, version=10, last_received_version=-1,
    cols=packing.pack_columnar([txn]))))
assert role.status()["qos"]["stages"]["key_sample"]["count"] == 1
sys.exit("jax" in sys.modules)
"""

_SIM_RESOLVER = """
import sys
from foundationdb_tpu.config import TEST_CONFIG
from foundationdb_tpu.models.types import (
    CommitTransaction, ResolveTransactionBatchRequest)
from foundationdb_tpu.resolver import Resolver
from foundationdb_tpu.runtime.flow import Scheduler
sched = Scheduler(sim=True)
res = Resolver(sched, TEST_CONFIG, backend="cpu")
txn = CommitTransaction(read_conflict_ranges=[(b"a", b"b")],
                        write_conflict_ranges=[(b"a", b"b")])
t = sched.spawn(res.resolve(ResolveTransactionBatchRequest(
    prev_version=-1, version=10, last_received_version=-1,
    transactions=[txn])), name="drive")
sched.run_until(t.done)
assert len(t.done.get().committed) == 1
sys.exit("jax" in sys.modules)
"""


@pytest.mark.parametrize("script", [_NATIVE_ROLE, _SIM_RESOLVER],
                         ids=["native_role", "sim_resolver"])
def test_jax_free_resolvers_never_import_jax(script):
    p = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu",
                            "PYTHONPATH": REPO})
    assert p.returncode == 0, p.stderr[-3000:]


def test_wire_resolve_span_carries_wall_clock_times():
    from foundationdb_tpu.utils import spans

    exporter = spans.SpanExporter()
    old = spans.set_exporter(exporter)
    try:
        role = mp.ResolverRole("native")
        t0 = time.time()
        req = _batches(1)[0]
        req.span = (4242, 7)
        asyncio.run(role.resolve(req))
        t1 = time.time()
    finally:
        spans.set_exporter(old)
    (rec,) = [s for s in exporter.finished
              if s["location"] == "Resolver.resolveBatch"]
    assert t0 <= rec["begin"] < rec["end"] <= t1
    assert rec["trace_id"] == 4242

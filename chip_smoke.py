#!/usr/bin/env python
"""Chip smoke: the served commit path and the device resolver on a TPU.

    python chip_smoke.py             # phases A and B on one chip
    python chip_smoke.py --chips 4   # only the 4-chip sharded resolver

Phase A, the served commit path (BASELINE config 5's shape): a wire
cluster started the way `scripts/bench_pipeline.py --mode wire` starts it
— a ResolverRole child at the production knob value backend="tpu" with
the served cell's kernel (64K-txn capacity, 16-byte keys), tlog and
storage children, and ProxyPipeline with YCSB-A clients in this process.
Every acknowledged write must read back at its commit version, and the
resolver must report that a TPU served it. This process imports no JAX
until the phase's children have exited: a chip belongs to one process.

Phase B, the resolver at BASELINE size in this process: TpuConflictSet on
the tiered kernel over the streams bench.py builds (uniform, zipf with
read dedup, ycsb_e with the range sweep and delta spill), every decision
compared batch by batch with the native skip list.

--chips 4, BASELINE config 4: TpuConflictSet with n_shards=4 over a
4-chip mesh, against per-shard skip lists combined with min (the
reference's multi-resolver split), with each shard's history on its own
chip.

Earlier lines report each phase, compile seconds apart from run seconds.
The last line is one JSON object, {"ok": true, "device": {...}}, printed
only when every phase passed on a TPU; any failure exits non-zero
without it.
"""

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time
import types

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [REPO, os.path.join(REPO, "scripts")]

import numpy as np  # noqa: E402

#: the platform every phase must have run on (CPU tests steer it)
PLATFORM = "tpu"

#: phase A: the served cell (bench_pipeline.kernel_config(65536)) under
#: a few thousand concurrent YCSB-A clients (bench_pipeline's defaults
#: for records and proxy batch)
SERVED = dict(kernel_txns=65536, clients=4096, ops=2, records=1000,
              batch=4096)

#: phase B: (bench mode, txns per batch, batches) — BASELINE configs 1-3
STREAMS = (("uniform", 65536, 16), ("zipf", 65536, 8), ("ycsb_e", 16384, 8))

#: --chips 4: uniform 64K-txn batches over a 4-shard mesh
SHARDED = dict(n_shards=4, n_txns=65536, n_batches=8)

#: batches per fused dispatch (bench.py's default group size)
FUSE = 8


def say(*a) -> None:
    print(*a, flush=True)


def phase_a(kernel_txns, clients, ops, records, batch) -> dict:
    """The served commit path through bench_pipeline's wire cluster."""
    import bench_pipeline

    args = types.SimpleNamespace(
        kernel_txns=kernel_txns, classic_kernel=False, clients=clients,
        ops=ops, records=records, batch=batch,
    )
    t0 = time.perf_counter()
    res = asyncio.run(bench_pipeline._run_wire("tpu", args))
    wall = time.perf_counter() - t0
    k = res["resolver_kernel"]
    say(f"phase A: resolver conflict_set={k['conflict_set']} "
        f"jax_backend={k['jax_backend']} device_kind={k['device_kind']} "
        f"group_dispatches={k['group_dispatches']} "
        f"batches={k['batches']}")
    say(f"phase A: {res['committed']} commits acknowledged, "
        f"{res['acked_read_back']} read back at their commit version, "
        f"{res['reads']} reads, {res['conflicted']} conflicted attempts; "
        f"consistency {res['consistency']}")
    say(f"phase A seconds: resolver compile {k['compile_seconds']} "
        f"(role warm-up), workload {res['wall_s']}, whole phase {wall} "
        f"(role start and teardown included)")
    if (k["conflict_set"], k["jax_backend"]) != ("tpu", PLATFORM):
        raise RuntimeError(f"phase A resolver did not serve on {PLATFORM}")
    if PLATFORM == "tpu" and not str(k["device_kind"]).startswith("TPU"):
        raise RuntimeError(f"phase A device kind {k['device_kind']!r}")
    if k["group_dispatches"] <= 0:
        raise RuntimeError("phase A resolver dispatched no kernel group")
    if res["committed"] <= 0 or res["acked_read_back"] != res["committed"]:
        raise RuntimeError("phase A acknowledged writes were not read back")
    return res


def device_info(min_count: int = 1) -> dict:
    """The device JAX reports, refusing anything but PLATFORM."""
    import jax

    devs = jax.devices()
    if devs[0].platform != PLATFORM or len(devs) < min_count:
        raise RuntimeError(
            f"need {min_count} {PLATFORM} device(s), JAX has {devs}"
        )
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _groups(batches):
    from foundationdb_tpu.utils.packing import stack_device_args

    return [stack_device_args(batches[g:g + FUSE])
            for g in range(0, len(batches), FUSE)]


def _flats(batch):
    """A packed batch's (snapshots, reads, writes) in the native ABI."""
    from foundationdb_tpu.testing.benchgen import flatten_for_native

    return (batch.snapshot[:batch.n_txns].astype(np.int64),
            flatten_for_native(batch, "r"), flatten_for_native(batch, "w"))


def _compile(streams, **cs_kw) -> float:
    """Compile every program the streams will run, concurrently: each
    stream's kernel (latch armed, as the run dispatches it) and each
    distinct compaction, by running them once on throwaway instances —
    the jit caches are module-wide, so the runs after this compile
    nothing. A 64K-txn program takes minutes to compile for the chip,
    on a few host cores each. Returns the wall seconds."""
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from foundationdb_tpu.models.conflict_set import TpuConflictSet

    def kernel(st):
        warm = TpuConflictSet(st["config"], **cs_kw)
        warm.resolve_group_args(st["groups"][0], check_latch=False)
        jax.block_until_ready(warm.state)

    def compaction(config):
        warm = TpuConflictSet(config, **cs_kw)
        warm.compact_history()
        jax.block_until_ready(warm.state)

    tiers = {(st["config"].history_capacity, st["config"].delta_capacity,
              st["config"].key_words): st["config"] for st in streams}
    jobs = [(kernel, st) for st in streams] + [
        (compaction, c) for c in tiers.values()
    ]
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(jobs)) as pool:
        for f in [pool.submit(fn, arg) for fn, arg in jobs]:
            f.result()
    return time.perf_counter() - t0


def _run(config, groups, **cs_kw):
    """The stream on a fresh instance: (instance, per-batch verdicts,
    seconds). A latch or dedup trip re-runs it on the exact kernel, as
    bench.py does — loud, never wrong."""
    from foundationdb_tpu.models.conflict_set import TpuConflictSet

    t0 = time.perf_counter()
    cs = TpuConflictSet(config, **cs_kw)
    outs = [cs.resolve_group_args(g, check_latch=False) for g in groups]
    verdicts = [v for o in outs for v in np.asarray(o.verdict)]
    if any(np.asarray(o.unconverged).any() for o in outs):
        say("latch tripped: re-running on the exact kernel")
        exact = dataclasses.replace(config, fixpoint_latch=False,
                                    dedup_reads=0)
        cs = TpuConflictSet(exact, **cs_kw)
        outs = [cs.resolve_group_args(g) for g in groups]
        verdicts = [v for o in outs for v in np.asarray(o.verdict)]
    seconds = time.perf_counter() - t0
    cs.check_overflow()
    return cs, verdicts, seconds


def _compare(mode, ref, got, n_txns) -> dict:
    if len(ref) != len(got):
        raise RuntimeError(f"{mode}: {len(got)} batches vs {len(ref)}")
    for i, (r, g) in enumerate(zip(ref, got)):
        if not np.array_equal(np.asarray(r), g[:n_txns]):
            bad = int(np.count_nonzero(np.asarray(r) != g[:n_txns]))
            raise RuntimeError(
                f"{mode}: batch {i} differs from the reference in {bad} "
                f"decision(s)"
            )
    allv = np.concatenate([np.asarray(r) for r in ref])
    return {"committed": int((allv == 3).sum()),
            "conflicted": int((allv == 0).sum()),
            "too_old": int((allv == 1).sum())}


def prepare_stream(mode: str, n_txns: int, n_batches: int) -> dict:
    """One bench stream, its kernel config and the native skip list's
    decisions on it."""
    import bench
    from foundationdb_tpu.native import NativeSkipListConflictSet

    config, batches, profile, routed = bench.build_stream(
        mode, n_txns, n_batches, fuse=FUSE
    )
    # (read dedup, range sweep) each stream must compile: config 2's
    # zipf runs dedup, config 3's range scans the sweep with spill
    want = {"uniform": (False, False), "zipf": (True, False),
            "ycsb_e": (False, True)}[mode]
    if (bool(config.dedup_reads), config.range_sweep) != want or (
        config.range_sweep != config.delta_spill
    ):
        raise RuntimeError(f"{mode}: unexpected kernel config {config}")
    t0 = time.perf_counter()
    sk = NativeSkipListConflictSet(window=bench.WINDOW)
    ref = []
    for b in batches:
        snaps, reads, writes = _flats(b)
        ref.append(sk.resolve_raw(int(b.version), snaps, *reads, *writes))
    return {
        "mode": mode, "txns_per_batch": n_txns, "batches": n_batches,
        "profile": profile, "routed": routed, "config": config,
        "groups": _groups(batches), "ref": ref,
        "gc_floor": int(batches[-1].version) - bench.WINDOW,
        "skiplist_s": time.perf_counter() - t0,
    }


def phase_b(streams=STREAMS) -> list:
    """The resolver at BASELINE size, decision-checked batch by batch."""
    from foundationdb_tpu.utils import compile_cache

    compile_cache.enable()
    device_info()
    prepared = [prepare_stream(*s) for s in streams]
    compile_s = _compile(prepared)
    say(f"phase B seconds: compile {compile_s} (every stream's programs, "
        f"concurrently)")
    rows = []
    for st in prepared:
        cs, got, run_s = _run(st["config"], st["groups"])
        c = cs.metrics.counters
        r = {
            **{k: st[k] for k in ("mode", "txns_per_batch", "batches",
                                  "profile", "routed", "gc_floor",
                                  "skiplist_s")},
            "dedup_reads": st["config"].dedup_reads,
            "range_sweep": st["config"].range_sweep,
            **_compare(st["mode"], st["ref"], got, st["txns_per_batch"]),
            **{k: c.get(k) for k in ("groupDispatches", "compactions",
                                     "spills", "sweepGroups")},
            "run_s": run_s,
        }
        mode = r["mode"]
        if mode == "uniform" and (r["compactions"] < 1 or r["gc_floor"] <= 0):
            raise RuntimeError(f"uniform: no compaction or GC floor: {r}")
        if mode == "ycsb_e" and r["sweepGroups"] < 1:
            raise RuntimeError(f"ycsb_e: the sweep never dispatched: {r}")
        say(f"phase B {mode}: {r['batches']} x {r['txns_per_batch']} txns "
            f"identical to the skip list (committed {r['committed']}, "
            f"conflicted {r['conflicted']}, too_old {r['too_old']}); "
            f"dispatches {r['groupDispatches']} compactions "
            f"{r['compactions']} spills {r['spills']} sweep_groups "
            f"{r['sweepGroups']} dedup_reads {r['dedup_reads']} gc_floor "
            f"{r['gc_floor']}")
        say(f"phase B {mode} seconds: run {run_s} skiplist "
            f"{r['skiplist_s']}")
        rows.append(r)
    say(f"phase B compile cache: {json.dumps(compile_cache.stats())}")
    return rows


def _clip_flat(flat, lo: int, hi):
    """One side of a native-ABI batch clipped to the key range [lo, hi)
    (8-byte big-endian keys as integers; hi None = +inf): the pieces a
    resolver owning that range sees, empty pieces dropped."""
    blob, _off, txn = flat
    keys = np.frombuffer(blob.tobytes(), ">u8").astype(np.uint64)
    b, e = keys[0::2], keys[1::2]
    b = np.maximum(b, np.uint64(lo))
    if hi is not None:
        e = np.minimum(e, np.uint64(hi))
    keep = b < e
    pairs = np.stack([b[keep], e[keep]], axis=1).astype(">u8")
    n = int(keep.sum())
    return (np.frombuffer(pairs.tobytes(), np.uint8),
            np.arange(2 * n + 1, dtype=np.int64) * 8, txn[keep])


def phase_sharded(n_shards: int, n_txns: int, n_batches: int) -> dict:
    """BASELINE config 4: the mesh-sharded tiered kernel vs per-shard
    skip lists combined with min, each shard's history on its own chip."""
    import jax

    import bench
    from foundationdb_tpu.native import NativeSkipListConflictSet
    from foundationdb_tpu.parallel.mesh import resolver_mesh
    from foundationdb_tpu.utils import compile_cache

    compile_cache.enable()
    device_info(n_shards)
    config, batches, _, _ = bench.build_stream("uniform", n_txns, n_batches,
                                               fuse=FUSE)
    if config.max_key_bytes != 8:
        raise RuntimeError("the clipped reference assumes 8-byte keys")
    config = dataclasses.replace(config, n_shards=n_shards)
    # an even split of the stream's integer keyspace (the split a
    # ResolutionBalancer converges to on uniform traffic): the default
    # byte-prefix split would leave every key of it on shard 0
    edges = [bench.KEYSPACE * (i + 1) // n_shards
             for i in range(n_shards - 1)]
    bounds = [e.to_bytes(8, "big") for e in edges]
    los, his = [0] + edges, edges + [None]
    shards = [NativeSkipListConflictSet(window=bench.WINDOW)
              for _ in range(n_shards)]
    pieces = [0] * n_shards
    t0 = time.perf_counter()
    ref = []
    for b in batches:
        snaps, reads, writes = _flats(b)
        v = None
        for s, (sk, lo, hi) in enumerate(zip(shards, los, his)):
            r, w = _clip_flat(reads, lo, hi), _clip_flat(writes, lo, hi)
            pieces[s] += len(r[2]) + len(w[2])
            sv = sk.resolve_raw(int(b.version), snaps, *r, *w)
            v = sv if v is None else np.minimum(v, sv)
        ref.append(v)
    ref_s = time.perf_counter() - t0
    if min(pieces) == 0:
        raise RuntimeError(f"a shard saw no conflict ranges: {pieces}")
    mesh = resolver_mesh(n_shards)
    kw = {"mesh": mesh, "shard_boundaries": bounds}
    groups = _groups(batches)
    compile_s = _compile([{"config": config, "groups": groups}], **kw)
    cs, got, run_s = _run(config, groups, **kw)
    counts = _compare("sharded", ref, got, n_txns)
    placement = set()
    for leaf in jax.tree.leaves(cs.state):
        devs = [s.device for s in leaf.addressable_shards]
        if len(set(devs)) != n_shards or any(
            s.data.shape[0] != 1 for s in leaf.addressable_shards
        ):
            raise RuntimeError(
                f"history leaf {leaf.shape} is not one shard per device: "
                f"{leaf.sharding}"
            )
        placement.add(tuple(sorted(str(d) for d in devs)))
    say(f"sharded: {n_batches} x {n_txns} txns over {n_shards} shards "
        f"identical to per-shard skip lists (committed {counts['committed']}"
        f", conflicted {counts['conflicted']}); dispatches "
        f"{cs.metrics.counters.get('groupDispatches')} compactions "
        f"{cs.metrics.counters.get('compactions')}")
    say(f"sharded: conflict-range pieces per shard {pieces}; history "
        f"state on {sorted(placement)}")
    say(f"sharded seconds: compile {compile_s} run {run_s} "
        f"skiplists {ref_s}")
    say(f"sharded compile cache: {json.dumps(compile_cache.stats())}")
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip sharded resolver phase")
    args = ap.parse_args(argv)
    requested = os.environ.get("JAX_PLATFORMS", "")
    if requested and PLATFORM not in requested.split(","):
        print(f"chip_smoke: JAX_PLATFORMS={requested!r} leaves no {PLATFORM}",
              file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    if args.chips == 1:
        if "jax" in sys.modules:
            raise RuntimeError("phase A must start in a process without JAX")
        phase_a(**SERVED)
        phase_b()
    else:
        phase_sharded(**SHARDED)
    device = device_info(args.chips)
    say(f"total seconds {time.perf_counter() - t0}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

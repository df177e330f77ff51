#!/usr/bin/env python
"""End-to-end commit-pipeline bench at BASELINE.json config-5 shapes.

YCSB-A (50% read-modify-write / 50% read over a zipf-hot record set)
through the full commit pipeline, BOTH resolver backends, measuring
committed transactions per second and commit-latency percentiles:

* --mode cluster (default): GRV -> proxy batching -> resolver -> tlog ->
  storage inside one deterministic simulation (open_cluster). Fast to
  drive at high client counts; virtual-time rates.
* --mode wire: client + proxy in this process; resolver, tlog and
  storage as SEPARATE OS PROCESSES over the serialized UDS wire
  (cluster/multiprocess.py) — the CommitProxy->Resolver hop pays real
  serialization, framing and scheduling. Wall-clock rates.

The config-5 spec point (BASELINE.md:36) is --spec5: 256K in-flight
client transactions, wire mode, both backends. In-flight = concurrent
client tasks, each with at most one outstanding transaction. On hosts
where 256K tasks are impractical, pass --clients explicitly and say so
next to the committed log — the JSON row records the shapes it ran.

Prints one JSON row (and appends it to --json-out if given):
  {"metric": "pipeline_commit_txn_s", "spec": ..., "backends":
   {"<backend>": {"txn_s": ..., "commit_p99_ms": ..., ...}}}

Usage:
  python scripts/bench_pipeline.py                         # legacy quick run
  python scripts/bench_pipeline.py --clients 4096 --ops 4 --mode wire \
      --backends native,tpu-force --json-out PIPELINE_r06.json
  python scripts/bench_pipeline.py --spec5
"""

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _pctl(samples, q):
    if not samples:
        return 0.0
    s = sorted(samples)
    return s[min(len(s) - 1, int(len(s) * q))]


def kernel_config(kernel_txns: int, tiered: bool):
    from foundationdb_tpu.config import KernelConfig

    kt = 1 << (kernel_txns - 1).bit_length()
    return KernelConfig(
        max_key_bytes=16,
        max_txns=kt,
        max_reads=4 * kt,
        max_writes=4 * kt,
        history_capacity=1 << max(17, (12 * kt).bit_length()),
        window_versions=5_000_000,
        delta_capacity=(1 << max(16, (4 * kt).bit_length())) if tiered else 0,
    )


def run_cluster(backend: str, args) -> dict:
    """In-process simulated cluster (virtual-time rates)."""
    from foundationdb_tpu.cluster.commit_proxy import NotCommitted
    from foundationdb_tpu.cluster.database import ClusterConfig, open_cluster
    from foundationdb_tpu.runtime.flow import all_of

    kcfg = kernel_config(args.kernel_txns, tiered=not args.classic_kernel)
    sched, cluster, db = open_cluster(
        ClusterConfig(
            n_commit_proxies=2, n_resolvers=2, n_storage=2,
            kernel_config=kcfg, resolver_backend=backend,
        )
    )

    stats = {"committed": 0, "conflicted": 0, "reads": 0}
    lat: list[float] = []

    async def client(cid: int):
        rng = np.random.default_rng(cid)
        for _ in range(args.ops):
            key = b"ycsb%06d" % int(rng.zipf(1.2) % args.records)
            txn = db.create_transaction()
            try:
                if rng.random() < 0.5:  # read-modify-write
                    t0 = sched.now()
                    v = await txn.get(key)
                    n = int.from_bytes(v or b"\0" * 8, "little")
                    txn.set(key, (n + 1).to_bytes(8, "little"))
                    await txn.commit()
                    if len(lat) < 100_000:
                        lat.append(sched.now() - t0)
                    stats["committed"] += 1
                else:
                    await txn.get(key)
                    stats["reads"] += 1
            except NotCommitted:
                stats["conflicted"] += 1

    t0 = time.perf_counter()
    tasks = [
        sched.spawn(client(i), name=f"ycsb{i}") for i in range(args.clients)
    ]
    sched.run_until(all_of([t.done for t in tasks]))
    wall = time.perf_counter() - t0
    virtual = sched.now()

    # ops / txn_s count SUCCESSFUL client operations (committed RMWs +
    # reads) in BOTH modes, so cluster-mode and wire-mode rows are
    # comparable; conflicted attempts ship as their own counter
    ops = stats["committed"] + stats["reads"]
    from foundationdb_tpu.cluster.consistency import check_cluster

    check_cluster(cluster)
    cluster.stop()
    return {
        **stats,
        "ops": ops,
        "virtual_s": round(virtual, 3),
        "wall_s": round(wall, 2),
        "txn_s": round(ops / virtual, 1),
        "txn_s_wall": round(ops / wall, 1),
        "commit_p50_ms": round(_pctl(lat, 0.50) * 1e3, 2),
        "commit_p99_ms": round(_pctl(lat, 0.99) * 1e3, 2),
        "consistency": "ok",
    }


async def _run_wire(backend: str, args) -> dict:
    """Real-wire mode: resolver/tlog/storage as OS processes over UDS."""
    from foundationdb_tpu.cluster import multiprocess as mp
    from foundationdb_tpu.models.types import CommitTransaction
    from foundationdb_tpu.wire.codec import Mutation

    trace_dir = getattr(args, "trace_dir", None)
    if trace_dir:
        # span-threaded wire run: the proxy process emits CommitProxy.*
        # micro-events + batch spans to its own JSONL file, resolve
        # requests carry (trace_id, span_id) + debug ids over the UDS
        # wire, and the resolver PROCESS writes child spans to ITS file
        # — scripts/commit_debug.py merges them into one cross-process
        # timeline per committed transaction.
        import time as _time

        from foundationdb_tpu.utils import spans as _spans
        from foundationdb_tpu.utils import trace as _tr

        os.makedirs(trace_dir, exist_ok=True)
        sink = _tr.TraceLog(
            min_severity=_tr.SEV_DEBUG, clock=_time.time,
            path=os.path.join(trace_dir, f"proxy-{backend}.jsonl"),
        )
        _tr.install(
            sink, _tr.TraceBatch(clock=_time.time, logger=sink, enabled=True)
        )
        _spans.set_exporter(_spans.SpanExporter(trace_log=sink))

    if backend in ("cpu", "tpu", "tpu-force"):
        kcfg = kernel_config(args.kernel_txns, tiered=not args.classic_kernel)
        os.environ["RESOLVER_KERNEL"] = (
            "KernelConfig("
            f"max_key_bytes={kcfg.max_key_bytes}, max_txns={kcfg.max_txns}, "
            f"max_reads={kcfg.max_reads}, max_writes={kcfg.max_writes}, "
            f"history_capacity={kcfg.history_capacity}, "
            f"window_versions={kcfg.window_versions}, "
            f"delta_capacity={kcfg.delta_capacity})"
        )
    import contextlib

    from foundationdb_tpu.runtime import census

    # resource-census gate: the drill owns this whole process, so the
    # gate is strict (fds included) — snapshot AFTER the trace sink is
    # installed (its file stays open past the run by design) and check
    # after teardown; any growth is a leak and fails the run
    census_pre = census.snapshot()

    # --socket-dir pins the role sockets to a caller-owned dir so an
    # EXTERNAL fdbtop can poll StatusRequest on them mid-run (the
    # check.sh fdbtop lane); default stays a self-cleaning tempdir
    sock_ctx = (
        contextlib.nullcontext(args.socket_dir)
        if getattr(args, "socket_dir", None)
        else tempfile.TemporaryDirectory()
    )
    with sock_ctx as sock_dir:
        def role_trace(name):
            if not trace_dir:
                return None
            return os.path.join(trace_dir, f"{name}-{backend}.jsonl")

        procs = [
            mp.spawn_role("resolver", sock_dir, backend=backend,
                          trace_file=role_trace("resolver")),
            mp.spawn_role("tlog", sock_dir),
            mp.spawn_role("storage", sock_dir),
        ]
        seq_proc = None
        if getattr(args, "sequencer", False):
            # the scale-out version allotment role: grants ride
            # GetCommitVersion, GRV rides ReportRawCommittedVersion
            seq_proc = mp.spawn_role("sequencer", sock_dir)
            procs.append(seq_proc)
        if getattr(args, "ratekeeper", False):
            # the admission-control role: polls every role's
            # StatusRequest sensors (plus the parent's proxy0.sock when
            # --serve-status is on) and serves the budget over
            # GetRateInfo — the pipeline's GRV front door enforces it
            procs.append(mp.spawn_role(
                "ratekeeper", sock_dir,
                peers=[p.address for p in procs]
                + [os.path.join(sock_dir, "proxy0.sock")],
            ))
        try:
            resolver = await mp.connect(procs[0])
            tlog = await mp.connect(procs[1])
            storage = await mp.connect(procs[2])
            seq_conn = None
            if seq_proc is not None:
                seq_conn = await mp.connect(seq_proc)
                # boot the resolver's version chain at the sequencer's
                # recovery version (what the controller's recovery walk
                # does) so the first grant's prev_version resolves
                await resolver.call(
                    mp.TOKEN_RESOLVE,
                    mp.ResolveTransactionBatchRequest(
                        prev_version=-1, version=0,
                        last_received_version=-1, epoch=0,
                    ),
                )
            rk_conn = None
            if getattr(args, "ratekeeper", False):
                rk_conn = await mp.connect(procs[-1])
            # resolve-hop frame A/B (r12): --resolve-path pins the
            # columnar vs object frame per run; None = RESOLVE_COLUMNAR
            # env default (columnar)
            rp = getattr(args, "resolve_path", None)
            pipe = mp.ProxyPipeline(
                [resolver], tlog, storage,
                batch_interval=0.001, max_batch=args.batch,
                trace=bool(trace_dir),
                ratekeeper=rk_conn,
                resolve_columnar=(None if rp is None else rp == "columnar"),
                sequencer=seq_conn,
            )
            pipe.start()
            status_server = None
            if getattr(args, "serve_status", False):
                # the parent's own proxy/GRV qos blocks on proxy0.sock,
                # next to the role sockets — fdbtop sees every role
                status_server = mp.serve_status(sock_dir, pipe)
                await status_server.start()

            stats = {"committed": 0, "conflicted": 0, "reads": 0,
                     "grv_throttled": 0}
            committed_by_key: dict[bytes, int] = {}
            #: (key, commit version, value written) per acknowledged RMW
            acked: list[tuple[bytes, int, bytes]] = []
            lat: list[float] = []

            async def grv():
                # client-side backoff on grv_throttled: the front door
                # sheds past its queue bound under admission control;
                # the retry-with-backoff IS the client contract
                backoff = 0.001
                while True:
                    try:
                        return await pipe.get_read_version()
                    except mp.GrvThrottledError:
                        stats["grv_throttled"] += 1
                        await asyncio.sleep(backoff)
                        backoff = min(backoff * 2, 0.1)

            async def client(cid: int):
                rng = np.random.default_rng(cid)
                for op_i in range(args.ops):
                    key = b"ycsb%06d" % int(rng.zipf(1.2) % args.records)
                    kr = (key, key + b"\x00")
                    if rng.random() < 0.5:  # RMW with bounded retries
                        # t0 spans the WHOLE retry loop: the client-
                        # observed commit latency includes every
                        # conflicted attempt's GRV+read+commit round
                        t0 = time.perf_counter()
                        for _attempt in range(8):
                            rv = await grv()
                            cur = await pipe.read(key, rv)
                            n = int.from_bytes(cur or b"\0" * 8, "little")
                            txn = CommitTransaction(
                                read_conflict_ranges=[kr],
                                write_conflict_ranges=[kr],
                                read_snapshot=rv,
                                mutations=[Mutation(
                                    0, key,
                                    (n + 1).to_bytes(8, "little"),
                                )],
                            )
                            if trace_dir:
                                from foundationdb_tpu.utils import (
                                    commit_debug as _cdbg,
                                )
                                from foundationdb_tpu.utils import (
                                    trace as _tr,
                                )

                                txn.debug_id = (
                                    f"wire-{cid}-{op_i}-{_attempt}"
                                )
                                _tr.g_trace_batch.add_event(
                                    "CommitDebug", txn.debug_id,
                                    _cdbg.COMMIT_BEFORE,
                                )
                            try:
                                cv = await pipe.commit(txn)
                                acked.append(
                                    (key, cv, txn.mutations[0].param2)
                                )
                                if trace_dir:
                                    _tr.g_trace_batch.add_event(
                                        "CommitDebug", txn.debug_id,
                                        _cdbg.COMMIT_AFTER,
                                    )
                                if len(lat) < 100_000:
                                    lat.append(time.perf_counter() - t0)
                                stats["committed"] += 1
                                committed_by_key[key] = (
                                    committed_by_key.get(key, 0) + 1
                                )
                                break
                            except mp.NotCommittedError:
                                stats["conflicted"] += 1
                    else:
                        rv = await grv()
                        await pipe.read(key, rv)
                        stats["reads"] += 1

            t0 = time.perf_counter()
            await asyncio.gather(*(client(c) for c in range(args.clients)))
            wall = time.perf_counter() - t0

            # exact-count consistency check across the process boundary
            rv = await grv()
            snap = await storage.call(
                mp.TOKEN_STORAGE_SNAPSHOT, mp.StorageSnapshotReq(version=rv)
            )
            got = {k: int.from_bytes(v, "little") for k, v in snap.kvs}
            for key, cnt in committed_by_key.items():
                assert got.get(key, 0) == cnt, (
                    f"{key}: storage={got.get(key, 0)} committed={cnt}"
                )
            # every acknowledged write reads back at its own commit
            # version: a later increment of the key lands at a later
            # version, and a same-batch one would have conflicted
            readback = await asyncio.gather(
                *(pipe.read(key, cv) for key, cv, _ in acked)
            )
            for (key, cv, value), got_v in zip(acked, readback):
                assert got_v == value, (
                    f"{key}@{cv}: storage={got_v!r} acknowledged={value!r}"
                )
            stats["acked_read_back"] = len(acked)

            # columnar-vs-object structural accounting from the resolver
            # role (status qos.resolve_path): full key-data copies per
            # batch between wire payload and conflict-backend input, and
            # per-txn Python objects materialized by decode — the
            # "two copies" claim as ledger-gated numbers (perfcheck),
            # deterministic ratios regardless of batching/timing.
            st = await resolver.call(
                mp.TOKEN_STATUS, mp.StatusRequest(pad=0)
            )
            rqos = json.loads(st.payload)["qos"]
            ps = rqos["resolve_path"]
            # what served the resolves: conflict set, JAX backend and
            # device kind, plus the kernel's dispatch/compile counters
            stats["resolver_kernel"] = rqos["kernel"]
            n_batches = ps["columnar_batches"] + ps["object_batches"]
            stats["resolve_copies_per_batch"] = round(
                ps["copies"] / max(1, n_batches), 3
            )
            stats["resolve_decode_allocs_per_txn"] = round(
                ps["decode_allocs"] / max(1, ps["txns"]), 3
            )
            stats["resolve_path"] = (
                "columnar" if ps["columnar_batches"] else "object"
            )
            hold = float(getattr(args, "hold", 0) or 0)
            if hold:
                # keep the cluster (and status sockets) alive so an
                # external fdbtop can poll a LIVE wire cluster
                print(f"[hold] cluster live for {hold:.0f}s "
                      f"(sockets in {sock_dir})", flush=True)
                await asyncio.sleep(hold)
            await pipe.stop()
            if status_server is not None:
                await status_server.close()
            # rk_conn included: leaving the ratekeeper connection open
            # was exactly the leak class the census gate exists to
            # catch (res.leak-on-error-path's dynamic twin)
            for c in (resolver, tlog, storage, rk_conn, seq_conn):
                if c is not None:
                    await c.close()
        finally:
            for p in procs:
                p.stop()
            os.environ.pop("RESOLVER_KERNEL", None)
    # post-drain census: one loop-tick sleep lets asyncio finish the
    # writer/transport closes queued by the teardown above
    await asyncio.sleep(0.1)
    census.check_drained(census_pre, census.snapshot(),
                         label="bench_pipeline wire")
    if trace_dir:
        # merge this process's trace with the resolver process's and
        # reconstruct: committed wire transactions must chain across the
        # process boundary (same trace ids on both sides of the UDS)
        from foundationdb_tpu.utils import commit_debug as cd

        sink.flush()
        # rolled generations first (TraceLog rotates path -> path.1 at
        # max_events): a big run's older half lives in the .1 files
        files = [
            p
            for base in (
                os.path.join(trace_dir, f"proxy-{backend}.jsonl"),
                os.path.join(trace_dir, f"resolver-{backend}.jsonl"),
            )
            for p in (base + ".1", base)
            if os.path.exists(p)
        ]
        idx = cd.TraceIndex(cd.load_jsonl(files))
        tls = idx.timelines()
        cross = [
            tl for tl in tls
            if cd.RESOLVER_BEFORE in tl.locations()
        ]
        print(
            f"[trace] {len(tls)} committed timeline(s), "
            f"{len(cross)} crossed the process boundary "
            f"(resolver events from the child process); "
            f"files: {files}", flush=True,
        )
        stats["traced_timelines"] = len(tls)
        stats["traced_cross_process"] = len(cross)
    # same successful-ops definition as cluster mode (cross-mode
    # comparable); "conflicted" counts retried attempts
    ops = stats["committed"] + stats["reads"]
    return {
        **stats,
        "ops": ops,
        "wall_s": round(wall, 2),
        "txn_s": round(ops / wall, 1),
        "commit_p50_ms": round(_pctl(lat, 0.50) * 1e3, 2),
        "commit_p99_ms": round(_pctl(lat, 0.99) * 1e3, 2),
        "consistency": "ok",
    }


def emit_row(args, results: dict) -> dict:
    """Build + print the run's JSON row, append --json-out, and land
    one perf-ledger record per backend (the shared tail of normal runs
    and each smoke sub-run)."""
    row = {
        "metric": "pipeline_commit_txn_s",
        "spec": "config5_ycsb_a",
        "mode": args.mode,
        "inflight": args.clients,
        "ops_per_client": args.ops,
        "records": args.records,
        "batch": args.batch,
        "kernel_txns": args.kernel_txns,
        "kernel": "classic" if args.classic_kernel else "tiered",
        "backends": results,
    }
    if getattr(args, "knob_overrides", None):
        row["knob_overrides"] = args.knob_overrides
    # the resolve-hop frame, as OBSERVED by the resolver role's
    # path_stats (wire mode only) — never re-derived from env/args, so
    # the ledger's fingerprint knob cannot mislabel a run if the
    # pipeline's frame-selection policy grows a new fallback
    observed = {
        r["resolve_path"] for r in results.values() if "resolve_path" in r
    }
    if len(observed) == 1:
        row["resolve_path"] = observed.pop()
    print(json.dumps(row))
    if args.json_out:
        with open(args.json_out, "a") as f:
            f.write(json.dumps(row) + "\n")
    if not args.no_perf:
        # canonical perf-ledger rows (one per backend), same converter
        # the historical-artifact importer uses so fingerprint keys line
        # up across PIPELINE_r0*.json and fresh runs
        from foundationdb_tpu.utils import perf

        fp = perf.device_fingerprint()
        for rec in perf.pipeline_row_to_records(row, fingerprint=None):
            # fingerprint.backend stays the RESOLVER backend (also in
            # the workload key), but the HOST device identity — device
            # kind/count, jax/jaxlib — must be real: without it a
            # tpu-force wire run on a CPU laptop and one on a v5e
            # would share a hardware comparability key
            rec["fingerprint"].update(
                {k: fp[k] for k in ("device_kind", "device_count",
                                    "jax_version", "jaxlib_version",
                                    "python_version", "machine")}
            )
            path = perf.append(rec, path=args.perf_ledger)
        print(f"[perf] {len(results)} ledger row(s) appended to {path}",
              flush=True)
    return row


def run_smoke(args) -> int:
    """The check.sh lane, now with the columnar A/B (r12):

    1. native + columnar frame, traced: consistency ok + >=1
       cross-process commit_debug timeline (the original contract).
    2. native + object frame at identical shapes: DECISION PARITY —
       committed/read/op counts must match run 1 exactly (clients draw
       from per-client seeded rngs, so both runs submit the same
       transactions; a frame that changed any verdict changes the
       counts).
    3. tpu-force + columnar at a tiny kernel (--kernel-txns 64): the
       structural two-copies row — resolve_copies_per_batch == 2 and
       resolve_decode_allocs_per_txn == 0 — asserted here AND gated by
       the perfcheck lane against the committed perf history.
    """
    args.mode = "wire"
    args.clients = 32
    args.ops = 2
    if not args.trace_dir:
        import tempfile as _tf

        args.trace_dir = _tf.mkdtemp(prefix="bench_pipe_smoke_")
    if not args.perf_ledger and "FDBTPU_PERF_LEDGER" not in os.environ:
        # smoke rows are still emitted (schema-valid, gate-checked by
        # tests) but land next to the trace files, not in the committed
        # history — a green CI run must not dirty it
        args.perf_ledger = os.path.join(args.trace_dir, "perf_smoke.jsonl")

    def sub(backend, resolve_path, *, traced, kernel_txns=None):
        a = argparse.Namespace(**vars(args))
        a.resolve_path = resolve_path
        if not traced:
            a.trace_dir = None
        if kernel_txns is not None:
            a.kernel_txns = kernel_txns
        print(f"== smoke {backend} / {resolve_path} frame ==", flush=True)
        res = asyncio.run(_run_wire(backend, a))
        emit_row(a, {backend: res})
        return res

    r_col = sub("native", "columnar", traced=True)
    r_obj = sub("native", "object", traced=False)
    r_tpu = sub("tpu-force", "columnar", traced=False, kernel_txns=64)

    failures = []
    if r_col.get("consistency") != "ok" or r_obj.get("consistency") != "ok" \
            or r_tpu.get("consistency") != "ok":
        failures.append("consistency not ok")
    if (r_col.get("traced_timelines", 0) < 1
            or r_col.get("traced_cross_process", 0) < 1):
        failures.append("no cross-process commit_debug timeline")
    if r_col.get("resolve_path") != "columnar" \
            or r_obj.get("resolve_path") != "object":
        failures.append(
            f"frame routing: {r_col.get('resolve_path')} / "
            f"{r_obj.get('resolve_path')}"
        )
    for k in ("committed", "reads", "ops"):
        if r_col.get(k) != r_obj.get(k):
            failures.append(
                f"columnar/object {k} parity: "
                f"{r_col.get(k)} vs {r_obj.get(k)}"
            )
    if r_tpu.get("resolve_copies_per_batch") != 2.0:
        failures.append(
            "columnar copies per batch "
            f"{r_tpu.get('resolve_copies_per_batch')} != 2"
        )
    if r_tpu.get("resolve_decode_allocs_per_txn") != 0.0:
        failures.append(
            "columnar decode allocs "
            f"{r_tpu.get('resolve_decode_allocs_per_txn')} != 0"
        )
    if failures:
        print(f"bench_pipeline smoke FAILED: {failures}")
        return 1
    print("bench_pipeline smoke ok (columnar A/B parity + two-copies row)")
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("legacy", nargs="*", type=int,
                    help="legacy positional [n_clients] [n_ops]")
    ap.add_argument("--mode", choices=("cluster", "wire"), default="cluster")
    ap.add_argument("--clients", type=int, default=64,
                    help="in-flight client transactions (concurrent tasks)")
    ap.add_argument("--ops", type=int, default=40, help="ops per client")
    ap.add_argument("--records", type=int, default=1000,
                    help="YCSB record-set size")
    ap.add_argument("--batch", type=int, default=4096,
                    help="proxy max batch (wire mode)")
    ap.add_argument("--kernel-txns", type=int, default=4096,
                    help="resolver kernel max_txns for tpu backends")
    ap.add_argument("--backends", default=None,
                    help="comma list; default cpu,tpu-force (cluster) / "
                         "native,tpu-force (wire)")
    ap.add_argument("--classic-kernel", action="store_true",
                    help="tpu backends use the classic (non-tiered) kernel")
    ap.add_argument("--resolve-path", choices=("columnar", "object"),
                    default=None,
                    help="wire mode: resolve-hop frame A/B — columnar "
                         "(pack once at the proxy, decode straight into "
                         "kernel tensors; default) vs the per-txn object "
                         "frame (the RESOLVE_COLUMNAR=0 escape hatch)")
    ap.add_argument("--spec5", action="store_true",
                    help="BASELINE.md:36 config-5 preset: wire mode, 256K "
                         "in-flight, both backends")
    ap.add_argument("--trace-dir", default=None,
                    help="wire mode: write per-process TraceLog JSONL "
                         "files here, thread span contexts + debug ids "
                         "across the UDS, and reconstruct cross-process "
                         "timelines after the run (commit_debug)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI lane: tiny in-flight traced wire run (native "
                         "backend); exits nonzero unless consistency is "
                         "\"ok\" AND >=1 complete cross-process "
                         "commit_debug timeline reconstructed")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--perf-ledger", default=None,
                    help="append the run's perf-ledger rows here "
                         "(default: perf/history.jsonl)")
    ap.add_argument("--no-perf", action="store_true",
                    help="skip the perf-ledger append")
    ap.add_argument("--socket-dir", default=None,
                    help="wire mode: pin role sockets to this dir so an "
                         "external fdbtop can poll them mid-run")
    ap.add_argument("--serve-status", action="store_true",
                    help="wire mode: serve the parent's commit/GRV proxy "
                         "qos blocks on proxy0.sock (StatusRequest RPC)")
    ap.add_argument("--ratekeeper", action="store_true",
                    help="wire mode: spawn the ratekeeper role (polls "
                         "every role's StatusRequest sensors, serves the "
                         "budget over GetRateInfo) and enforce it at the "
                         "pipeline's GRV front door")
    ap.add_argument("--sequencer", action="store_true",
                    help="wire mode: spawn the sequencer role and route "
                         "the pipeline's version allotment through its "
                         "GetCommitVersion grants (the scale-out commit "
                         "path, opt-in so legacy baselines stay keyed)")
    ap.add_argument("--hold", type=float, default=0.0,
                    help="wire mode: keep the cluster alive N seconds "
                         "after the workload (fdbtop polling window)")
    args = ap.parse_args()
    # autotune trial hook: FDBTPU_KNOB_OVERRIDES drives server-knob
    # points (adaptive-batch count/bytes/interval targets) through this
    # harness; what was APPLIED lands in the row's knob fingerprint so
    # every trial keys apart in the ledger
    from foundationdb_tpu.utils.knobs import SERVER_KNOBS

    args.knob_overrides = SERVER_KNOBS.apply_env_overrides()
    if args.legacy:
        args.clients = args.legacy[0]
        if len(args.legacy) > 1:
            args.ops = args.legacy[1]
    if args.ratekeeper:
        # the ratekeeper's actualTps feedback comes from the parent's
        # status socket (the embedded GRV block): without it the law
        # scales every engaged limit from min_tps and a throttle would
        # clamp to the floor instead of tracking the admission rate
        args.serve_status = True
    if args.smoke:
        return run_smoke(args)
    if args.spec5:
        args.mode = "wire"
        args.clients = 256 * 1024
        args.ops = 1
    backends = (
        args.backends.split(",") if args.backends
        else (["native", "tpu-force"] if args.mode == "wire"
              else ["cpu", "tpu-force"])
    )

    results = {}
    for backend in backends:
        print(f"== backend {backend} ({args.mode}, {args.clients} in-flight, "
              f"{args.ops} ops/client) ==", flush=True)
        if args.mode == "wire":
            res = asyncio.run(_run_wire(backend, args))
        else:
            res = run_cluster(backend, args)
        results[backend] = res
        print(json.dumps({backend: res}), flush=True)

    emit_row(args, results)
    return 0


if __name__ == "__main__":
    sys.exit(main())

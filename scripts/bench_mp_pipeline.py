#!/usr/bin/env python
"""YCSB-A pipeline bench across real OS processes.

VERDICT r1 task 5's acceptance run: client + proxy (this process) with
resolver, tlog, and storage as separate OS processes over the serialized
wire (UDS). 50% read-modify-write / 50% read over a Zipf-hot record set,
retry-on-conflict clients, exact-count consistency check at the end.

Usage: python scripts/bench_mp_pipeline.py [n_clients] [n_ops] [backend]
  backend: native (default, C++ skip-list) | cpu (oracle) | tpu
"""

import asyncio
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from foundationdb_tpu.cluster import multiprocess as mp
from foundationdb_tpu.models.types import CommitTransaction
from foundationdb_tpu.wire.codec import Mutation


async def run(n_clients: int, n_ops: int, backend: str) -> None:
    with tempfile.TemporaryDirectory() as sock_dir:
        procs = [
            mp.spawn_role("resolver", sock_dir, backend=backend),
            mp.spawn_role("tlog", sock_dir),
            mp.spawn_role("storage", sock_dir),
        ]
        try:
            resolver = await mp.connect(procs[0])
            tlog = await mp.connect(procs[1])
            storage = await mp.connect(procs[2])
            pipe = mp.ProxyPipeline(
                [resolver], tlog, storage, batch_interval=0.001, max_batch=4096
            )
            pipe.start()

            stats = {"committed": 0, "conflicted": 0, "reads": 0}
            committed_by_key: dict[bytes, int] = {}

            async def client(cid: int):
                rng = np.random.default_rng(cid)
                for _ in range(n_ops):
                    key = b"ycsb%05d" % int(rng.zipf(1.2) % 1000)
                    kr = (key, key + b"\x00")
                    if rng.random() < 0.5:  # read-modify-write w/ retries
                        for _attempt in range(8):
                            rv = await pipe.get_read_version()
                            cur = await pipe.read(key, rv)
                            n = int.from_bytes(cur or b"\0" * 8, "little")
                            try:
                                await pipe.commit(
                                    CommitTransaction(
                                        read_conflict_ranges=[kr],
                                        write_conflict_ranges=[kr],
                                        read_snapshot=rv,
                                        mutations=[
                                            Mutation(
                                                0,
                                                key,
                                                (n + 1).to_bytes(8, "little"),
                                            )
                                        ],
                                    )
                                )
                                stats["committed"] += 1
                                committed_by_key[key] = (
                                    committed_by_key.get(key, 0) + 1
                                )
                                break
                            except mp.NotCommittedError:
                                stats["conflicted"] += 1
                    else:
                        rv = await pipe.get_read_version()
                        await pipe.read(key, rv)
                        stats["reads"] += 1

            t0 = time.perf_counter()
            await asyncio.gather(*(client(c) for c in range(n_clients)))
            wall = time.perf_counter() - t0

            # exact-count consistency check across the process boundary
            rv = await pipe.get_read_version()
            snap = await storage.call(
                mp.TOKEN_STORAGE_SNAPSHOT, mp.StorageSnapshotReq(version=rv)
            )
            got = {k: int.from_bytes(v, "little") for k, v in snap.kvs}
            for key, cnt in committed_by_key.items():
                assert got.get(key, 0) == cnt, (
                    f"{key}: storage={got.get(key, 0)} committed={cnt}"
                )
            ops = stats["committed"] + stats["reads"]
            print(
                f"backend={backend} clients={n_clients} "
                f"ops={ops} committed={stats['committed']} "
                f"reads={stats['reads']} conflicted={stats['conflicted']}"
            )
            print(
                f"wall {wall:.2f}s -> {ops / wall:,.0f} op/s across "
                f"{1 + len(procs)} OS processes; consistency check: OK"
            )
            await pipe.stop()
            for c in (resolver, tlog, storage):
                await c.close()
        finally:
            for p in procs:
                p.stop()


def main():
    n_clients = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    n_ops = int(sys.argv[2]) if len(sys.argv) > 2 else 50
    backend = sys.argv[3] if len(sys.argv) > 3 else "native"
    asyncio.run(run(n_clients, n_ops, backend))


if __name__ == "__main__":
    main()

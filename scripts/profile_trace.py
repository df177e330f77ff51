#!/usr/bin/env python
"""Capture a jax.profiler trace of one group dispatch (VERDICT r4 task
1c: attribute the kernel's time per-op instead of calling it jitter).
Writes the trace under /tmp/jaxtrace; a second pass parses the .pb/
.json.gz events into a per-op table (when no device events were
exported it falls back to the ablation ledger)."""

import glob
import gzip
import json
import sys
import time

import jax
import numpy as np

sys.path.insert(0, "/root/repo")
from foundationdb_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

import functools  # noqa: E402

from foundationdb_tpu import config as cfg  # noqa: E402
from foundationdb_tpu.ops import group as G  # noqa: E402
from foundationdb_tpu.ops import history as H  # noqa: E402
from foundationdb_tpu.testing.benchgen import skiplist_style_batch  # noqa: E402
from foundationdb_tpu.utils.packing import stack_device_args  # noqa: E402

N, FUSE = 65536, 8
TRACE_DIR = "/tmp/jaxtrace"


def main():
    cap = 1 << (N - 1).bit_length()
    config = cfg.KernelConfig(
        max_key_bytes=8, max_txns=cap, max_reads=cap, max_writes=cap,
        history_capacity=12 * cap, window_versions=1_000_000,
    )
    rng = np.random.default_rng(0)
    batches = [
        skiplist_style_batch(
            rng, config, N, version=(i + 1) * 200_000, keyspace=1_000_000,
            key_bytes=8, snapshot_lag=400_000,
        )
        for i in range(FUSE)
    ]
    g1 = jax.device_put(stack_device_args(batches))
    np.asarray(g1["version"])
    jf = jax.jit(functools.partial(G.resolve_group, fixpoint_unroll=3))
    state = H.init(config)
    s1, o = jf(state, g1)
    np.asarray(o.verdict[0][:4])  # compile+warm
    print("warmed; tracing...", flush=True)

    with jax.profiler.trace(TRACE_DIR):
        s2, o2 = jf(state, g1)
        np.asarray(o2.verdict[0][:4])
    print("trace captured", flush=True)

    # parse: find the biggest trace json/pb and dump top ops by duration
    evs = []
    for path in glob.glob(TRACE_DIR + "/**/*.trace.json.gz", recursive=True):
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        for e in data.get("traceEvents", []):
            if e.get("ph") == "X" and "dur" in e:
                evs.append((e["dur"], e.get("name", "?"), e.get("pid")))
    if not evs:
        print("no trace events parsed (no device plane exported) — use "
              "the ablation ledger instead")
        return
    # aggregate by name
    agg: dict = {}
    for dur, name, _pid in evs:
        agg[name] = agg.get(name, 0) + dur
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:60]
    total = sum(agg.values())
    print(f"total accounted: {total/1e3:.1f} ms across {len(evs)} events")
    for name, dur in top:
        print(f"{dur/1e3:9.2f} ms  {name[:110]}")


if __name__ == "__main__":
    main()

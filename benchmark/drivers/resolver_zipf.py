"""Driver: one Resolver role served over the wire, on the hot-key stream.

`resolver_wire` end to end: its closed-loop proxy connections, window,
tail, end-to-end numbers, reference check and Outcome, run on a copy
of that module whose frames come from `gen_zipf.rmw_batch` (traffic
generator "rmw_batch") in place of `gen.resolver_batch`. Each frame is
built on a worker thread ahead of the senders, so the ~0.1 s a 64K-txn
frame takes to draw overlaps the batch in flight; the build times go to
stderr once the run is done. A frame holds the traffic's transactions,
or as many as the role's kernel holds where that is fewer (`batch_txns`).

The role must count its fixpoint applications (`qos.stages.
fixpoint_iters`), the mechanism this traffic works. A program whose
conflict set keeps no such count is refused before the role starts:
that program's reply assembly slices the key of every history-hit read
row out of the frame at a cost that grows with the row's position,
about 0.36 ms a hit at this frame size on a CPU host, and a batch of
this stream has about 68K such rows, so it would take tens of seconds
a batch and could not finish its warm-up within a run.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time
import types
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import gen  # noqa: E402
import gen_zipf  # noqa: E402
import harness  # noqa: E402

#: frames built ahead of the one asked for
AHEAD = 2


class Frames:
    """`rmw_batch` as `resolver_wire` asks for frames, in index order:
    each request returns the frame built ahead and queues the next
    ones on a worker thread."""

    def __init__(self):
        self.pool = ThreadPoolExecutor(1, thread_name_prefix="frames")
        self.ahead: dict = {}
        self.build_s: list[float] = []

    def _build(self, *key):
        t0 = time.perf_counter()
        out = gen_zipf.rmw_batch(*key)
        self.build_s.append(time.perf_counter() - t0)
        return out

    def resolver_batch(self, traffic, config, seed, index):
        def key(i):
            return (seed, i)

        fut = self.ahead.pop(key(index), None)
        if fut is None:
            fut = self.pool.submit(self._build, traffic, config, seed, index)
        for i in range(index + 1, index + 1 + AHEAD):
            if key(i) not in self.ahead:
                self.ahead[key(i)] = self.pool.submit(
                    self._build, traffic, config, seed, i)
        for k in [k for k in self.ahead if k[0] != seed or k[1] < index]:
            self.ahead.pop(k).cancel()
        return fut.result()

    def close(self) -> None:
        self.pool.shutdown(wait=True, cancel_futures=True)


def batch_txns(cell) -> int:
    """Transactions a frame holds: the traffic's, or as many as the
    role's kernel holds where it holds fewer (a commit proxy sizes its
    batches to what its resolver takes). The cell's own kernel holds
    the whole 64K-txn batch; a smaller one is a test's."""
    k, tr = cell.config["kernel"], cell.traffic
    return min(tr["txns_per_batch"], k["max_txns"],
               k["max_reads"] // tr["ops_per_txn"])


def counts_fixpoint() -> bool:
    """Whether the program's conflict set counts fixpoint applications
    (read from its JAX-free stage telemetry: this process never imports
    JAX)."""
    from foundationdb_tpu.models.conflict_base import KernelStageMetrics

    return "fixpoint_iters" in KernelStageMetrics().stages()


async def run(cell, platform: str):
    if cell.traffic["generator"] != "rmw_batch":
        raise ValueError(f"traffic for generator "
                         f"{cell.traffic['generator']!r}, this driver "
                         f"reads 'rmw_batch'")
    if not counts_fixpoint():
        raise RuntimeError("the program's conflict set does not count "
                           "fixpoint applications; this cell needs one "
                           "that does")
    wire = harness.load_module(os.path.join(HERE, "drivers",
                                            "resolver_wire.py"))
    frames = Frames()
    wire.gen = types.SimpleNamespace(resolver_batch=frames.resolver_batch,
                                     batch_version=gen.batch_version)
    n = batch_txns(cell)
    if n < cell.traffic["txns_per_batch"]:
        print(f"frames: {n} txns a batch, as many as the kernel holds",
              file=sys.stderr)
    # resolver_wire draws every frame through its `gen.resolver_batch`
    as_wire = dataclasses.replace(
        cell, traffic={**cell.traffic, "generator": "resolver_batch",
                       "txns_per_batch": n})
    try:
        return await wire.run(as_wire, platform)
    finally:
        frames.close()
        s = sorted(frames.build_s)
        if s:
            print(f"frames: {len(s)} built, median "
                  f"{s[len(s) // 2] * 1e3:.1f} ms, max {s[-1] * 1e3:.1f} ms "
                  f"(a worker thread, {AHEAD} ahead)", file=sys.stderr)

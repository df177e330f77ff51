#!/usr/bin/env python
"""Headline bench: resolver throughput at 64K-txn batches.

The TPU conflict kernel versus the measured CPU baseline
(foundationdb_tpu/native — the stand-in for the reference's
`fdbserver -r skiplisttest` microbench, fdbserver/SkipList.cpp:1082-1177:
uniform 1M keyspace, one read + one write range per txn; snapshots lag up
to two batch-versions so reads really contend with history). The device
path is the DELTA-TIERED kernel (foundationdb_tpu.ops.delta —
G-independent compile, delta-tier merges, periodic compaction, optional
read dedup).

Prints ONE JSON line whose PRIMARY `value` is the TRANSFER-INCLUSIVE
pipelined rate (pack -> host->device copy -> kernel, overlapped by
TpuConflictSet.resolve_stream_pipelined) — the operative number a live
resolver fed by a proxy would see (VERDICT r5 task 2; the r3-r5 primary
was device-resident and is now the secondary `device_resident_txn_s`).

Phases: (1) CPU baseline timing + verdicts; (2) parity phase — the TPU
kernel resolves the same stream and decisions are asserted identical;
(3) device-resident pipelined throughput (kernel-only, inputs pre-staged
— the ablation ledger's "kernel" stage); (3b) PRIMARY transfer-inclusive
pipelined throughput + the per-stage ablation ledger
(pack / transfer / kernel / fence); (4) per-batch latency probe with
blocking calls, device-resident and transfer-inclusive.

Env overrides: BENCH_TXNS (default 65536), BENCH_BATCHES (default 32),
BENCH_CPU_BATCHES (default 4), BENCH_MODE (uniform | zipf | range —
BASELINE.json configs 1-3), BENCH_FUSE (group size; tiered compiles ONCE for any value),
BENCH_DELTA_CAP, BENCH_COMPACT_INTERVAL, BENCH_REPS.

Flags: --profile-dir DIR captures a jax.profiler device/compile trace
of the PRIMARY measurement phase (TensorBoard/XProf xplanes);
--perf-ledger PATH / --no-perf control the perf-ledger row every run
appends to perf/history.jsonl (foundationdb_tpu/utils/perf.py).
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


#: the stream shape every mode shares (skiplisttest's: 1M keyspace)
KEYSPACE = 1_000_000
VERSION_STEP = 200_000
WINDOW = 1_000_000  # floor rises after 5 batches -> steady-state GC
SNAPSHOT_LAG = 2 * VERSION_STEP  # spans ~2 batches: history conflicts real


def build_stream(mode: str, n_txns: int, n_batches: int, *,
                 fuse: int = 8,
                 compact_interval: int = 0, delta_cap: int = 0,
                 sweep_allowed: bool = True):
    """The bench stream for `mode` (BASELINE configs 1-3 and the YCSB
    letters) and the KernelConfig sized for it — seeded, so every
    caller (this bench, chip_smoke.py) resolves the same batches.
    Returns (config, batches, stream_profile, routed_backend).
    compact_interval / delta_cap 0 mean the defaults below."""
    keyspace, version_step, snapshot_lag = KEYSPACE, VERSION_STEP, SNAPSHOT_LAG
    window = WINDOW
    # BASELINE configs 1-3 plus the YCSB letter suite (ISSUE 14 —
    # workload breadth: B/C/D are zipf point mixes at different write
    # rates / recency, E is the range-scan-heavy profile the router
    # used to exile to the CPU skiplist; with the sorted-endpoint sweep
    # configured it stays on device and this bench re-measures that
    # routing every run)
    gen_kw = {
        "uniform": {},
        "zipf": {"zipf": 1.1, "keyspace": 10_000_000},  # hot-key contention
        "range": {"range_len": 500},  # wide scans vs point-ish writes
        "ycsb_b": {"zipf": 1.1, "keyspace": 10_000_000},
        "ycsb_c": {"zipf": 1.1, "keyspace": 10_000_000},
        "ycsb_d": {"keyspace": 10_000_000},
        "ycsb_e": {"zipf": 1.1, "scan_max": 100},
    }[mode]
    ycsb = mode.startswith("ycsb")
    # Fixpoint unroll depth per contention profile: measured convergence
    # depth (scripts/iters_model.py: uniform 3, zipf 6, range 12) plus
    # margin. fixpoint_latch drops the residual while_loop (~50ms/group
    # of XLA pessimization at ZERO iterations); a deeper-than-unroll
    # chain trips the unconverged latch and this script re-runs the
    # stream on the exact while kernel — loud fallback, never wrong.
    # Fixpoint depth per mode: the idealized model (scripts/
    # iters_model.py) says uniform 3 / zipf 6 / range 12, but the REAL
    # uniform stream's history masks deepen chains past 4 (the r4 latch
    # tripped at 3 and 4). r4 ran uniform on the EXACT kernel because at
    # the old per-application cost unroll>=5 broke even with the
    # residual while — and the r5 attempt (latched unroll 6 + the
    # prefix-count cross) MEASURED 702K txn/s vs the exact path's
    # 891-973K, so uniform stays on the EXACT kernel. zipf/range keep
    # the latch with margin; a trip falls back to the exact kernel
    # (loud, never wrong — the warm pass checks before any timed pass,
    # and prewarm_exact makes the swap compile-free).
    unroll = {"uniform": 3, "zipf": 8, "range": 14, "ycsb_b": 8,
              "ycsb_c": 3, "ycsb_d": 8, "ycsb_e": 14}[mode]
    latch = mode != "uniform"
    # ycsb_e arms the ISSUE-14 device-native range path: the
    # sorted-endpoint sweep probe + spill-and-compact pressure handling
    # (both tiered-only; BENCH_SWEEP=0 ablates back to the probe path)
    sweep = mode == "ycsb_e" and sweep_allowed

    from foundationdb_tpu.config import KernelConfig
    from foundationdb_tpu.testing.benchgen import skiplist_style_batch

    cap = 1 << (n_txns - 1).bit_length()
    # hard bound on live boundaries: a range contributes its begin
    # (live) plus its end (carrier of the prior value), and the GC
    # floor trails one batch behind the newest — so
    # 2*writes/batch x (window/step + 1) = 12*cap live rows worst
    # case (coalescing only shrinks it; overflow raises, never lies —
    # 10*cap overflowed at BENCH_TXNS=16384 where uniform ranges
    # barely coalesce)
    hist_cap = 12 * cap
    # delta tier sized for the same window-worst-case (compaction every
    # group trims it back; occupancy scales with DISTINCT written
    # boundaries, so zipf keeps it tiny — the ledger reports both)
    delta_cap = delta_cap or hist_cap
    # group size for fused dispatch (also the default compaction
    # cadence: compact_interval counts BATCHES, so one compaction per
    # fused group). The tiered kernel compiles once for ANY value.
    compact_interval = compact_interval or fuse
    config = KernelConfig(
        max_key_bytes=8,
        max_txns=cap,
        max_reads=cap,
        max_writes=cap,
        # short_span_limit stays 0: the direct short-span range ops
        # measured SLOWER than the doubling tables at these shapes
        # (scripts/profile_group.py ablations) — the option remains for
        # other shapes/platforms, latched and parity-tested.
        history_capacity=hist_cap,
        window_versions=window,
        fixpoint_unroll=unroll,
        fixpoint_latch=latch,
        delta_capacity=delta_cap,
        compact_interval=compact_interval,
        range_sweep=sweep,
        delta_spill=sweep,
    )
    import dataclasses as _dc

    from foundationdb_tpu.testing.benchgen import ycsb_batch

    rng = np.random.default_rng(0)
    batches = []
    # ycsb_d read-latest insert frontier — from the MODE's keyspace
    # (gen_kw overrides the module default for the zipf-family modes)
    frontier = gen_kw.get("keyspace", keyspace) // 2
    for i in range(n_batches):
        version = (i + 1) * version_step
        kw = {"keyspace": keyspace, **gen_kw}
        if ycsb:
            b = ycsb_batch(
                rng, config, n_txns, mode, version=version, key_bytes=8,
                snapshot_lag=snapshot_lag, insert_frontier=frontier, **kw,
            )
            frontier += b.n_writes
        else:
            b = skiplist_style_batch(
                rng, config, n_txns, version=version,
                key_bytes=8, snapshot_lag=snapshot_lag, **kw,
            )
        batches.append(b)
    log(f"generated {n_batches} batches of {n_txns} txns")

    # the router re-measure (ISSUE 14): the stream's classified profile
    # and the backend the config-aware router would choose — ycsb_e must
    # classify range_heavy and STAY on device when the sweep is
    # configured (the no-fallback acceptance direction)
    from foundationdb_tpu.models.conflict_set import (
        backend_for_profile,
        profile_batch,
    )

    stream_profile = profile_batch(batches[0])
    routed_backend = backend_for_profile(stream_profile, config)
    log(f"contention profile: {stream_profile} -> routed {routed_backend}")
    if sweep:
        assert stream_profile == "range_heavy", stream_profile
        assert routed_backend == "tpu", (
            "range_heavy must stay on device with the sweep configured"
        )

    # Device-side read dedup (tiered only): size the distinct-range cap
    # from the ACTUAL stream — the max per-batch distinct (begin, end)
    # count, next power of two. Worth compiling only when duplicates are
    # common (zipf); a uniform stream's distinct count ~= its point
    # count, so dedup would add sorts for nothing and stays off.
    dedup = 0
    if not sweep:
        # (sweep-configured streams skip dedup: the endpoint sweep has
        # no per-range searches to dedup and the knobs are exclusive)
        max_uniq = 0
        for b in batches:
            pairs = np.concatenate(
                [b.read_begin[: b.n_reads], b.read_end[: b.n_reads]], axis=1
            )
            max_uniq = max(max_uniq, len(np.unique(pairs, axis=0)))
        if max_uniq <= cap // 2:
            dedup = 1 << (max_uniq - 1).bit_length()
            config = _dc.replace(config, dedup_reads=dedup)
        log(f"read dedup: max distinct ranges/batch {max_uniq} of {n_txns} "
            f"-> dedup_reads={dedup}")

    return config, batches, stream_profile, routed_backend


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--profile-dir", default=os.environ.get(
        "BENCH_PROFILE_DIR") or None,
        help="capture a jax.profiler trace of the primary phase here")
    ap.add_argument("--perf-ledger", default=None,
                    help="append the run's perf record to this JSONL "
                         "(default: perf/history.jsonl)")
    ap.add_argument("--no-perf", action="store_true",
                    help="skip the perf-ledger append")
    args = ap.parse_args()
    n_txns = int(os.environ.get("BENCH_TXNS", 65536))
    # 32-batch default (r5): the stream is long enough that per-fence
    # startup noise amortizes — measured 3.41x (32) vs 3.19x (16) on
    # back-to-back runs with overlapping device spreads; the CPU
    # baseline runs the SAME longer stream. "batches" ships in the JSON.
    n_batches = int(os.environ.get("BENCH_BATCHES", 32))
    cpu_batches = int(os.environ.get("BENCH_CPU_BATCHES", 4))
    mode = os.environ.get("BENCH_MODE", "uniform")
    fuse = max(1, int(os.environ.get("BENCH_FUSE", 8)))
    import jax

    from foundationdb_tpu.utils import compile_cache, perf

    cache_dir = compile_cache.enable()
    log(f"compilation cache: {cache_dir}")
    # the FULL device fingerprint (r10 satellite): `backend` alone made
    # CPU-host and v5e ledger rows indistinguishable to a comparator
    fingerprint = perf.device_fingerprint()
    log(f"fingerprint: {fingerprint}")

    log(f"devices: {jax.devices()}")
    from foundationdb_tpu.models.conflict_set import TpuConflictSet

    config, batches, stream_profile, routed_backend = build_stream(
        mode, n_txns, n_batches, fuse=fuse,
        compact_interval=int(os.environ.get("BENCH_COMPACT_INTERVAL", 0)),
        delta_cap=int(os.environ.get("BENCH_DELTA_CAP", 0)),
        sweep_allowed=os.environ.get("BENCH_SWEEP", "1") != "0",
    )
    keyspace, version_step, snapshot_lag = KEYSPACE, VERSION_STEP, SNAPSHOT_LAG
    window = WINDOW
    hist_cap = config.history_capacity
    sweep = config.range_sweep
    import dataclasses as _dc

    exact_config = _dc.replace(config, fixpoint_latch=False, dedup_reads=0)

    # ---- CPU baselines (native C++ ConflictBatch-equivalents) -----------
    # Two independent implementations (VERDICT r1 task 3): the ordered-map
    # semantic model and the skip-list port of the reference's algorithm
    # class (pyramid max-versions, radix point sort, bitset intra sweep).
    # vs_baseline is reported against the FASTER of the two.
    from foundationdb_tpu.native import (
        NativeConflictSet,
        NativeSkipListConflictSet,
    )

    from foundationdb_tpu.testing.benchgen import flatten_for_native as flat

    flats = [(flat(b, "r"), flat(b, "w")) for b in batches]

    def cpu_pass(cls, collect_verdicts=False):
        """One full stream through a fresh CPU conflict set; returns the
        steady-state rate (and optionally the first batches' verdicts)."""
        cpu = cls(window=window)
        cpu_times = []
        verdicts = []
        for i, b in enumerate(batches):
            (rkeys, roff, rtxn), (wkeys, woff, wtxn) = flats[i]
            snaps = b.snapshot[:n_txns].astype(np.int64)
            t0 = time.perf_counter()
            v = cpu.resolve_raw(
                int(b.version), snaps, rkeys, roff, rtxn, wkeys, woff, wtxn
            )
            cpu_times.append(time.perf_counter() - t0)
            if collect_verdicts and i < cpu_batches:
                verdicts.append(v)
        # steady-state rate: skip the warm-up batches before the window fills
        steady = cpu_times[len(cpu_times) // 2 :]
        return n_txns * len(steady) / sum(steady), verdicts

    # one verdict-collecting pass per impl up front: the two baselines
    # must agree before either is a baseline (timing comes later,
    # interleaved with the device passes — see the measurement phase)
    _, cpu_verdicts = cpu_pass(NativeConflictSet, collect_verdicts=True)
    _, sk_verdicts = cpu_pass(NativeSkipListConflictSet, collect_verdicts=True)
    for i in range(cpu_batches):
        assert (cpu_verdicts[i] == sk_verdicts[i]).all(), \
            f"cpu baseline disagreement at batch {i}"

    # ---- phase 1.5: rangemax flat-gather selftest on THIS device --------
    # The doubling-table query uses a flattened data-dependent gather; an
    # older XLA:TPU was seen miscompiling that pattern at large m (gather
    # landing on the wrong level). This randomized large-m check runs on
    # the real device every bench run so a regression trips loudly here,
    # before any throughput number is produced.
    from foundationdb_tpu.ops import rangemax as _rm

    mm = config.history_capacity
    _rm.flat_gather_selftest(mm, force=True)
    log(f"rangemax large-m selftest: OK (m={mm}, 8192 queries)")

    # ---- phase 2: decision parity ---------------------------------------
    cs = TpuConflictSet(config)
    t0 = time.perf_counter()
    for i in range(cpu_batches):
        out = cs.resolve_packed(batches[i])
        dv = np.asarray(out.verdict)[:n_txns]
        n_commit = int((dv == 3).sum())
        n_conflict = int((dv == 0).sum())
        assert (dv == cpu_verdicts[i]).all(), f"decision mismatch at batch {i}"
    log(f"decision parity: OK ({cpu_batches} batches, last: "
        f"{n_commit} committed / {n_conflict} conflicted; "
        f"incl. compile {time.perf_counter() - t0:.1f}s)")

    # ---- phase 3: pipelined throughput ----------------------------------
    # Batches are staged on device untimed: on a TPU host the per-batch
    # host->device hop is PCIe (~7MB => well under 1ms, negligible
    # against a >100ms kernel), and staging measures the resolver. The
    # CPU baseline's inputs are likewise in RAM before its timer starts.
    # Phase 4 reports the transfer-inclusive latency separately so the
    # staging effect is visible, and the JSON marks the methodology.
    # Batches are dispatched in groups of BENCH_FUSE (default 8) through
    # the tiered kernel (ops/delta.py): one program resolves the whole
    # group, one dispatch per group. A loaded resolver coalescing its
    # queue is exactly how the reference behaves under backpressure
    # (fdbserver/Resolver.actor.cpp resolveBatch queueing). Per-batch
    # latency is still reported un-fused (phase 4). The body is
    # G-independent: BENCH_FUSE up to MAX_GROUP_TIERED=64, and the
    # compile probe logs the flat curve.
    from foundationdb_tpu.utils.packing import stack_device_args

    dev_groups = [
        jax.device_put(stack_device_args(batches[g : g + fuse]))
        for g in range(0, n_batches, fuse)
    ]
    jax.block_until_ready(dev_groups)
    # warm the group program for every group shape (the ragged tail group
    # compiles separately) so compilation stays out of the timed window
    warm = TpuConflictSet(config)
    for dg in {g["version"].shape[0]: g for g in dev_groups}.values():
        t0 = time.perf_counter()
        warm.resolve_group_args(dg, check_latch=False)
        jax.block_until_ready(warm.state)
        log(f"warm compile G={dg['version'].shape[0]}: "
            f"{time.perf_counter() - t0:.1f}s")
        # latch mode: pre-warm the exact while-loop program for the same
        # shape so a mid-stream latch trip swaps programs instead of
        # paying an XLA compile inside a timed rep (VERDICT r4 task 5)
        warm.prewarm_exact(dg)
    jax.block_until_ready(warm.state)

    # HLO cost-model extraction (ISSUE 10): FLOPs / bytes accessed of
    # the compiled group program, per run — hardware sessions compare
    # achieved rate against this roofline. Warm signature => persistent
    # compile-cache hit, so this costs deserialization, not a compile.
    hlo_cost = warm.kernel_cost_analysis(dev_groups[0])
    log(f"kernel HLO cost model: {hlo_cost or 'unavailable'}")

    # G-independence probe (opt-in: BENCH_COMPILE_PROBE=1): compile the
    # SAME kernel at extra group sizes and log the wall time per G. The
    # tiered kernel's scan body is G-independent, so the curve is ~flat
    # (ops/group.py MAX_GROUP note: a G-sized skeleton's compile grew to
    # a >35min wall at G=16). Sizes clamp to the kernel's group cap.
    if os.environ.get("BENCH_COMPILE_PROBE"):
        from foundationdb_tpu.ops.delta import MAX_GROUP_TIERED

        probe_cap = min(n_batches, MAX_GROUP_TIERED)
        for g_probe in sorted({2, fuse // 2, min(2 * fuse, probe_cap)}):
            if g_probe < 1 or g_probe == fuse or g_probe > probe_cap:
                continue
            probe_args = jax.device_put(
                stack_device_args(batches[:g_probe])
            )
            warm_p = TpuConflictSet(config)
            t0 = time.perf_counter()
            warm_p.resolve_group_args(probe_args, check_latch=False)
            jax.block_until_ready(warm_p.state)
            log(f"compile probe G={g_probe}: "
                f"{time.perf_counter() - t0:.1f}s wall")
            del warm_p, probe_args

    def device_pass(check_parity=False, cfg_=None):
        cs2 = TpuConflictSet(cfg_ or config)
        outs = []
        t0 = time.perf_counter()
        for dg in dev_groups:
            # check_latch=False: the per-group latch sync would serialize
            # the async pipeline; this loop fences ONCE below and handles
            # an unconverged group itself (return None -> caller falls
            # back to the exact kernel)
            outs.append(cs2.resolve_group_args(dg, check_latch=False))
        np.asarray(outs[-1].verdict)  # honest fence: device->host transfer
        total = time.perf_counter() - t0
        cs2.check_overflow()
        # the latch-mode kernel REFUSES (does not mis-answer) chains
        # deeper than the unroll — and the tiered dedup latch refuses
        # batches with more distinct ranges than compiled for: check
        # after timing, fall back loudly
        if (
            (cfg_ or config).fixpoint_latch or (cfg_ or config).dedup_reads
        ) and any(
            bool(np.asarray(o.unconverged).any()) for o in outs
        ):
            return None
        if check_parity:
            # decision parity of the fused path against the CPU verdicts
            for i in range(cpu_batches):
                dv = np.asarray(outs[i // fuse].verdict[i % fuse])[:n_txns]
                assert (dv == cpu_verdicts[i]).all(), \
                    f"fused-path decision mismatch at batch {i}"
        return n_txns * n_batches / total

    if device_pass(check_parity=True) is None:  # warm + parity, untimed
        log("fixpoint latch tripped: falling back to the exact "
            "while-loop kernel for the measured passes")
        config = exact_config
        warm2 = TpuConflictSet(config)
        for dg in {g["version"].shape[0]: g for g in dev_groups}.values():
            warm2.resolve_group_args(dg)
        jax.block_until_ready(warm2.state)
        assert device_pass(check_parity=True) is not None

    # INTERLEAVED median-of-N measurement (VERDICT r3 weak #4): the
    # shared-host CPU baseline swings >2x run-to-run, so a single draw of
    # each side makes the graded ratio a dice roll. Alternating
    # cpu/device passes sample the same noise environment; medians of
    # each side are the numbers of record and the spreads ship in the
    # JSON. (Core pinning is moot here: the host has ONE core.)
    reps = max(1, int(os.environ.get("BENCH_REPS", 5)))
    cpu_samples = {"map": [], "skiplist": []}
    dev_samples = []
    for rep in range(reps):
        cpu_samples["map"].append(cpu_pass(NativeConflictSet)[0])
        d = device_pass()
        # reps replay the identical pre-staged groups, so a latch trip
        # here would contradict the clean warm pass above — fail loudly
        # rather than let None poison the median (ADVICE r4)
        assert d is not None, "latch tripped mid-rep on a warm-clean stream"
        dev_samples.append(d)
        cpu_samples["skiplist"].append(
            cpu_pass(NativeSkipListConflictSet)[0]
        )
        log(f"rep {rep}: cpu map {cpu_samples['map'][-1]:,.0f} | "
            f"skiplist {cpu_samples['skiplist'][-1]:,.0f} | "
            f"device {dev_samples[-1]:,.0f} txn/s")

    med = lambda xs: sorted(xs)[len(xs) // 2]
    cpu_medians = {k: med(v) for k, v in cpu_samples.items()}
    cpu_name, cpu_rate = max(cpu_medians.items(), key=lambda kv: kv[1])
    dev_rate = med(dev_samples)
    log(f"baseline of record: {cpu_name} median {cpu_rate:,.0f} txn/s "
        f"(spread {min(cpu_samples[cpu_name]):,.0f}-"
        f"{max(cpu_samples[cpu_name]):,.0f}); device median "
        f"{dev_rate:,.0f} (spread {min(dev_samples):,.0f}-"
        f"{max(dev_samples):,.0f})")

    # ---- phase 3b: PRIMARY — transfer-inclusive pipelined throughput ----
    # The operative number (VERDICT r5 task 2): batches start HOST-side
    # as packed tensors every rep, and the timed region covers the full
    # pack (group stacking) -> host->device copy -> kernel pipeline.
    # TpuConflictSet.resolve_stream_pipelined stages at sub-group depth
    # on a separate thread: the pack+copy of chunk k+1 overlaps the
    # compute of chunk k, so packing is off the critical thread and the
    # stream rate should approach the device-resident rate.
    latchy = config.fixpoint_latch or config.dedup_reads
    incl_samples = []
    # --profile-dir: the PRIMARY phase runs under a jax.profiler trace
    # (device/compile timelines per dispatch — the per-device timing
    # attribution the multi-chip shard work will need)
    with perf.profile_trace(args.profile_dir):
        for _rep in range(reps):
            cs_s = TpuConflictSet(config)
            t0 = time.perf_counter()
            outs_s = cs_s.resolve_stream_pipelined(batches, chunk=fuse)
            np.asarray(outs_s[-1].verdict)  # honest fence
            total = time.perf_counter() - t0
            if latchy and any(
                bool(np.asarray(o.unconverged).any()) for o in outs_s
            ):
                log("phase 3b: latch tripped; skipping incl-transfer sample")
                continue
            incl_samples.append(n_txns * n_batches / total)
    if args.profile_dir:
        log(f"jax.profiler trace captured in {args.profile_dir}")
    incl_rate = med(incl_samples) if incl_samples else 0.0
    log(f"PRIMARY incl-transfer pipelined (pack->copy->compute overlap): "
        f"{incl_rate:,.0f} txn/s ({len(incl_samples)} reps, "
        f"spread {min(incl_samples):,.0f}-{max(incl_samples):,.0f})"
        if incl_samples else "PRIMARY incl-transfer pipelined: NO SAMPLES")

    # ---- phase 3c: per-stage ablation ledger ---------------------------
    # READER of the shared instrumentation (ISSUE 5): the stage timers,
    # merge-row accounting and tier-occupancy pass all live in
    # models/conflict_set.py (KernelStageMetrics + stage_ledger) — the
    # same metrics a live resolver emits continuously; this script owns
    # no private timers. kernel_s is the phase-3 device-resident
    # measurement; pipelined_s the phase-3b transfer-inclusive one.
    from foundationdb_tpu.models.conflict_set import stage_ledger

    ledger = stage_ledger(
        config,
        batches,
        fuse=fuse,
        kernel_s=n_txns * n_batches / dev_rate,
        pipelined_s=(n_txns * n_batches / incl_rate) if incl_rate else 0.0,
        occupancy_delta_capacity=hist_cap,
    )
    log(f"ablation ledger: {json.dumps(ledger)}")

    # ---- structural decision + range-path accounting (ISSUE 14) ---------
    # One more clean pass over the pre-staged groups, untimed: total
    # commit/abort decisions plus the sweep/spill counters — all
    # deterministic given the seeded stream, so the perfcheck lane gates
    # them exactly on any host (a flipped verdict or a silently
    # re-routed probe path fails CI before hardware ever re-measures).
    cs_m = TpuConflictSet(config)
    decisions = {"committed": 0, "conflicted": 0, "too_old": 0}
    for dg in dev_groups:
        o = cs_m.resolve_group_args(dg, check_latch=False)
        decisions["committed"] += int(np.asarray(o.committed_count).sum())
        decisions["conflicted"] += int(np.asarray(o.conflict_count).sum())
        decisions["too_old"] += int(np.asarray(o.too_old_count).sum())
    cs_m.check_overflow()
    _c = cs_m.metrics.counters
    structural = {
        **decisions,
        "spills": _c.get("spills"),
        "sweep_groups": _c.get("sweepGroups"),
        "compactions": _c.get("compactions"),
    }
    if getattr(config, "range_sweep", False):
        from foundationdb_tpu.ops.delta import sweep_rows_per_group

        structural["sweep_rows_per_group"] = sweep_rows_per_group(
            config.history_capacity, fuse, config.max_reads
        )
    log(f"structural: {json.dumps(structural)}")

    # ---- phase 4: per-batch latency probe -------------------------------
    del dev_groups  # release phase-3 staging before re-staging
    dev_batches = [jax.device_put(b.device_args()) for b in batches]
    jax.block_until_ready(dev_batches)
    # compact_interval counts batches, so these per-batch dispatches
    # already pay compaction at the same cadence as the fused stream
    cs3 = TpuConflictSet(config)
    lat = []
    for db in dev_batches:
        t0 = time.perf_counter()
        out = cs3.resolve_args(db)
        np.asarray(out.verdict)  # fence: device->host transfer
        lat.append(time.perf_counter() - t0)
    lat_s = sorted(lat[1:])
    p50 = lat_s[len(lat_s) // 2]
    p99 = lat_s[min(len(lat_s) - 1, int(len(lat_s) * 0.99))]

    # Same probe with the host->device transfer inside the timed region
    # (what a caller on THIS host would see).
    cs4 = TpuConflictSet(config)
    lat_h = []
    for b in batches:
        t0 = time.perf_counter()
        out = cs4.resolve_packed(b)
        np.asarray(out.verdict)
        lat_h.append(time.perf_counter() - t0)
    lat_hs = sorted(lat_h[1:])
    p50_h = lat_hs[len(lat_hs) // 2]

    log(
        f"device: {dev_rate:,.0f} txn/s pipelined | kernel latency p50 "
        f"{p50*1e3:.0f}ms p99 {p99*1e3:.0f}ms | incl. host->device transfer "
        f"p50 {p50_h*1e3:.0f}ms | speedup {dev_rate / cpu_rate:.2f}x"
    )

    # ---- phase 5 (opt-in): small-batch latency sweep --------------------
    # BENCH_SMALL=1: the reference's resolver lives on a <3ms commit path
    # (performance.rst:49; Resolver.actor.cpp:174-208 latency histograms)
    # at batches of hundreds-to-thousands of txns. Measure that regime
    # honestly: device p50 (resident + transfer-inclusive) vs the CPU
    # backends on identical small batches. These numbers set the
    # RESOLVER_TPU_MIN_BATCH auto-routing knob (utils/knobs.py): below
    # the threshold the CPU resolves before the device dispatch returns.
    small = {}
    if os.environ.get("BENCH_SMALL"):
        from foundationdb_tpu.config import KernelConfig
        from foundationdb_tpu.testing.benchgen import skiplist_style_batch

        rng = np.random.default_rng(1)
        for n_small in (512, 2048):
            cap_s = 4096
            cfg_s = KernelConfig(
                max_key_bytes=8, max_txns=cap_s, max_reads=cap_s,
                max_writes=cap_s, history_capacity=12 * cap_s,
                window_versions=window,
            )
            sb = [
                skiplist_style_batch(
                    rng, cfg_s, n_small, version=(i + 1) * version_step,
                    key_bytes=8, snapshot_lag=snapshot_lag,
                    keyspace=keyspace,
                )
                for i in range(12)
            ]
            css = TpuConflictSet(cfg_s)
            dev_sb = [jax.device_put(b.device_args()) for b in sb]
            jax.block_until_ready(dev_sb)
            lat_d, lat_t = [], []
            for db_, b in zip(dev_sb, sb):
                t0 = time.perf_counter()
                np.asarray(css.resolve_args(db_).verdict)
                lat_d.append(time.perf_counter() - t0)
            css2 = TpuConflictSet(cfg_s)
            for b in sb:
                t0 = time.perf_counter()
                np.asarray(css2.resolve_packed(b).verdict)
                lat_t.append(time.perf_counter() - t0)
            cpu_s = NativeSkipListConflictSet(window=window)
            lat_c = []
            for b in sb:
                (rk, ro, rt), (wk, wo, wt) = flat(b, "r"), flat(b, "w")
                t0 = time.perf_counter()
                cpu_s.resolve_raw(
                    int(b.version), b.snapshot[:n_small].astype(np.int64),
                    rk, ro, rt, wk, wo, wt,
                )
                lat_c.append(time.perf_counter() - t0)
            m_ = lambda xs: sorted(xs[1:])[len(xs[1:]) // 2]
            small[str(n_small)] = {
                "device_p50_ms": round(m_(lat_d) * 1e3, 2),
                "device_incl_transfer_p50_ms": round(m_(lat_t) * 1e3, 2),
                "cpu_skiplist_p50_ms": round(m_(lat_c) * 1e3, 2),
            }
            log(f"small-batch n={n_small}: {small[str(n_small)]}")

    suffix = "" if mode == "uniform" else f"_{mode}"
    cc_stats = compile_cache.stats()
    row = {
        "metric": f"resolver_txns_per_sec_{n_txns // 1024}k_batch{suffix}",
        # PRIMARY (r6, VERDICT r5 task 2): the transfer-inclusive
        # pipelined rate — pack + host->device copy + kernel,
        # overlapped. The r3-r5 primary (device-resident) ships
        # as device_resident_txn_s; "staging": "pipelined" marks
        # the methodology switch (BASELINE.md note).
        "value": round(incl_rate, 1),
        "unit": "txn/s",
        "vs_baseline": round(incl_rate / cpu_rate, 3),
        "baseline": cpu_name,
        "baseline_txns_per_sec": round(cpu_rate, 1),
        "reps": reps,
        "baseline_spread": [
            round(min(cpu_samples[cpu_name]), 1),
            round(max(cpu_samples[cpu_name]), 1),
        ],
        "device_resident_txn_s": round(dev_rate, 1),
        "device_resident_vs_baseline": round(dev_rate / cpu_rate, 3),
        "device_spread": [
            round(min(dev_samples), 1),
            round(max(dev_samples), 1),
        ],
        "incl_spread": [
            round(min(incl_samples), 1),
            round(max(incl_samples), 1),
        ] if incl_samples else [],
        "staging": "pipelined",
        "backend": jax.default_backend(),
        # full device fingerprint (kind/count/jaxlib): without
        # it CPU-host and v5e rows are indistinguishable to the
        # perfcheck comparator
        "device": fingerprint,
        "compile_cache": cc_stats,
        "hlo_cost": hlo_cost,
        "delta_capacity": config.delta_capacity,
        "dedup_reads": config.dedup_reads,
        "range_sweep": config.range_sweep,
        "delta_spill": config.delta_spill,
        "compact_interval": config.compact_interval,
        "profile": stream_profile,
        "routed_backend": routed_backend,
        "structural": structural,
        "fused_dispatch": fuse,
        "batches": n_batches,
        "p50_ms": round(p50 * 1e3, 1),
        "p99_ms": round(p99 * 1e3, 1),
        "p50_incl_transfer_ms": round(p50_h * 1e3, 1),
        "ablation": ledger,
        **({"small_batch": small} if small else {}),
    }
    print(json.dumps(row))
    # the canonical perf-ledger row (utils/perf.py): the printed JSON
    # stays the human/driver view; the ledger is what perfcheck gates
    if not args.no_perf:
        rec = perf.bench_row_to_record(row, fingerprint=fingerprint)
        path = perf.append(rec, path=args.perf_ledger)
        log(f"perf ledger row appended to {path}")


if __name__ == "__main__":
    main()

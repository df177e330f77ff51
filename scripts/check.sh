#!/usr/bin/env bash
# The verify path: flowcheck gate first (cheap, seconds), then the
# spec smoke lanes, then the tier-1 pytest lane (-m 'not slow' — the
# ROADMAP verify contract; note this INCLUDES the compile-heavy
# `kernel` tests, exactly like tier-1). Extra args pass through to
# pytest:
#
#   scripts/check.sh                          # gate + smoke + tier-1 lane
#   scripts/check.sh -m 'not slow and not kernel'  # skip compiles too
#
# flowcheck exits nonzero on any NEW violation (baselined findings in
# foundationdb_tpu/analysis/baseline.json don't fail; the baseline is
# EMPTY and stays that way) and on stale `# flowcheck: ignore` comments.
# The gate's wall time is printed so cost regressions in the static
# pass (it now includes the flow.* dataflow rules) are visible in CI
# output, not discovered by feel.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== flowcheck (python -m foundationdb_tpu.analysis) =="
t0=$(date +%s.%N)
JAX_PLATFORMS=cpu python -m foundationdb_tpu.analysis --timings
t1=$(date +%s.%N)
# r18 contract: the whole static pass (res.* path walk included) stays
# interactive — enforce the ~10s budget, don't just print it
awk -v a="$t0" -v b="$t1" 'BEGIN {
    w = b - a
    printf "flowcheck wall time: %.1fs\n", w
    if (w > 10.0) { printf "flowcheck BUDGET EXCEEDED (>10s)\n"; exit 1 }
}'

echo "== wire-fuzz smoke (corpus replay + ~1k seeded mutations over    =="
echo "== every registered frame: decode must reject with CodecError,   =="
echo "== never crash/hang/partial-decode — exit-code enforced; the     =="
echo "== wire-manifest drift gate itself runs inside flowcheck above)  =="
t0=$(date +%s.%N)
JAX_PLATFORMS=cpu python scripts/wire_fuzz.py --smoke
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "wire-fuzz smoke wall time: %.1fs\n", b - a}'

echo "== kernel-parity smoke (tiny shapes: tiered + dedup fallback   =="
echo "== vs the Python oracle — seconds, compile-bound)                =="
t0=$(date +%s.%N)
perf_row=$(mktemp /tmp/perfcheck_row.XXXXXX.jsonl)
JAX_PLATFORMS=cpu python scripts/kernel_smoke.py --perf-out "$perf_row"
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "kernel smoke wall time: %.1fs\n", b - a}'

echo "== perf regression gate (the kernel_smoke structural row vs the  =="
echo "== committed perf/history.jsonl baseline — exact compare,        =="
echo "== exit-code enforced; see scripts/perfcheck.py)                 =="
t0=$(date +%s.%N)
JAX_PLATFORMS=cpu python scripts/perfcheck.py --check "$perf_row" --tier structural
rm -f "$perf_row"
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "perfcheck wall time: %.1fs\n", b - a}'

echo "== shard_smoke (mesh-sharded tiered kernel on an 8-virtual-device  =="
echo "== CPU mesh: sharded-vs-multi-resolver-oracle parity at widths     =="
echo "== 1/2/4/8 + structural scaling-ledger rows gated by perfcheck)    =="
t0=$(date +%s.%N)
shard_row=$(mktemp /tmp/shardcheck_row.XXXXXX.jsonl)
JAX_PLATFORMS=cpu python scripts/shard_smoke.py --perf-out "$shard_row"
JAX_PLATFORMS=cpu python scripts/perfcheck.py --check "$shard_row" --tier structural
rm -f "$shard_row"
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "shard_smoke wall time: %.1fs\n", b - a}'

echo "== ycsb_e bench (tiny-shape YCSB-E through the sweep+spill kernel: =="
echo "== range_heavy must classify + route to the device, and the run's  =="
echo "== structural ledger row — decisions, sweep rows, spills — gates    =="
echo "== against the committed baseline via perfcheck)                    =="
t0=$(date +%s.%N)
ycsb_row=$(mktemp /tmp/ycsbcheck_row.XXXXXX.jsonl)
JAX_PLATFORMS=cpu BENCH_MODE=ycsb_e BENCH_TXNS=256 BENCH_BATCHES=6 \
    BENCH_CPU_BATCHES=2 BENCH_REPS=1 BENCH_FUSE=3 BENCH_DELTA_CAP=2048 \
    BENCH_COMPACT_INTERVAL=0 \
    python bench.py --perf-ledger "$ycsb_row" > /dev/null
JAX_PLATFORMS=cpu python scripts/perfcheck.py --check "$ycsb_row" --tier structural
rm -f "$ycsb_row"
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "ycsb_e bench wall time: %.1fs\n", b - a}'

echo "== autotune smoke (deterministic structural-objective search over  =="
echo "== the tiny YCSB-E spill fixture: must converge to the known-best   =="
echo "== knob, re-run as a 100% fingerprint-cache hit, leave the          =="
echo "== committed ledger byte-stable, and prove experiment rows never    =="
echo "== enter a baseline window)                                         =="
t0=$(date +%s.%N)
JAX_PLATFORMS=cpu python scripts/autotune.py --smoke
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "autotune smoke wall time: %.1fs\n", b - a}'

echo "== elasticity smoke (limiter-driven live resolver recruitment, both =="
echo "== directions: ON must recruit a second resolver off the            =="
echo "== resolver_busy streak and scale goodput >= 1.5x the plateau with  =="
echo "== exact consistency; OFF must stay pinned at the plateau, still    =="
echo "== attributed resolver_busy — structural ledger row perfcheck-gated; =="
echo "== census gate armed: recruit + teardown must leak nothing)          =="
t0=$(date +%s.%N)
elastic_row=$(mktemp /tmp/elasticcheck_row.XXXXXX.jsonl)
JAX_PLATFORMS=cpu python scripts/elasticity_drill.py --smoke --perf-ledger "$elastic_row"
JAX_PLATFORMS=cpu python scripts/perfcheck.py --check "$elastic_row" --tier structural
rm -f "$elastic_row"
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "elasticity smoke wall time: %.1fs\n", b - a}'

echo "== spec + perturbation smoke (1 short seed per spec, then the same =="
echo "== seed x 3 schedule perturbations, api workload + auditor on)    =="
# --perturb runs the unperturbed base seed first, so one lane covers both
JAX_PLATFORMS=cpu python scripts/soak.py --smoke --perturb 3

echo "== commit_debug smoke (one traced seed: the reconstructor must   =="
echo "== yield >=1 complete commit timeline, zero chain violations)    =="
t0=$(date +%s.%N)
JAX_PLATFORMS=cpu python scripts/commit_debug.py --smoke
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "commit_debug smoke wall time: %.1fs\n", b - a}'

echo "== bench_pipeline smoke (tiny traced wire run over real role    =="
echo "== processes: consistency ok + >=1 cross-process timeline, plus  =="
echo "== the columnar A/B — object-frame decision parity and the       =="
echo "== structural two-copies row gated by perfcheck; the resource    =="
echo "== census gate is ARMED: fds/connections/servers must return to  =="
echo "== their pre-run baseline after drain — exit-code enforced)      =="
t0=$(date +%s.%N)
pipe_row=$(mktemp /tmp/pipecheck_row.XXXXXX.jsonl)
JAX_PLATFORMS=cpu python scripts/bench_pipeline.py --smoke --perf-ledger "$pipe_row"
JAX_PLATFORMS=cpu python scripts/perfcheck.py --check "$pipe_row" --tier structural
rm -f "$pipe_row"
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "bench_pipeline smoke wall time: %.1fs\n", b - a}'

echo "== chaos smoke (wire-cluster lifecycle: controller + workers under =="
echo "== the monitor, kill -9 one resolver mid-run — gate on a recovered =="
echo "== generation, exact-count consistency, the trace-reconstructable  =="
echo "== recovery timeline, and the structural recovery ledger row;      =="
echo "== census gate armed: a kill-recover cycle must leak nothing)      =="
t0=$(date +%s.%N)
chaos_row=$(mktemp /tmp/chaoscheck_row.XXXXXX.jsonl)
JAX_PLATFORMS=cpu python scripts/chaos_pipeline.py --smoke --perf-ledger "$chaos_row"
JAX_PLATFORMS=cpu python scripts/perfcheck.py --check "$chaos_row" --tier structural
rm -f "$chaos_row"
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "chaos smoke wall time: %.1fs\n", b - a}'

echo "== proxy-scaling smoke (commit-path scale-out: 1 vs 2 wire commit =="
echo "== proxies on one sequencer + tag-partitioned tlogs — exact-count  =="
echo "== consistency through BOTH front doors, census gate armed per     =="
echo "== width, structural ledger row gated by perfcheck)                =="
t0=$(date +%s.%N)
scaling_row=$(mktemp /tmp/scalingcheck_row.XXXXXX.jsonl)
JAX_PLATFORMS=cpu python scripts/proxy_scaling.py --smoke --perf-ledger "$scaling_row"
JAX_PLATFORMS=cpu python scripts/perfcheck.py --check "$scaling_row" --tier structural
rm -f "$scaling_row"
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "proxy-scaling smoke wall time: %.1fs\n", b - a}'

echo "== saturation smoke (short overload ramp via the saturation spec: =="
echo "== admission ON must hold the p99/goodput SLO, OFF must violate)  =="
t0=$(date +%s.%N)
JAX_PLATFORMS=cpu python scripts/saturation.py --smoke
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "saturation smoke wall time: %.1fs\n", b - a}'

echo "== hotspot smoke (keyspace-skew attribution gate, all four legs: =="
echo "== zipf mix MUST attribute the injected tenant top-1 and the      =="
echo "== uniform mix must NOT flag, on BOTH the sim status path and     =="
echo "== real wire role processes; sim legs emit structural sampling-   =="
echo "== overhead ledger rows gated by perfcheck)                       =="
t0=$(date +%s.%N)
hotspot_row=$(mktemp /tmp/hotspotcheck_row.XXXXXX.jsonl)
JAX_PLATFORMS=cpu python scripts/hotspot.py --smoke --perf-ledger "$hotspot_row"
JAX_PLATFORMS=cpu python scripts/perfcheck.py --check "$hotspot_row" --tier structural
rm -f "$hotspot_row"
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "hotspot smoke wall time: %.1fs\n", b - a}'

echo "== fdbtop smoke (bench_pipeline wire cluster held live, fdbtop  =="
echo "== polls StatusRequest: every role must report its qos sensors   =="
echo "== AND its resource-census block — conns/tasks/fds per process)  =="
t0=$(date +%s.%N)
JAX_PLATFORMS=cpu python scripts/fdbtop.py --smoke
t1=$(date +%s.%N)
awk -v a="$t0" -v b="$t1" 'BEGIN {printf "fdbtop smoke wall time: %.1fs\n", b - a}'

echo "== pytest (fast lane: -m 'not slow') =="
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider "$@"

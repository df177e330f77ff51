#!/usr/bin/env python
"""autotune: resumable ledger-driven knob search over the bench harnesses.

    python scripts/autotune.py --harness bench --mode zipf \
        --space "fuse=8,16,32,64;delta_capacity=16384,65536" \
        --search zipf-fuse-r15                      # the hardware sweep
    python scripts/autotune.py --harness bench_pipeline --mode wire \
        --space "knob.COMMIT_TRANSACTION_BATCH_COUNT_MAX=4096,16384" \
        --backend native --search wire-batch-r15
    python scripts/autotune.py --smoke              # check.sh lane
    python scripts/autotune.py ... --promote-out winner.jsonl
    python scripts/perfcheck.py --check winner.jsonl --accept  # re-baseline

Every TRIAL subprocess-runs the existing harness (bench.py /
scripts/bench_pipeline.py) at one grid point — knobs ride the
documented env surface (BENCH_*) or the FDBTPU_KNOB_OVERRIDES hook —
and its emitted perf row lands in the search ledger stamped
`experiment: <search id>` (utils/autotune.run_search). The ledger IS
the resumability cache: a killed sweep re-run completes only the
missing trials (`autotune.cache_hit` per skip), across hardware
sessions for structural objectives (`--cache-scope any`) or pinned to
this device for wall-clock ones (`--cache-scope device`, the default
for rate objectives). Experiment rows never enter a perfcheck baseline
window (utils/perf.baseline_window) and `--accept` refuses them — the
winner is promoted WITHOUT the marker via --promote-out and committed
through the normal `perfcheck --check --accept` flow.

Stopping: roofline distance first (achieved txn/s vs the bytes-bound
ceiling from the winning row's recorded HLO cost and the device peak
table — utils/autotune.DEVICE_PEAK_BYTES_S), then --no-improve, then
grid exhaustion. CPU hosts have no peak entry, so structural searches
report "exhausted"/"no_improve" honestly.

--smoke is the deterministic check.sh lane: a 2-trial structural
search (`delta_capacity` over the tiny YCSB-E spill fixture, objective
= the structural `spills` counter) that must converge to the known-best
knob, re-run as a 100% cache hit, leave the committed ledger
byte-stable (trials go to a redirected ledger), and prove baseline
exclusion against the committed history.
"""

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: bench.py's documented env-knob surface (the "path" pseudo-knob picks
#: the probe strategy: range_sweep vs the dedup probe — BENCH_SWEEP)
BENCH_ENV_KNOBS = {
    "fuse": "BENCH_FUSE",
    "delta_capacity": "BENCH_DELTA_CAP",
    "compact_interval": "BENCH_COMPACT_INTERVAL",
    "txns": "BENCH_TXNS",
    "batches": "BENCH_BATCHES",
}


def parse_space(spec: str) -> dict:
    """"fuse=8,16;path=range_sweep,dedup" -> ordered {knob: (values,)}
    with ints parsed where they look like ints."""

    def coerce(v: str):
        try:
            return int(v)
        except ValueError:
            return v

    space = {}
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        name, _, values = part.partition("=")
        space[name.strip()] = tuple(
            coerce(v.strip()) for v in values.split(",") if v.strip()
        )
    if not space:
        raise SystemExit(f"empty --space {spec!r}")
    return space


def _read_rows(path: str) -> list:
    from foundationdb_tpu.utils import perf

    return perf.load_history(path)


def validate_space(space: dict, harness: str) -> None:
    """Every grid knob must be one the TARGET harness actually
    consumes — a knob the subprocess silently ignores would make every
    trial measure the identical default configuration, and the 'winner'
    (pure noise) could be promoted into the committed baseline.
    bench.py reads the BENCH_* env surface (+ the `path` sweep/dedup
    strategy); bench_pipeline reads FDBTPU_KNOB_OVERRIDES (`knob.*`)
    and the `batch` CLI cap, and no BENCH_* var at all."""
    bench_names = set(BENCH_ENV_KNOBS) | {"path"}
    for name in space:
        if harness == "bench":
            if name.startswith("knob.") or name == "batch":
                raise SystemExit(
                    f"--space knob {name!r}: bench.py consumes neither "
                    "server-knob overrides nor --batch — use --harness "
                    "bench_pipeline (bench env knobs: "
                    f"{sorted(bench_names)})"
                )
            if name not in bench_names:
                raise SystemExit(
                    f"unknown bench knob {name!r} (env knobs: "
                    f"{sorted(bench_names)})"
                )
        else:
            if not name.startswith("knob.") and name != "batch":
                raise SystemExit(
                    f"--space knob {name!r}: bench_pipeline reads no "
                    "BENCH_* env var — drive server knobs as "
                    "knob.<NAME> (FDBTPU_KNOB_OVERRIDES) or the "
                    "`batch` CLI cap, or use --harness bench"
                )


def _subprocess_env(knobs: dict, base_env: dict) -> dict:
    env = dict(os.environ)
    env.update(base_env)
    overrides = []
    for name, value in knobs.items():
        if name == "path":
            # dedup-vs-sweep probe strategy: bench auto-sizes
            # dedup_reads from the measured distinct-range count when
            # the sweep is ablated off
            env["BENCH_SWEEP"] = "1" if value == "range_sweep" else "0"
        elif name.startswith("knob."):
            overrides.append(f"{name[len('knob.'):]}={value}")
        elif name in BENCH_ENV_KNOBS:
            env[BENCH_ENV_KNOBS[name]] = str(value)
        else:
            raise SystemExit(f"unknown knob {name!r} (bench env knobs: "
                             f"{sorted(BENCH_ENV_KNOBS)}, server knobs: "
                             f"knob.<NAME>, path)")
    if overrides:
        env["FDBTPU_KNOB_OVERRIDES"] = ";".join(overrides)
    return env


def _run_trial_subprocess(args, harness: str, cmd: list, env: dict) -> dict:
    """The shared trial mechanics: run the harness with `--perf-ledger`
    pointed at a scratch file and return the row it emitted."""
    with tempfile.NamedTemporaryFile(
        suffix=".jsonl", prefix="autotune_trial."
    ) as tf:
        subprocess.run(
            cmd + ["--perf-ledger", tf.name],
            env=env, cwd=REPO, check=True, timeout=args.trial_timeout,
            stdout=subprocess.DEVNULL,
            stderr=(None if args.verbose else subprocess.DEVNULL),
        )
        rows = _read_rows(tf.name)
    if not rows:
        raise RuntimeError(f"{harness} emitted no ledger row")
    return rows[-1]


def make_bench_runner(args, extra_env: dict = None):
    base_env = {
        "BENCH_MODE": args.mode,
        "BENCH_TXNS": str(args.txns),
        "BENCH_BATCHES": str(args.batches),
        "BENCH_CPU_BATCHES": str(args.cpu_batches),
        "BENCH_REPS": str(args.reps),
        **(extra_env or {}),
    }

    def run(knobs: dict) -> dict:
        return _run_trial_subprocess(
            args, "bench",
            [sys.executable, os.path.join(REPO, "bench.py")],
            _subprocess_env(knobs, base_env),
        )

    return run


def make_pipeline_runner(args):
    def run(knobs: dict) -> dict:
        # `batch` rides the CLI, not the env — pop it before the
        # env builder (run_trial hands this runner its own copy)
        batch = knobs.pop("batch", None)
        cmd = [
            sys.executable,
            os.path.join(REPO, "scripts", "bench_pipeline.py"),
            "--mode", args.mode, "--clients", str(args.clients),
            "--ops", str(args.ops), "--backends", args.backend,
        ]
        if batch is not None:
            cmd += ["--batch", str(batch)]
        return _run_trial_subprocess(
            args, "bench_pipeline", cmd, _subprocess_env(knobs, {})
        )

    return run


def print_report(report, objective: str) -> None:
    print(f"== autotune {report.experiment}: {len(report.trials)} trial(s), "
          f"{report.cache_hits} cached / {report.ran} ran, "
          f"stopped: {report.stopped} ==")
    for t in report.trials:
        tag = "cache" if t.cached else ("FAIL " if t.error else "ran  ")
        # objectives are normalized higher-is-better (lower-direction
        # metrics negated); show the raw metric value
        obj = "-" if t.objective is None else f"{abs(t.objective):g}"
        print(f"  [{tag}] {json.dumps(t.knobs, sort_keys=True)}  "
              f"{objective}={obj}"
              + (f"  ({t.error})" if t.error else ""))
    if report.best is not None:
        print(f"  WINNER {json.dumps(report.best.knobs, sort_keys=True)} "
              f"{objective}={abs(report.best.objective):g}")
    if report.roofline:
        print(f"  roofline {report.roofline:g} txn/s, achieved "
              f"{report.roofline_frac_achieved:.2%}")


def run_smoke(args) -> int:
    """The check.sh lane: deterministic structural-objective search.

    Fixture: the ycsb_e tiny-shape spill stream (the same shapes as the
    check.sh ycsb_e perfcheck lane, compact_interval=0 so compaction is
    purely pressure-driven) searched over `delta_capacity` — the spill
    count is pure host arithmetic over a seeded stream, so the
    objective is STRUCTURAL: byte-identical on any host. Known best:
    the largest capacity (strictly fewest spills). Gates: convergence
    to it, 100% cache-hit re-run, committed-ledger byte-stability, and
    experiment-row exclusion from a committed-history baseline window.
    """
    from foundationdb_tpu.utils import autotune, perf

    committed = perf.history_path()
    committed_digest = None
    if os.path.exists(committed):
        with open(committed, "rb") as f:
            committed_digest = hashlib.sha256(f.read()).hexdigest()

    args.mode = "ycsb_e"
    args.txns, args.batches, args.cpu_batches = 256, 6, 2
    args.reps = 1
    space = autotune.SearchSpace(
        {"delta_capacity": (1536, 3072), "compact_interval": (0,)}
    )
    ledger = args.ledger or os.path.join(
        tempfile.mkdtemp(prefix="autotune_smoke_"), "search.jsonl"
    )
    runner = make_bench_runner(args, extra_env={"BENCH_FUSE": "3"})

    failures = []

    def sweep(tag: str):
        report = autotune.run_search(
            "smoke-spill", space, runner,
            objective_metric="spills", ledger=ledger, cache_scope="any",
            log=lambda m: print(f"  {tag} {m}", flush=True),
        )
        print_report(report, "spills")
        return report

    first = sweep("first")
    if first.best is None or first.best.knobs.get("delta_capacity") != 3072:
        failures.append(
            f"did not converge to the known-best knob "
            f"(delta_capacity=3072): {first.best and first.best.knobs}"
        )
    objs = {t.knobs["delta_capacity"]: t.objective for t in first.trials}
    if not (objs.get(3072) is not None and objs.get(1536) is not None
            and objs[3072] > objs[1536]):
        failures.append(f"spill objective not strictly better at the "
                        f"known-best capacity: {objs}")
    if first.ran != len(first.trials):
        failures.append("first sweep unexpectedly hit the cache "
                        f"({first.cache_hits} hits) — ledger not fresh?")

    second = sweep("rerun")
    if second.ran != 0 or second.cache_hits != len(second.trials):
        failures.append(
            f"re-run was not a 100% cache hit: ran={second.ran}, "
            f"cached={second.cache_hits}/{len(second.trials)}"
        )
    if (second.best and first.best
            and second.best.knobs != first.best.knobs):
        failures.append("cached re-run picked a different winner")

    # committed-ledger byte-stability: trials went to the redirected
    # search ledger, never perf/history.jsonl
    if committed_digest is not None:
        with open(committed, "rb") as f:
            if hashlib.sha256(f.read()).hexdigest() != committed_digest:
                failures.append("committed perf/history.jsonl changed "
                                "during the smoke")

    # exclusion proof, BOTH directions, against the committed history:
    # spike a copy of the history with an experiment row built to be a
    # PERFECT baseline match for a committed row (same source/workload/
    # knobs/fingerprint — only the experiment stamp and wildly-wrong
    # metric values differ). The exclusion must keep the committed
    # row's verdict identical; the OTHER direction proves the spike is
    # no strawman — the same row WITHOUT the stamp must flip the
    # structural comparison to a failure (i.e. the fingerprint keys
    # really do collide, so only the exclusion is doing the work).
    history = perf.load_history(committed) if committed_digest else []
    candidates = [r for r in history if r.get("source") == "kernel_smoke"]
    if candidates:
        cand = candidates[-1]
        poison = json.loads(json.dumps(cand))
        poison["experiment"] = "smoke-exclusion-proof"
        for m in poison["metrics"].values():
            m["value"] = (m["value"] + 1) * 1000
        window = perf.baseline_window(
            history + [poison], cand, tier="structural"
        )
        if any(r.get("experiment") for r in window):
            failures.append(
                "experiment rows leaked into a baseline window"
            )
        unmarked = {k: v for k, v in poison.items() if k != "experiment"}
        control = perf.baseline_window(
            history + [unmarked], cand, tier="structural"
        )
        if unmarked not in control:
            failures.append(
                "exclusion proof is vacuous: the spiked row without its "
                "experiment marker did not enter the baseline window "
                "(fingerprint keys never collided)"
            )
    elif committed_digest is not None:
        failures.append("no kernel_smoke row in the committed history to "
                        "prove baseline exclusion against")

    # the winner promotes cleanly (experiment marker stripped)
    if first.best is not None and first.best.record is not None:
        promoted = autotune.promote_record(first.best.record)
        if "experiment" in promoted or "trial_key" in str(
            promoted.get("extra", "")
        ):
            failures.append("promote_record left trial markers in place")

    if failures:
        print(f"autotune smoke FAILED: {failures}", file=sys.stderr)
        return 1
    print(f"autotune smoke ok (winner {first.best.knobs}, "
          f"{second.cache_hits}/{len(second.trials)} cached on re-run, "
          f"search ledger {ledger})")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--harness", choices=("bench", "bench_pipeline"),
                    default="bench")
    ap.add_argument("--mode", default="uniform",
                    help="bench: uniform|zipf|range|ycsb_*; "
                         "bench_pipeline: cluster|wire")
    ap.add_argument("--space", default=None,
                    help='grid, e.g. "fuse=8,16,32;delta_capacity='
                         '16384,65536;path=range_sweep,dedup;'
                         'knob.COMMIT_TRANSACTION_BATCH_COUNT_MAX='
                         '4096,16384"')
    ap.add_argument("--search", default=None,
                    help="the experiment id trials are stamped with "
                         "(resume = same id + same ledger)")
    ap.add_argument("--objective", default="txn_s",
                    help="ledger metric the search maximizes "
                         "(direction-aware: lower-is-better metrics "
                         "are negated)")
    ap.add_argument("--ledger", default=None,
                    help="search ledger (default: the committed "
                         "perf/history.jsonl — trials are experiment "
                         "rows and never pollute baselines)")
    ap.add_argument("--cache-scope", choices=("any", "device"),
                    default=None,
                    help="resume trials from any host (structural "
                         "objectives) or only this device fingerprint "
                         "(default: device for rate objectives, any "
                         "for count objectives)")
    ap.add_argument("--roofline-txns", type=int, default=0,
                    help="txns per compiled dispatch (arms the "
                         "roofline stopping rule when the device peak "
                         "is known)")
    ap.add_argument("--roofline-frac", type=float, default=0.5)
    ap.add_argument("--no-improve", type=int, default=0,
                    help="stop after N consecutive non-improving "
                         "trials (0 = off)")
    ap.add_argument("--txns", type=int, default=8192)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--cpu-batches", type=int, default=2)
    ap.add_argument("--reps", type=int, default=1)
    ap.add_argument("--clients", type=int, default=256)
    ap.add_argument("--ops", type=int, default=20)
    ap.add_argument("--backend", default="native",
                    help="bench_pipeline resolver backend for trials")
    ap.add_argument("--trial-timeout", type=float, default=1800.0)
    ap.add_argument("--promote-out", default=None,
                    help="write the winner (experiment marker "
                         "stripped) here for perfcheck --check "
                         "--accept")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="check.sh lane: deterministic structural "
                         "2-trial search, convergence + cache + "
                         "ledger-discipline gated")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    if args.smoke:
        return run_smoke(args)
    if not args.space or not args.search:
        ap.error("--space and --search are required (or --smoke)")

    from foundationdb_tpu.utils import autotune

    parsed = parse_space(args.space)
    validate_space(parsed, args.harness)
    space = autotune.SearchSpace(parsed)
    runner = (
        make_bench_runner(args) if args.harness == "bench"
        else make_pipeline_runner(args)
    )
    if args.cache_scope is None:
        # rates/latencies are device-bound; counts resume anywhere
        args.cache_scope = (
            "device" if args.objective.endswith(("_s", "_ms", "txn_s"))
            else "any"
        )
    from foundationdb_tpu.utils import perf

    ledger = args.ledger or perf.history_path()
    report = autotune.run_search(
        args.search, space, runner, objective_metric=args.objective,
        ledger=ledger, cache_scope=args.cache_scope,
        roofline_frac=args.roofline_frac,
        roofline_txns_per_dispatch=args.roofline_txns,
        no_improve_limit=args.no_improve,
        log=lambda m: print(f"  {m}", flush=True),
    )
    print_report(report, args.objective)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report.as_dict(), f, indent=2, sort_keys=True)
    if args.promote_out and report.best and report.best.record:
        promoted = autotune.promote_record(report.best.record)
        with open(args.promote_out, "w") as f:
            f.write(json.dumps(promoted, sort_keys=True) + "\n")
        print(f"winner promoted -> {args.promote_out} (commit it with: "
              f"python scripts/perfcheck.py --check {args.promote_out} "
              "--accept)")
    return 0 if report.best is not None else 1


if __name__ == "__main__":
    sys.exit(main())

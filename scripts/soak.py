#!/usr/bin/env python
"""Spec-driven seed-sweeping soak runner.

    python scripts/soak.py --seeds 100                  # the default spec
    python scripts/soak.py --spec api_correctness --seeds 300
    python scripts/soak.py --smoke                      # 1 short seed per spec

The Joshua-ensemble driver (contrib/TestHarness2/test_harness/run.py's
role): N seeds, each a deterministic simulated-cluster run whose shape,
knobs, fault mix and workload set come from a NAMED SPEC
(foundationdb_tpu/testing/specs/*.toml — the reference's TOML-driven
tester), executed across worker processes. Every K-th seed (the spec's
determinism_every) is run TWICE and the signatures compared — the
unseed determinism check (contrib/debug_determinism/). Any assertion
failure reports the seed and spec for exact reproduction.

Probe accounting: the whole static manifest is declared up front; after
the sweep the spec's `[probes].expected` list is reported, and with
`--probe-gate` an expected-but-never-hit probe fails the run (the
coveragetool contract, applied per spec).
"""

import argparse
import os
import sys
import time
from concurrent.futures import ProcessPoolExecutor, as_completed

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"  # the simulation is CPU-only


def _perturbed_rerun(seed, spec, pid, spec_label, trace=False,
                     status_probe=False):
    """One perturbed re-run with the (seed, perturb) pair named in any
    failure — run_seed's own asserts only know the seed, and a report
    that can't be reproduced is no report (both sweep and smoke lanes
    share this)."""
    from foundationdb_tpu.testing import soak

    try:
        return soak.run_seed(seed, spec=spec, perturb=pid, trace=trace,
                             status_probe=status_probe)
    except Exception as e:
        raise AssertionError(
            f"seed {seed} perturb {pid} (spec {spec_label}): {e}"
        ) from e


def _one(args):
    seed, spec_name, check_determinism, perturb, trace, status_probe = args
    from foundationdb_tpu.testing import soak

    t0 = time.perf_counter()
    sig, hits = soak.run_seed(
        seed, spec=spec_name, collect_probes=True, trace=trace,
        status_probe=status_probe,
    )
    if check_determinism:
        sig2 = soak.run_seed(seed, spec=spec_name, trace=trace,
                             status_probe=status_probe)
        if sig != sig2:
            raise AssertionError(
                f"seed {seed} (spec {spec_name}): NONDETERMINISTIC\n"
                f"  run1: {sig}\n  run2: {sig2}"
            )
    # Schedule perturbation: each perturbation id reruns the seed under
    # seeded randomized tie-breaking among equally-runnable actors. A
    # perturbed order is a LEGAL schedule, so every gate must still
    # pass (model checks, interleaving auditor, unhandled-error gate);
    # outcome COUNTS may legitimately differ (different conflict
    # winners are different legal executions). What must be identical
    # is each perturbed schedule with itself: on determinism-cadence
    # seeds every (seed, perturb) pair runs twice and must match —
    # the unseed-determinism contract extended to perturbed schedules.
    for pid in range(1, perturb + 1):
        psig = _perturbed_rerun(seed, spec_name, pid, spec_name,
                                trace=trace, status_probe=status_probe)
        if check_determinism:
            psig2 = soak.run_seed(
                seed, spec=spec_name, perturb=pid, trace=trace,
                status_probe=status_probe,
            )
            if psig != psig2:
                raise AssertionError(
                    f"seed {seed} perturb {pid} (spec {spec_name}): "
                    f"NONDETERMINISTIC\n  run1: {psig}\n  run2: {psig2}"
                )
    return seed, sig, time.perf_counter() - t0, check_determinism, hits


def _emit_perf_row(spec_name: str, seeds: list, perturb: int,
                   totals: dict, traced_commits: int) -> None:
    """One canonical perf-ledger row for a traced sweep (utils/perf.py):
    outcome totals across a FIXED (spec, seed set, perturb) plan are
    deterministic, so they land in the structural tier and perfcheck
    exact-compares them — a traced sweep whose committed/aborted totals
    drift without a spec change is a behavior change, not noise."""
    from foundationdb_tpu.utils import perf

    metrics = {
        name: perf.metric(v, "count", direction, tier="structural")
        for name, v, direction in (
            ("committed", totals["committed"], "higher"),
            ("aborted", totals["aborted"], "lower"),
            ("read_checks", totals["read_checks"], "higher"),
            ("api_acked", totals["api_acked"], "higher"),
            ("traced_commits", traced_commits, "higher"),
        )
    }
    rec = perf.emit(
        "soak", metrics,
        workload={
            "spec": spec_name,
            "seeds": [seeds[0], seeds[-1]] if seeds else [],
            "n_seeds": len(seeds),
            "perturb": perturb,
        },
    )
    print(f"[perf] soak ledger row appended "
          f"(committed={rec['metrics']['committed']['value']})")


def sweep(spec_name: str, seeds: list, jobs: int, probe_gate: bool,
          perturb: int = 0, trace: bool = False,
          status_probe: bool = False, inline: bool = False) -> int:
    """Run one spec's seed sweep; returns the number of failures."""
    from foundationdb_tpu.testing.spec import load_spec
    from foundationdb_tpu.utils import probes as _probes

    spec = load_spec(spec_name)
    det_every = spec.policy["determinism_every"]
    work = [
        (s, spec_name, i % det_every == 0, perturb, trace, status_probe)
        for i, s in enumerate(seeds)
    ]
    t0 = time.perf_counter()
    failures = []
    done = 0
    committed = aborted = rechecks = det_checked = 0
    api_acked = api_reads = traced_commits = 0
    # per-seed probe snapshots aggregate LOCALLY, not straight into the
    # probes global: inline (--profile-dir) mode runs run_seed in THIS
    # process, and each seed's collect_probes reset would wipe whatever
    # an eager merge had accumulated (pool mode resets only workers).
    # The local total folds into the global once, after the last seed.
    probe_agg: dict = {}
    # Worker RSS grows across seeds (~20GB by seed ~2000 once the
    # backup workload added a second cluster per seed), so workers must
    # recycle. max_tasks_per_child forces the SPAWN context, whose
    # worker respawn wedges under this environment's shell — recycle by
    # CHUNK instead: a fresh fork-context pool every 400 seeds bounds
    # worker lifetime with no start-method change.
    CHUNK = 400

    class _InlineFuture:
        """Run one work item in THIS process (--profile-dir: a worker
        pool's device activity is invisible to the parent's jax
        profiler). Same .result() surface as the pool future."""

        def __init__(self, w):
            try:
                self._result, self._err = _one(w), None
            except Exception as e:  # surfaced via result(), like a pool
                self._result, self._err = None, e

        def result(self):
            if self._err is not None:
                raise self._err
            return self._result

    import contextlib

    for lo in range(0, len(work), CHUNK):
        with (contextlib.nullcontext() if inline
              else ProcessPoolExecutor(max_workers=jobs)) as pool:
            if inline:
                # a LAZY generator: each seed runs as the loop reaches
                # it, so progress lines stay live and a crash surfaces
                # immediately instead of after the whole chunk
                pairs = (
                    (_InlineFuture(w), w[0]) for w in work[lo:lo + CHUNK]
                )
            else:
                futs = {
                    pool.submit(_one, w): w[0] for w in work[lo:lo + CHUNK]
                }
                pairs = ((f, futs[f]) for f in as_completed(futs))
            for fut, seed in pairs:
                try:
                    s, sig, dt, det, hits = fut.result()
                    from foundationdb_tpu.testing.soak import (
                        signature_metrics,
                    )

                    sm = signature_metrics(sig)
                    for k, v in hits.items():
                        probe_agg[k] = probe_agg.get(k, 0) + v
                    done += 1
                    committed += sm["committed"]
                    aborted += sm["aborted"]
                    rechecks += sm["read_checks"]
                    traced_commits += sm.get("traced_commits", 0)
                    det_checked += int(det)
                    api_sig = sm["api"]
                    if api_sig is not None:
                        api_acked += api_sig[0]
                        api_reads += api_sig[7]
                    print(
                        f"seed {s:5d} ok in {dt:5.1f}s  "
                        f"committed={sig[1]:3d} "
                        f"aborted={sig[2]:3d} epoch={sig[5]}"
                        + (
                            f"  api(acked={api_sig[0]},"
                            f"checked={api_sig[7]})"
                            if api_sig is not None else ""
                        )
                        + ("  [determinism OK]" if det else ""),
                        flush=True,
                    )
                except Exception as e:
                    failures.append((seed, repr(e)))
                    print(f"seed {seed:5d} FAILED: {e!r}", flush=True)
    wall = time.perf_counter() - t0
    # fold the locally-aggregated hits into the global ONCE (an inline
    # run's last seed left its own hits there — reset first so the
    # aggregate is the single source and nothing double-counts)
    _probes.reset()
    _probes.merge(probe_agg)
    print(
        f"\n[{spec_name}] {done}/{len(seeds)} seeds passed in {wall:.0f}s "
        f"({jobs} jobs, {perturb} perturbation(s)/seed); "
        f"committed={committed} aborted={aborted} "
        f"read_checks={rechecks} api_acked={api_acked} "
        f"api_reads_checked={api_reads} determinism_checked={det_checked}"
    )
    # ensemble CODE_PROBE coverage (the Joshua probe-accounting role):
    # a declared probe no seed hit means our randomization never reaches
    # that rare path — widen the ensemble or fix the path.
    fired = {k: v for k, v in _probes.snapshot().items() if v}
    print(f"CODE_PROBEs fired ({len(fired)}):")
    for k in sorted(fired):
        print(f"  {k}: {fired[k]}")
    missed = _probes.missed()
    if missed:
        print(f"CODE_PROBEs NEVER HIT ({len(missed)}): {missed}")
    expected_missed = sorted(set(spec.expected_probes) & set(missed))
    if expected_missed:
        print(
            f"[{spec_name}] spec-EXPECTED probes never hit: "
            f"{expected_missed}"
        )
        # occurrence budgets: a rare probe (e.g. api_unknown_resolved,
        # ~2/100 seeds) only gates once this sweep is big enough that
        # its budget predicts >= PROBE_GATE_MIN_EXPECTED hits — short
        # smoke sweeps report the miss but can't false-fail on it
        gated = spec.gated_probes(len(seeds))
        under_budget = sorted(set(expected_missed) - gated)
        gated_missed = sorted(set(expected_missed) & gated)
        if under_budget:
            print(
                f"[{spec_name}] missed-but-under-budget at "
                f"{len(seeds)} seed(s) (not gated): {under_budget}"
            )
        if probe_gate and gated_missed:
            failures.append(("probe-gate", repr(gated_missed)))
    if failures:
        print(f"[{spec_name}] FAILURES:")
        for s, e in failures:
            tag = f"seed {s}" if isinstance(s, int) else s
            print(f"  {tag}: {e}")
    elif trace:
        # traced sweeps are perf runs of record: outcome totals +
        # traced-commit counts land in the ledger's structural tier
        _emit_perf_row(
            spec_name, seeds, perturb,
            {"committed": committed, "aborted": aborted,
             "read_checks": rechecks, "api_acked": api_acked},
            traced_commits,
        )
    return len(failures)


def main():
    from foundationdb_tpu.testing.spec import list_specs

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=20)
    ap.add_argument("--start", type=int, default=0)
    ap.add_argument("--jobs", type=int, default=os.cpu_count() or 4)
    ap.add_argument(
        "--spec", default="default", choices=list_specs(),
        help="named ensemble spec (foundationdb_tpu/testing/specs/)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI lane: run ONE seed per checked-in spec, in process",
    )
    ap.add_argument(
        "--probe-gate", action="store_true",
        help="fail the sweep if a spec-expected probe never fires",
    )
    ap.add_argument(
        "--perturb", type=int, default=0, metavar="K",
        help="re-run each seed K extra times under seeded randomized "
             "tie-breaking among equally-runnable actors; every gate "
             "must still pass and each (seed, perturbation) must be "
             "exactly reproducible",
    )
    ap.add_argument(
        "--status-probe", action="store_true",
        help="arm the saturation-sensor determinism guard: a background "
             "actor samples the full cluster_status() document during "
             "every seed (with --trace, the digest check then proves "
             "reading the sensors leaves traces bit-identical)",
    )
    ap.add_argument(
        "--trace", action="store_true",
        help="run every seed with commit-path telemetry on: the "
             "span-chain gate arms (a committed txn missing a pipeline "
             "stage fails the seed) and the trace digest joins the "
             "determinism signature (bit-identical per seed/perturb)",
    )
    ap.add_argument(
        "--profile-dir", default=None,
        help="capture a jax.profiler trace of the run (forces jobs=1 "
             "in-process execution: a process pool's device work is "
             "invisible to the parent's profiler)",
    )
    args = ap.parse_args()
    if args.profile_dir:
        # the profiler sees THIS process only; a worker pool would
        # produce an empty trace that looks like a measurement
        args.jobs = 1

    from foundationdb_tpu.utils import probes as _probes

    # Pre-declare the ENTIRE static probe manifest (flowcheck's ledger):
    # ensemble coverage accounting then spans every probe in the tree,
    # including ones whose declaring module no seed happened to import —
    # a probe only the manifest knows about shows up as NEVER HIT below.
    from foundationdb_tpu.analysis.manifest import load_manifest

    _probes.declare(*load_manifest())

    if args.smoke:
        # one short deterministic seed per spec, in this process: the
        # scripts/check.sh lane that proves every checked-in spec loads,
        # plans, runs and verifies (api workload included) — not a
        # coverage sweep, so no probe gate.
        from foundationdb_tpu.testing import soak
        from foundationdb_tpu.testing.spec import load_spec

        from foundationdb_tpu.utils import perf as _perf

        failures = []
        with _perf.profile_trace(args.profile_dir):
            for name in list_specs():
                # api=1.0: the lane's contract is that EVERY spec's
                # smoke seed exercises the api model check, whatever
                # the spec's own ensemble probability
                spec = load_spec(name).with_overrides(
                    rounds=(6, 9), api_rounds=6, api=1.0
                )
                t0 = time.perf_counter()
                try:
                    sig = soak.run_seed(
                        args.start, spec=spec, trace=args.trace,
                        status_probe=args.status_probe,
                    )
                    # the perturbation smoke lane: K reorderings of the
                    # same smoke seed must all pass every gate
                    for pid in range(1, args.perturb + 1):
                        _perturbed_rerun(args.start, spec, pid, name,
                                         trace=args.trace,
                                         status_probe=args.status_probe)
                    print(
                        f"spec {name:16s} seed {args.start} ok in "
                        f"{time.perf_counter() - t0:4.1f}s  "
                        f"committed={sig[1]} api={sig[7]}"
                        + (f"  [perturb x{args.perturb} OK]"
                           if args.perturb else ""),
                        flush=True,
                    )
                except Exception as e:
                    failures.append((name, repr(e)))
                    print(f"spec {name:16s} FAILED: {e!r}", flush=True)
        if args.profile_dir:
            print(f"[perf] jax.profiler trace captured in "
                  f"{args.profile_dir}")
        if failures:
            sys.exit(1)
        return

    seeds = list(range(args.start, args.start + args.seeds))
    from foundationdb_tpu.utils import perf as _perf

    with _perf.profile_trace(args.profile_dir):
        failures = sweep(
            args.spec, seeds, args.jobs, args.probe_gate, args.perturb,
            trace=args.trace, status_probe=args.status_probe,
            inline=bool(args.profile_dir),
        )
    if args.profile_dir:
        print(f"[perf] jax.profiler trace captured in {args.profile_dir}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

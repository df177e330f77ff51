#!/usr/bin/env python
"""perfcheck: the perf-ledger regression gate + artifact migration.

    python scripts/perfcheck.py --check /tmp/row.jsonl          # gate
    python scripts/perfcheck.py --check row.jsonl --tier auto
    python scripts/perfcheck.py --import                        # one-shot
    python scripts/perfcheck.py --list
    python scripts/perfcheck.py --compare --source bench

The comparator half (`--check`): each candidate row (a JSONL file of
schema rows, usually just-emitted by a perf CLI) is compared against the
baseline window selected from perf/history.jsonl by FINGERPRINT — rows
whose (source, workload, knobs) key (plus device identity for the
hardware tier) doesn't match are ignored, never "close enough". Per
metric: median of the window + a MAD-derived noise band;
exit 1 on any metric landing outside the band in the WORSE direction.
Two tiers:

* structural (always armed — the check.sh lane): deterministic values
  (merge-row counts, decision counts, compile/batch/shed counts) with a
  ZERO noise floor — an injected doubled merge-row count fails even on
  a CPU-only host.
* hardware (armed by --tier hardware, or --tier auto when the
  candidate's fingerprint shows a real accelerator): wall-clock rates
  and latencies inside median +/- max(4*1.4826*MAD, 5%).

The migration half (`--import`): converts the historical root artifacts
(BENCH_r06.json, PIPELINE_r06/r07.json, SATURATION_r08.json,
MULTICHIP_r0*.json) into schema rows — `schema_version` stamped,
`timestamp: null`, `imported_from` naming the artifact — and writes
them to perf/history.jsonl. The conversion is BYTE-STABLE: re-running
--import reproduces identical bytes (pinned in tests/test_perf.py).

A candidate with no comparable baseline passes with every metric "new"
— the seeding path; --accept appends the candidate to the history
after a passing check (the re-baseline flow for intentional changes).
"""

import argparse
import glob
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# --import: historical artifacts -> ledger rows (deterministic order,
# byte-stable output).


def import_records(repo: str = REPO) -> list:
    from foundationdb_tpu.utils import perf

    recs = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        name = os.path.basename(path)
        with open(path) as f:
            art = json.load(f)
        row = art.get("parsed")
        if not row:
            continue
        recs.append(perf.bench_row_to_record(row, imported_from=name))
    for path in sorted(glob.glob(os.path.join(repo, "PIPELINE_r*.json"))):
        name = os.path.basename(path)
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                recs.extend(perf.pipeline_row_to_records(
                    json.loads(line), imported_from=name
                ))
    for path in sorted(glob.glob(os.path.join(repo, "SATURATION_r*.json"))):
        name = os.path.basename(path)
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                recs.append(perf.saturation_report_to_record(
                    json.loads(line), imported_from=name
                ))
    for path in sorted(glob.glob(os.path.join(repo, "MULTICHIP_r*.json"))):
        name = os.path.basename(path)
        with open(path) as f:
            art = json.load(f)
        recs.append(perf.multichip_artifact_to_record(
            art, imported_from=name
        ))
    return recs


def do_import(out: str, force: bool) -> int:
    from foundationdb_tpu.utils import perf

    recs = import_records()
    imported_already = [
        r for r in perf.load_history(out) if r.get("imported_from")
    ] if os.path.exists(out) else []
    if imported_already and not force:
        print(f"perfcheck --import: {out} already holds "
              f"{len(imported_already)} imported row(s); pass --force to "
              "append anyway", file=sys.stderr)
        return 1
    for rec in recs:
        perf.append(rec, path=out)
    by_src: dict = {}
    for r in recs:
        by_src[r["source"]] = by_src.get(r["source"], 0) + 1
    print(f"perfcheck --import: {len(recs)} row(s) -> {out} "
          f"({', '.join(f'{k}={v}' for k, v in sorted(by_src.items()))})")
    return 0


# ---------------------------------------------------------------------------
# --scaling: the per-chip scaling curve from the ledger.


def do_scaling(history: list, source: str = None, window: int = 8) -> int:
    """Group ledger rows by device count at a fixed fingerprint and
    print the per-chip scaling curve: txn/s, txn/s per device, and
    parallel efficiency vs the smallest device count (1-chip when a
    1-chip row exists). Replaces eyeballing MULTICHIP_r*.json tails:
    every multichip/shard run lands a fingerprinted row, and this view
    reads the curve straight off the ledger."""
    import json as _json

    from foundationdb_tpu.utils import perf

    groups: dict = {}
    for r in history:
        if source and r.get("source") != source:
            continue
        m = r.get("metrics", {})
        if "txn_s" not in m:
            continue
        wl = dict(r.get("workload", {}))
        fp = r.get("fingerprint") or {}
        # the device count is the VARYING axis: strip it from the
        # grouping key, read it from the workload (virtual-device rows
        # record their mesh width there — the host flag pins the
        # fingerprint's device_count at the max) or the fingerprint
        n = wl.pop("n_devices", None) or wl.pop("n_shards", None)
        if n is None:
            n = fp.get("device_count")
        if not n:
            continue
        key = (
            r.get("source"),
            _json.dumps(wl, sort_keys=True),
            _json.dumps(r.get("knobs", {}), sort_keys=True),
            fp.get("backend"), fp.get("device_kind"),
            fp.get("jaxlib_version"),
        )
        groups.setdefault(key, {}).setdefault(int(n), []).append(
            float(m["txn_s"]["value"])
        )
    groups = {k: v for k, v in groups.items() if len(v) > 1}
    if not groups:
        print("perfcheck --scaling: no ledger group spans more than one "
              "device count (need txn_s rows at >= 2 widths; run "
              "scripts/shard_smoke.py --perf-out perf/history.jsonl)")
        return 0
    for key, by_n in sorted(groups.items(), key=str):
        src, wl, knobs, backend, kind, jaxlib = key
        print(f"== {src} {wl}")
        print(f"   knobs {knobs} [{backend}/{kind}/jaxlib {jaxlib}] ==")
        base = None
        for n in sorted(by_n):
            samples = by_n[n][-window:]
            med = perf._median(samples)
            per_dev = med / n
            if base is None:
                base = per_dev
            eff = per_dev / base if base else 0.0
            print(f"  {n:>3} device(s) {med:>14.1f} txn/s "
                  f"{per_dev:>14.1f} txn/s/device  efficiency {eff:5.2f}  "
                  f"(median of {len(samples)})")
    return 0


# ---------------------------------------------------------------------------
# --check: candidate rows vs the history's baseline windows.


def check_rows(candidates: list, history: list, tiers: list[str],
               window: int) -> tuple[int, list]:
    from foundationdb_tpu.utils import perf

    rc = 0
    reports = []
    for rec in candidates:
        perf.validate_record(rec)
        for tier in tiers:
            if not any(
                m.get("tier") == tier for m in rec["metrics"].values()
            ):
                continue
            rep = perf.compare(rec, history, tier=tier, window=window)
            reports.append((rec, tier, rep))
            label = f"{rec['source']}/{tier}"
            print(f"== {label}: {rep['baseline_rows']} baseline row(s) ==")
            for name, m in rep["metrics"].items():
                status = m["status"]
                line = (f"  {name:<32} {m['value']:>14g} {m['unit'] or '':<6}"
                        f" [{status}]")
                if "baseline_median" in m:
                    line += (f" baseline {m['baseline_median']:g} "
                             f"+/- {m['band']:g} (n={m['n_baseline']})")
                print(line)
            if rep["regressions"]:
                print(f"perfcheck: {label} REGRESSED: "
                      f"{rep['regressions']}", file=sys.stderr)
                rc = 1
    return rc, reports


def load_rows(path: str) -> list:
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--check", metavar="ROWS_JSONL",
                      help="gate candidate row(s) against the history")
    mode.add_argument("--import", dest="do_import", action="store_true",
                      help="migrate the root BENCH/PIPELINE/SATURATION/"
                           "MULTICHIP artifacts into the ledger")
    mode.add_argument("--list", action="store_true",
                      help="summarize the ledger")
    mode.add_argument("--compare", action="store_true",
                      help="latest row per (source, workload) vs its "
                           "baseline window — the hardware re-measure "
                           "checklist's view")
    mode.add_argument("--scaling", action="store_true",
                      help="group txn_s rows by device count at a fixed "
                           "fingerprint and print the per-chip scaling "
                           "curve (txn/s per device, efficiency vs the "
                           "smallest width)")
    ap.add_argument("--history", default=None,
                    help="ledger path (default perf/history.jsonl)")
    ap.add_argument("--tier", default="structural",
                    choices=("structural", "hardware", "auto", "both"),
                    help="auto = structural always + hardware when the "
                         "candidate fingerprint shows an accelerator")
    ap.add_argument("--window", type=int, default=8,
                    help="baseline window size (median-of-N)")
    ap.add_argument("--accept", action="store_true",
                    help="append passing candidates to the history "
                         "(the re-baseline flow)")
    ap.add_argument("--source", default=None,
                    help="--list/--compare: restrict to one source")
    ap.add_argument("--force", action="store_true",
                    help="--import: append even if imported rows exist")
    args = ap.parse_args()

    from foundationdb_tpu.utils import perf

    history_path = args.history or perf.history_path()

    if args.do_import:
        return do_import(history_path, args.force)

    history = perf.load_history(history_path)

    if args.scaling:
        return do_scaling(history, args.source, args.window)

    if args.list:
        by_key: dict = {}
        for r in history:
            if args.source and r.get("source") != args.source:
                continue
            k = (r.get("source"), r.get("workload", {}).get("metric")
                 or r.get("workload", {}).get("spec") or "")
            by_key[k] = by_key.get(k, 0) + 1
        print(f"{len(history)} row(s) in {history_path}")
        for (src, wk), n in sorted(by_key.items()):
            print(f"  {src:<16} {wk:<40} {n} row(s)")
        return 0

    if args.compare:
        latest: dict = {}
        for r in history:
            if args.source and r.get("source") != args.source:
                continue
            latest[perf.fingerprint_key(r, "structural")] = r
        rc = 0
        for r in latest.values():
            rc2, _ = check_rows(
                [r], [h for h in history if h is not r],
                ["structural", "hardware"], args.window,
            )
            rc = rc or rc2
        return rc

    candidates = load_rows(args.check)
    if not candidates:
        print(f"perfcheck: no candidate rows in {args.check}",
              file=sys.stderr)
        return 2
    if args.tier == "both":
        tiers = ["structural", "hardware"]
    elif args.tier == "auto":
        tiers = ["structural"]
        # a real accelerator shows in device_kind (fingerprint.backend
        # can be a RESOLVER backend name like "native"/"tpu-force" on
        # pipeline rows, which says nothing about the host's device)
        if any(
            (c.get("fingerprint") or {}).get("device_kind")
            not in (None, "cpu")
            for c in candidates
        ):
            tiers.append("hardware")
    else:
        tiers = [args.tier]
    rc, _reports = check_rows(candidates, history, tiers, args.window)
    if rc == 0 and args.accept:
        # experiment rows are autotune TRIALS — the searcher's cache,
        # never a committed baseline. The winner must be re-emitted
        # without the field (scripts/autotune.py --promote does) before
        # it can be accepted.
        trials = [r for r in candidates if r.get("experiment")]
        if trials:
            print(f"perfcheck: refusing --accept: {len(trials)} candidate "
                  f"row(s) carry an `experiment` marker "
                  f"({sorted({r['experiment'] for r in trials})}); promote "
                  "the winner without it (scripts/autotune.py "
                  "--promote-out)",
                  file=sys.stderr)
            return 1
        for rec in candidates:
            perf.append(rec, path=history_path)
        print(f"perfcheck: {len(candidates)} candidate row(s) accepted "
              f"into {history_path}")
    print("perfcheck ok" if rc == 0 else "perfcheck FAILED")
    return rc


if __name__ == "__main__":
    sys.exit(main())

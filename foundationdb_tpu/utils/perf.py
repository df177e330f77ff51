"""The unified performance ledger: one canonical run-record schema.

Every perf CLI in this repo (bench.py, scripts/bench_pipeline.py,
scripts/saturation.py, scripts/soak.py --trace, scripts/kernel_smoke.py)
emits its headline numbers through `emit()` into ONE append-only JSONL
ledger — `perf/history.jsonl` — while keeping its existing JSON output
as a view. A ledger row is self-describing:

* `schema_version` — bump on any incompatible shape change.
* `source` — which CLI produced it ("bench", "bench_pipeline",
  "saturation", "soak", "kernel_smoke", "multichip").
* `git_sha` / `timestamp` — provenance (imported historical rows carry
  `timestamp: null` and `imported_from: <artifact>` so re-import is
  byte-stable).
* `fingerprint` — the host/device identity a comparator needs to avoid
  comparing a CPU-host structural run against a v5e hardware run:
  backend, device kind/count, jax/jaxlib versions, python, machine.
  (BENCH_r06 recorded only `backend`, so CPU-host and v5e rows
  were indistinguishable — the r10 satellite this field set fixes.)
* `workload` — the shapes that make two runs comparable (txns, batches,
  mode, spec, seeds, ...).
* `knobs` — the knob fingerprint (kernel kind, delta capacity, dedup,
  fuse, ...): a knob change is a different experiment, not noise.
* `experiment` — OPTIONAL: the autotune search this row is a TRIAL of
  (scripts/autotune.py stamps the search id). Experiment rows are the
  searcher's resumability cache — fingerprint-keyed, so a re-run skips
  already-measured configurations — and are EXCLUDED from baseline
  windows in both directions: a normal candidate never compares against
  trials, and a trial row can never be accepted as a committed baseline
  (`perfcheck --accept` refuses it). The search winner is re-emitted
  WITHOUT the field through `perfcheck --check --accept`.
* `metrics` — a FLAT name -> {value, unit, direction, tier} map.
  direction is "higher" | "lower" (which way is better); tier is
  "structural" (deterministic on any host: merge-row counts, compile
  counts, batch/shed/abort counts, bytes on the wire — compared
  exactly) or "hardware" (wall-clock rates/latencies — compared inside
  a median-of-N + MAD noise band, armed only when the fingerprints
  match).

The comparator (`compare()`, CLI scripts/perfcheck.py) selects the
baseline window from the ledger by fingerprint key, applies
median + MAD bands per metric, and reports regressions — the
`perf.regression_gate_tripped` probe fires on any. scripts/check.sh
gates the structural tier on every PR; the hardware tier arms when the
fingerprint shows a real accelerator.
"""

from __future__ import annotations

import json
import math
import os
from typing import Any, Optional

from foundationdb_tpu.utils.probes import declare, code_probe

declare("perf.regression_gate_tripped")

SCHEMA_VERSION = 1

#: metrics directions: which way is BETTER
DIRECTIONS = ("higher", "lower")
#: structural = deterministic on any host (exact compare);
#: hardware = wall-clock (noise-banded, fingerprint-gated)
TIERS = ("structural", "hardware")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: fingerprint fields that make hardware-tier rows comparable — a
#: different device kind/count or jaxlib is a different experiment
HARDWARE_FP_KEYS = ("backend", "device_kind", "device_count",
                    "jaxlib_version")


def perf_dir() -> str:
    return os.environ.get(
        "FDBTPU_PERF_DIR", os.path.join(_REPO_ROOT, "perf")
    )


def history_path() -> str:
    """The canonical ledger file. `FDBTPU_PERF_LEDGER` redirects every
    emitter at once (CI smoke lanes point it at a tempfile so green
    runs don't dirty the committed history)."""
    return os.environ.get(
        "FDBTPU_PERF_LEDGER", os.path.join(perf_dir(), "history.jsonl")
    )


# ---------------------------------------------------------------------------
# Fingerprints.


def device_fingerprint() -> dict:
    """The full host/device identity for a ledger row.

    bench.py's old `backend` field alone cannot distinguish a CPU-host
    structural run from a v5e hardware run; the comparator needs device
    kind/count and the jaxlib version (an XLA upgrade resets hardware
    baselines). Never raises: a host without a working JAX still gets a
    row (backend "none") so non-device CLIs can emit."""
    import platform

    fp = {
        "backend": "none",
        "device_kind": None,
        "device_count": 0,
        "jax_version": None,
        "jaxlib_version": None,
        "python_version": platform.python_version(),
        "machine": platform.machine(),
    }
    try:
        import jax
        import jaxlib

        fp["jax_version"] = jax.__version__
        fp["jaxlib_version"] = jaxlib.__version__
        devices = jax.devices()
        fp["backend"] = jax.default_backend()
        fp["device_count"] = len(devices)
        fp["device_kind"] = devices[0].device_kind if devices else None
    except Exception:
        pass
    return fp


def device_fingerprint_from_child() -> dict:
    """device_fingerprint() taken in a child process, which lets go of
    the chip when it exits: for a parent whose own subprocesses need
    the chip (a process that touched the TPU holds it until it exits)."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-c",
         "import json; from foundationdb_tpu.utils import perf; "
         "print(json.dumps(perf.device_fingerprint()))"],
        cwd=_REPO_ROOT, capture_output=True, text=True, timeout=300,
        check=True,
    )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _git_sha() -> Optional[str]:
    try:
        import subprocess

        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_REPO_ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Records.


def metric(value, unit: str, direction: str = "lower",
           tier: str = "hardware") -> dict:
    """One metrics-map entry; validated again at append time."""
    if direction not in DIRECTIONS:
        raise ValueError(f"direction must be one of {DIRECTIONS}")
    if tier not in TIERS:
        raise ValueError(f"tier must be one of {TIERS}")
    return {"value": value, "unit": unit, "direction": direction,
            "tier": tier}


_NOW = object()  # sentinel: stamp at build time


def make_record(source: str, metrics: dict, *, workload: dict = None,
                knobs: dict = None, fingerprint: dict = None,
                timestamp=_NOW, git_sha=None,
                imported_from: str = None, extra: dict = None,
                experiment: str = None) -> dict:
    """Assemble one schema-valid ledger row. Imported historical rows
    carry `timestamp: null` / `git_sha: null` (unless given) so the
    migration is byte-stable — re-running --import reproduces
    identical bytes. `experiment` marks the row an autotune TRIAL
    (absent on every non-trial row, keeping pre-r15 bytes stable)."""
    import time as _time

    rec = {
        "schema_version": SCHEMA_VERSION,
        "source": source,
        "git_sha": git_sha if (git_sha or imported_from) else _git_sha(),
        "timestamp": (
            None if imported_from
            else (round(_time.time(), 3) if timestamp is _NOW
                  else timestamp)
        ),
        "fingerprint": (
            fingerprint if fingerprint is not None else device_fingerprint()
        ),
        "workload": workload or {},
        "knobs": knobs or {},
        "metrics": metrics,
    }
    if imported_from:
        rec["imported_from"] = imported_from
    if experiment:
        rec["experiment"] = experiment
    if extra:
        rec["extra"] = extra
    validate_record(rec)
    return rec


def validate_record(rec: dict) -> None:
    """Raise ValueError (naming every problem) unless `rec` is a
    schema-valid ledger row."""
    problems = []
    if rec.get("schema_version") != SCHEMA_VERSION:
        problems.append(
            f"schema_version must be {SCHEMA_VERSION}, "
            f"got {rec.get('schema_version')!r}"
        )
    if not rec.get("source") or not isinstance(rec.get("source"), str):
        problems.append("source must be a non-empty string")
    fp = rec.get("fingerprint")
    if not isinstance(fp, dict):
        problems.append("fingerprint must be a dict")
    else:
        for key in ("backend", "device_kind", "device_count",
                    "jax_version", "jaxlib_version"):
            if key not in fp:
                problems.append(f"fingerprint missing {key!r}")
    for key in ("workload", "knobs"):
        if not isinstance(rec.get(key), dict):
            problems.append(f"{key} must be a dict")
    if "experiment" in rec and not (
        isinstance(rec["experiment"], str) and rec["experiment"]
    ):
        problems.append("experiment must be a non-empty string when present")
    metrics = rec.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        problems.append("metrics must be a non-empty dict")
    else:
        for name, m in metrics.items():
            if not isinstance(m, dict):
                problems.append(f"metric {name!r} must be a dict")
                continue
            if not isinstance(m.get("value"), (int, float)) or isinstance(
                m.get("value"), bool
            ):
                problems.append(f"metric {name!r} value must be a number")
            if m.get("direction") not in DIRECTIONS:
                problems.append(
                    f"metric {name!r} direction must be one of {DIRECTIONS}"
                )
            if m.get("tier") not in TIERS:
                problems.append(
                    f"metric {name!r} tier must be one of {TIERS}"
                )
            if "unit" not in m:
                problems.append(f"metric {name!r} missing unit")
    if problems:
        raise ValueError(
            "invalid perf record: " + "; ".join(problems)
        )


def append(rec: dict, path: str = None) -> str:
    """Validate + append one row to the ledger; returns the path."""
    validate_record(rec)
    path = path or history_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(rec, sort_keys=True) + "\n")
    return path


def emit(source: str, metrics: dict, *, workload: dict = None,
         knobs: dict = None, ledger: str = None, extra: dict = None,
         experiment: str = None) -> dict:
    """The one call every perf CLI makes: build a row for THIS host and
    append it to the ledger (or `ledger`/$FDBTPU_PERF_LEDGER)."""
    rec = make_record(source, metrics, workload=workload, knobs=knobs,
                      extra=extra, experiment=experiment)
    append(rec, path=ledger)
    return rec


def load_history(path: str = None) -> list[dict]:
    """All ledger rows, oldest first. Strict: a malformed line is a
    corrupted ledger, not noise to skip."""
    path = path or history_path()
    if not os.path.exists(path):
        return []
    rows = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: malformed ledger line "
                                 f"({e})") from e
    return rows


# ---------------------------------------------------------------------------
# Baseline selection + the noise-aware comparator.


def fingerprint_key(rec: dict, tier: str) -> tuple:
    """The comparability key for baseline selection.

    Structural metrics are deterministic on ANY host (merge-row counts,
    batch counts, shed/abort counts), so the key is (source, workload,
    knobs) — rows from different machines still gate each other. The
    hardware tier adds the device identity: wall-clock rates only
    compare within (backend, device kind/count, jaxlib)."""
    key = (
        rec.get("source"),
        json.dumps(rec.get("workload", {}), sort_keys=True),
        json.dumps(rec.get("knobs", {}), sort_keys=True),
    )
    if tier == "hardware":
        fp = rec.get("fingerprint", {})
        key += tuple(fp.get(k) for k in HARDWARE_FP_KEYS)
    return key


def baseline_window(history: list[dict], candidate: dict, *, tier: str,
                    window: int = 8) -> list[dict]:
    """The most recent `window` ledger rows comparable to `candidate`
    at `tier` (matching fingerprint key, same schema). Rows with a
    mismatched fingerprint are ignored, never 'close enough'.
    EXPERIMENT rows (autotune trials) are never baselines: a trial runs
    a deliberately non-default knob point, so comparing a committed
    configuration against it would gate the tree on a configuration
    nobody shipped."""
    want = fingerprint_key(candidate, tier)
    matched = [
        r for r in history
        if r.get("schema_version") == candidate.get("schema_version")
        and not r.get("experiment")
        and fingerprint_key(r, tier) == want
    ]
    return matched[-window:]


def _median(xs: list[float]) -> float:
    s = sorted(xs)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def _mad(xs: list[float], med: float) -> float:
    return _median([abs(x - med) for x in xs])


def compare(candidate: dict, history: list[dict], *, tier: str,
            window: int = 8, k_mad: float = 4.0,
            rel_floor: float = None) -> dict:
    """Noise-aware regression check of one candidate row against the
    ledger.

    Per metric in `candidate` at `tier`: take the matching-fingerprint
    baseline window, compute median + MAD, and flag a regression when
    the candidate lands OUTSIDE median +/- max(k_mad * 1.4826 * MAD,
    rel_floor * |median|) in the WORSE direction (improvements never
    fail — they widen the next window instead). Defaults: structural
    rel_floor 0.0 (deterministic values compare exactly — a doubled
    merge-row count is a regression, not noise), hardware rel_floor
    0.05 (shared-host timers swing; the MAD term grows the band when
    the recorded history is noisier than 5%).

    Returns {"tier", "baseline_rows", "metrics": {name: {...}},
    "regressions": [names]}. Fires perf.regression_gate_tripped when
    any metric regresses. A candidate with NO comparable baseline rows
    reports every metric "new" and passes — the seeding path.
    """
    if rel_floor is None:
        rel_floor = 0.0 if tier == "structural" else 0.05
    base = baseline_window(history, candidate, tier=tier, window=window)
    out: dict[str, Any] = {
        "tier": tier,
        "baseline_rows": len(base),
        "metrics": {},
        "regressions": [],
    }
    for name, m in sorted(candidate.get("metrics", {}).items()):
        if m.get("tier") != tier:
            continue
        samples = [
            float(r["metrics"][name]["value"]) for r in base
            if name in r.get("metrics", {})
        ]
        entry: dict[str, Any] = {
            "value": float(m["value"]),
            "unit": m.get("unit"),
            "direction": m.get("direction"),
            "n_baseline": len(samples),
        }
        if not samples:
            entry["status"] = "new"
            out["metrics"][name] = entry
            continue
        med = _median(samples)
        band = max(
            k_mad * 1.4826 * _mad(samples, med), rel_floor * abs(med)
        )
        entry.update(baseline_median=med, band=band)
        value = float(m["value"])
        worse = (
            value < med - band if m.get("direction") == "higher"
            else value > med + band
        )
        better = (
            value > med + band if m.get("direction") == "higher"
            else value < med - band
        )
        entry["status"] = (
            "regression" if worse else "improved" if better else "ok"
        )
        if worse:
            out["regressions"].append(name)
        out["metrics"][name] = entry
    code_probe(out["regressions"], "perf.regression_gate_tripped")
    return out


# ---------------------------------------------------------------------------
# JAX device / compile profiling hooks.


def profile_trace(profile_dir: Optional[str]):
    """Context manager: capture a `jax.profiler` device/host trace into
    `profile_dir` (xplane protos viewable in TensorBoard/XProf); a
    no-op when the dir is falsy or the profiler is unavailable, so
    callers gate on nothing."""
    import contextlib

    if not profile_dir:
        return contextlib.nullcontext()
    try:
        import jax

        os.makedirs(profile_dir, exist_ok=True)
        return jax.profiler.trace(profile_dir)
    except Exception:
        return contextlib.nullcontext()


def device_memory_stats(device=None) -> dict:
    """Live-buffer / peak device memory for one device, normalized to
    {"bytes_in_use", "peak_bytes_in_use", ...}. Empty on backends that
    don't report (XLA:CPU returns None) — samplers treat empty as
    'nothing to record', never an error."""
    try:
        import jax

        dev = device or jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return {}
    if not stats:
        return {}
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_alloc_size", "num_allocs"):
        if key in stats:
            out[key] = int(stats[key])
    return out


def cost_analysis_of(jitted, *args, **kwargs) -> dict:
    """HLO cost-model extraction for one compiled program: FLOPs and
    bytes accessed (plus transcendentals when reported), normalized
    key names. With the persistent compile cache on, lower+compile of
    an already-warm signature is a cache hit, so recording this per
    bench run is cheap. Empty dict on any failure — the roofline
    comparison is an observability extra, never a gate."""
    try:
        analysis = jitted.lower(*args, **kwargs).compile().cost_analysis()
    except Exception:
        return {}
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    if not isinstance(analysis, dict):
        return {}
    out = {}
    for key, norm in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed"),
                      ("transcendentals", "transcendentals"),
                      ("optimal_seconds", "optimal_seconds")):
        v = analysis.get(key)
        if isinstance(v, (int, float)) and math.isfinite(v):
            out[norm] = float(v)
    return out


# ---------------------------------------------------------------------------
# Converters: one shared row shape per CLI, used by BOTH the live
# emitters and the historical-artifact importer (scripts/perfcheck.py
# --import) so imported baselines and fresh rows land on the same
# fingerprint keys.


def bench_row_to_metrics(row: dict) -> dict:
    """bench.py's printed JSON row -> the ledger metrics map."""
    m = {
        "txn_s": metric(row.get("value", 0.0), "txn/s", "higher"),
        "vs_baseline": metric(row.get("vs_baseline", 0.0), "ratio",
                              "higher"),
    }
    for src, name, unit, direction in (
        ("device_resident_txn_s", "device_resident_txn_s", "txn/s",
         "higher"),
        ("baseline_txns_per_sec", "cpu_baseline_txn_s", "txn/s", "higher"),
        ("p50_ms", "latency_p50_ms", "ms", "lower"),
        ("p99_ms", "latency_p99_ms", "ms", "lower"),
        ("p50_incl_transfer_ms", "latency_incl_transfer_p50_ms", "ms",
         "lower"),
    ):
        if src in row:
            m[name] = metric(row[src], unit, direction)
    abl = row.get("ablation") or {}
    for src, name in (
        ("merge_rows_classic_per_group", "merge_rows_classic_per_group"),
        ("merge_rows_tiered_per_batch_cap", "merge_rows_tiered_cap"),
        ("merge_rows_tiered_per_batch_live", "merge_rows_tiered_live"),
        ("delta_live_boundaries", "delta_live_boundaries"),
        ("main_live_boundaries", "main_live_boundaries"),
    ):
        if src in abl:
            m[name] = metric(abl[src], "rows", "lower", tier="structural")
    for src, name in (("pack_ms_per_group", "pack_ms_per_group"),
                      ("transfer_ms_per_group", "transfer_ms_per_group"),
                      ("kernel_ms_per_group", "kernel_ms_per_group"),
                      ("fence_ms_per_group", "fence_ms_per_group")):
        if src in abl:
            m[name] = metric(abl[src], "ms", "lower")
    # ISSUE 14 structural accounting (absent on pre-r14 rows, keeping
    # the historical --import byte-stable): total decisions over the
    # seeded stream plus the range-path counters — deterministic on any
    # host, gated exactly by perfcheck (the YCSB-E acceptance row)
    st = row.get("structural") or {}
    for src, name, direction in (
        ("committed", "decisions_committed", "higher"),
        ("conflicted", "decisions_conflicted", "lower"),
        ("too_old", "decisions_too_old", "lower"),
        ("spills", "spills", "lower"),
        ("sweep_groups", "sweep_groups", "higher"),
        ("compactions", "compactions", "lower"),
    ):
        if src in st:
            m[name] = metric(st[src], "count", direction, tier="structural")
    if "sweep_rows_per_group" in st:
        m["sweep_rows_per_group"] = metric(
            st["sweep_rows_per_group"], "rows", "lower", tier="structural"
        )
    cc = row.get("compile_cache") or {}
    if cc:
        # both counters depend on persistent-cache warmth (JAX fires
        # backend_compile_duration only on an ACTUAL XLA compile; a
        # cache hit skips it) -> hardware tier, informational: a
        # recompile explosion is visible in the ledger without a cold
        # first run on a fresh clone false-failing the exact gate
        m["compile_count"] = metric(
            cc.get("backend_compiles", 0), "count", "lower"
        )
        m["compile_cache_misses"] = metric(
            cc.get("cache_misses", cc.get("misses", 0)), "count", "lower"
        )
    # HLO cost-model numbers depend on the XLA backend and compiler
    # version (fusion changes bytes accessed), so they live in the
    # hardware tier: compared only between matching device/jaxlib
    # fingerprints, never exact-gated across hosts
    hlo = row.get("hlo_cost") or {}
    if "flops" in hlo:
        m["kernel_flops"] = metric(hlo["flops"], "flops", "lower")
    if "bytes_accessed" in hlo:
        m["kernel_bytes_accessed"] = metric(
            hlo["bytes_accessed"], "bytes", "lower"
        )
    return m


def bench_row_to_record(row: dict, *, imported_from: str = None,
                        fingerprint: dict = None) -> dict:
    """bench.py row -> full ledger record (live or imported)."""
    if fingerprint is None:
        fp = {k: None for k in ("device_kind", "jax_version",
                                "jaxlib_version", "python_version",
                                "machine")}
        fp["backend"] = row.get("backend")
        fp["device_count"] = 1 if row.get("backend") else 0
        fingerprint = fp
    workload = {
        "metric": row.get("metric"),
        "batches": row.get("batches"),
        "staging": row.get("staging", "device"),
    }
    knobs = {
        # every row since r6 ran the tiered kernel, the only one left:
        # rows that no longer name it key with the rows that did
        "kernel": row.get("kernel", "tiered"),
        "fused_dispatch": row.get("fused_dispatch"),
        "delta_capacity": row.get("delta_capacity"),
        "dedup_reads": row.get("dedup_reads"),
        "compact_interval": row.get("compact_interval"),
    }
    # r14 knobs join the fingerprint only when present, so every
    # pre-r14 row's baseline key is unchanged (import byte-stability)
    for k in ("range_sweep", "delta_spill"):
        if row.get(k):
            knobs[k] = row[k]
    return make_record(
        "bench", bench_row_to_metrics(row), workload=workload, knobs=knobs,
        fingerprint=fingerprint, imported_from=imported_from,
    )


def pipeline_row_to_records(row: dict, *, imported_from: str = None,
                            fingerprint: dict = None) -> list[dict]:
    """bench_pipeline.py row (one per run, N backends) -> one ledger
    record per backend."""
    recs = []
    # committed/conflicted/ops counts are STRUCTURAL only in cluster
    # mode (the deterministic virtual-clock simulation); a wire run's
    # retry counts ride real asyncio timing and belong in the
    # noise-banded hardware tier
    count_tier = "structural" if row.get("mode") == "cluster" else "hardware"
    for backend, res in (row.get("backends") or {}).items():
        if fingerprint is None:
            fp = {k: None for k in ("device_kind", "jax_version",
                                    "jaxlib_version", "python_version",
                                    "machine")}
            fp["backend"] = backend
            fp["device_count"] = 0
            this_fp = fp
        else:
            this_fp = dict(fingerprint)
        metrics = {
            "txn_s": metric(res.get("txn_s", 0.0), "txn/s", "higher"),
            "commit_p50_ms": metric(res.get("commit_p50_ms", 0.0), "ms",
                                    "lower"),
            "commit_p99_ms": metric(res.get("commit_p99_ms", 0.0), "ms",
                                    "lower"),
            "committed": metric(res.get("committed", 0), "txns", "higher",
                                tier=count_tier),
            "conflicted": metric(res.get("conflicted", 0), "txns", "lower",
                                 tier=count_tier),
        }
        if "ops" in res:
            metrics["ops"] = metric(res["ops"], "ops", "higher",
                                    tier=count_tier)
        # columnar wire path (r12): the resolver role's copy/alloc
        # accounting is STRUCTURAL — path-determined ratios (copies
        # per batch, decode allocs per txn), deterministic regardless
        # of batching/timing — so the "two copies" claim is gated
        # exactly by perfcheck, not asserted in prose. Only present on
        # runs that report it (keeps the historical --import
        # byte-stable: PIPELINE_r0x rows predate the metric).
        if "resolve_copies_per_batch" in res:
            metrics["resolve_copies_per_batch"] = metric(
                res["resolve_copies_per_batch"], "copies", "lower",
                tier="structural",
            )
        if "resolve_decode_allocs_per_txn" in res:
            metrics["resolve_decode_allocs_per_txn"] = metric(
                res["resolve_decode_allocs_per_txn"], "allocs", "lower",
                tier="structural",
            )
        knobs = {
            "batch": row.get("batch"),
            "kernel_txns": row.get("kernel_txns"),
            "kernel": row.get("kernel", "tiered"),
        }
        if row.get("resolve_path"):
            # frame A/B knob: keys columnar and object rows apart in
            # the baseline fingerprint (absent on pre-r12 rows and
            # cluster-mode rows, so their keys are unchanged)
            knobs["resolve_path"] = row["resolve_path"]
        if row.get("knob_overrides"):
            # autotune trials drive server knobs through the env hook;
            # the applied overrides key each trial apart (absent on
            # every non-trial row — import byte-stability)
            knobs.update(row["knob_overrides"])
        recs.append(make_record(
            "bench_pipeline", metrics,
            workload={
                "spec": row.get("spec"),
                "mode": row.get("mode"),
                "inflight": row.get("inflight"),
                "ops_per_client": row.get("ops_per_client"),
                "records": row.get("records"),
                "resolver_backend": backend,
            },
            knobs=knobs,
            fingerprint=this_fp, imported_from=imported_from,
        ))
    return recs


def saturation_report_to_record(rep: dict, *, imported_from: str = None,
                                fingerprint: dict = None) -> dict:
    """testing/saturation report (one direction) -> ledger record.
    Everything is structural: the ramp runs on the deterministic
    virtual clock, so p99s and shed counts are exact per seed."""
    if fingerprint is None:
        fingerprint = {
            "backend": "cpu", "device_kind": None, "device_count": 0,
            "jax_version": None, "jaxlib_version": None,
            "python_version": None, "machine": None,
        }
    steps = rep.get("steps") or []
    worst_p99 = max((s.get("commit_p99_s", 0.0) for s in steps),
                    default=0.0)
    metrics = {
        "peak_goodput_tps": metric(rep.get("peak_goodput_tps", 0.0), "tps",
                                   "higher", tier="structural"),
        "worst_commit_p99_s": metric(worst_p99, "s", "lower",
                                     tier="structural"),
        "shed_total": metric(sum(s.get("shed", 0) for s in steps), "txns",
                             "lower", tier="structural"),
        "too_old_total": metric(
            sum(s.get("too_old", 0) for s in steps), "txns", "lower",
            tier="structural",
        ),
        "committed_total": metric(
            sum(s.get("committed", 0) for s in steps), "txns", "higher",
            tier="structural",
        ),
        "slo_passed": metric(
            int(bool((rep.get("slo") or {}).get("passed"))), "bool",
            # the OFF direction is SUPPOSED to violate; direction is
            # meaningful only per admission leg, encoded in workload
            "higher" if rep.get("admission") else "lower",
            tier="structural",
        ),
    }
    return make_record(
        "saturation", metrics,
        workload={
            "spec": rep.get("spec"),
            "seed": rep.get("seed"),
            "admission": bool(rep.get("admission")),
            "ramp": rep.get("ramp"),
            "step_seconds": rep.get("step_seconds"),
        },
        knobs=rep.get("config") or {},
        fingerprint=fingerprint, imported_from=imported_from,
    )


def hotspot_report_to_record(rep: dict, *, imported_from: str = None,
                             fingerprint: dict = None) -> dict:
    """testing/hotspot report (one leg) -> ledger record: the sampling
    overhead envelope. Only the SIM legs belong in the committed
    history — the byte sample is a pure function of (seed, key, size)
    and the tag counters run on the virtual clock, so every count here
    is structural (exact-compared by perfcheck). Wire legs use
    wall-entropy sampling seeds; ledger them only for local notes."""
    if fingerprint is None:
        fingerprint = {
            "backend": "cpu", "device_kind": None, "device_count": 0,
            "jax_version": None, "jaxlib_version": None,
            "python_version": None, "machine": None,
        }
    samp = rep.get("sampling") or {}
    skewed = rep.get("direction") == "zipf"
    metrics = {
        "sample_keys": metric(samp.get("sample_keys", 0), "keys", "lower",
                              tier="structural"),
        "sampled_bytes": metric(samp.get("sampled_bytes", 0), "bytes",
                                "lower", tier="structural"),
        "committed": metric(rep.get("committed", 0), "txns", "higher",
                            tier="structural"),
        # the verdict itself: the zipf leg is SUPPOSED to attribute,
        # the uniform leg is supposed to stay quiet — direction is
        # meaningful only per leg, encoded in workload
        "attributed": metric(
            int(bool((rep.get("attribution") or {}).get("attributed"))),
            "bool", "higher" if skewed else "lower", tier="structural",
        ),
    }
    for name, unit in (
        ("byte_sample_writes", "writes"),
        ("tag_counter_tags", "tags"),
        ("tag_notes", "notes"),
        ("tag_bytes_noted", "bytes"),
        ("resolver_key_sample_keys", "keys"),
    ):
        if name in samp:
            metrics[name] = metric(samp[name], unit, "lower",
                                   tier="structural")
    cfg = rep.get("config") or {}
    return make_record(
        "hotspot", metrics,
        workload={
            "spec": rep.get("spec", "hotspot"),
            "seed": rep.get("seed"),
            "path": rep.get("path"),
            "direction": rep.get("direction"),
            "txns": cfg.get("txns"),
            "value_bytes": cfg.get("value_bytes"),
        },
        knobs=cfg,
        fingerprint=fingerprint, imported_from=imported_from,
    )


def multichip_artifact_to_record(obj: dict, *, imported_from: str = None,
                                 fingerprint: dict = None) -> dict:
    """MULTICHIP_r0*.json (the 8-device lane's pass/fail artifact) ->
    ledger record."""
    if fingerprint is None:
        fingerprint = {
            "backend": "cpu", "device_kind": None,
            "device_count": obj.get("n_devices", 0),
            "jax_version": None, "jaxlib_version": None,
            "python_version": None, "machine": None,
        }
    metrics = {
        "ok": metric(int(bool(obj.get("ok"))), "bool", "higher",
                     tier="structural"),
        "rc": metric(obj.get("rc", 0), "code", "lower", tier="structural"),
        "skipped": metric(int(bool(obj.get("skipped"))), "bool", "lower",
                          tier="structural"),
    }
    return make_record(
        "multichip", metrics,
        workload={"n_devices": obj.get("n_devices", 0)},
        fingerprint=fingerprint, imported_from=imported_from,
    )

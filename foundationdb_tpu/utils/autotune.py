"""Ledger-driven knob autotuner: resumable search over the bench knob
space, every trial a fingerprint-keyed EXPERIMENT row in the perf
ledger.

The closed loop the ROADMAP's "self-driving performance" item asks for:

* the SEARCH SPACE is an ordered {knob: (values...)} grid
  (`SearchSpace`) — BENCH_FUSE, the adaptive-batch targets,
  `dedup_reads` vs `range_sweep`, `compact_interval`, `delta_capacity`,
  `n_shards` — walked in a deterministic order so a resumed search
  replays the same trial sequence;
* each TRIAL runs one of the existing harnesses (bench.py /
  scripts/bench_pipeline.py, driven as subprocesses through their env
  knobs + `--perf-ledger`) and lands the emitted row in the search
  ledger with `experiment: <search id>` stamped — utils/perf.py
  excludes experiment rows from every baseline window, so trials can
  NEVER pollute the perfcheck gate;
* the ledger IS the resumability cache: before running a trial the
  searcher scans the ledger for a row with the same (experiment,
  trial_key) and reuses its objective — killing a sweep mid-run and
  re-running completes only the missing trials, across hardware
  sessions (the fingerprint travels in the row, so a v5e trial is
  never confused with a CPU-host trial: `cache_scope="device"`
  restricts hits to matching device fingerprints);
* the STOPPING RULE is roofline distance: with the row's recorded
  `hlo_cost` (bytes accessed / FLOPs per dispatch) and the device's
  peak numbers, `roofline_txn_s` bounds the achievable rate; the
  search stops early once the best trial achieves `roofline_frac` of
  it (default 0.5 — past that, knob search is chasing the compiler).
  Hosts without a known peak (CPU fingerprints) fall back to
  exhaustion / no-improvement stopping, honestly reported;
* the WINNER is promoted by re-emitting its row WITHOUT the
  experiment field (`promote_record`) and handing it to
  `scripts/perfcheck.py --check --accept` — the committed-baseline
  flow, unchanged.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Optional, Sequence

from foundationdb_tpu.utils import perf
from foundationdb_tpu.utils.probes import code_probe, declare

declare("autotune.cache_hit", "autotune.roofline_stop")

#: peak memory bandwidth (bytes/s) by device kind — the roofline's
#: denominator (the resolver kernels are memory-bound scans, so the
#: bytes-accessed bound is the binding one; FLOPs peaks would only
#: loosen it). Unlisted kinds (CPU hosts included: XLA:CPU reports no
#: stable peak) disable the roofline stopping rule.
DEVICE_PEAK_BYTES_S = {
    "TPU v4": 1.2e12,
    "TPU v5 lite": 8.19e11,
    "TPU v5e": 8.19e11,
    "TPU v5p": 2.765e12,
    "TPU v6 lite": 1.64e12,
}


def roofline_txn_s(hlo_cost: dict, fingerprint: dict,
                   txns_per_dispatch: int) -> Optional[float]:
    """The bytes-bound roofline rate for one compiled resolver dispatch:
    txns_per_dispatch / (bytes_accessed / peak_bytes_s). None when the
    cost model or the device peak is unavailable — callers treat None
    as 'no roofline', never as zero."""
    if not hlo_cost or txns_per_dispatch <= 0:
        return None
    bytes_accessed = hlo_cost.get("bytes_accessed")
    peak = DEVICE_PEAK_BYTES_S.get((fingerprint or {}).get("device_kind"))
    if not bytes_accessed or not peak:
        return None
    seconds = float(bytes_accessed) / float(peak)
    if seconds <= 0:
        return None
    return txns_per_dispatch / seconds


class SearchSpace:
    """An ordered knob grid. Deterministic enumeration order (insertion
    order of `knobs`, values left to right, last knob fastest) so a
    resumed search replays the identical trial sequence and the
    fingerprint cache lines up."""

    def __init__(self, knobs: dict[str, Sequence]):
        if not knobs or not all(len(v) > 0 for v in knobs.values()):
            raise ValueError("every knob needs at least one value")
        self.knobs = {k: tuple(v) for k, v in knobs.items()}

    def __len__(self) -> int:
        n = 1
        for v in self.knobs.values():
            n *= len(v)
        return n

    def points(self) -> list[dict]:
        out: list[dict] = [{}]
        for name, values in self.knobs.items():
            out = [{**p, name: v} for p in out for v in values]
        return out


def trial_key(knobs: dict) -> str:
    """The canonical identity of one grid point — what the ledger cache
    matches on (sorted-key JSON, so dict order can't split the cache)."""
    return json.dumps(knobs, sort_keys=True)


@dataclasses.dataclass
class Trial:
    knobs: dict
    objective: Optional[float]  # direction-normalized: HIGHER is better
    record: Optional[dict]      # the ledger row (None: harness failed)
    cached: bool
    error: Optional[str] = None


def _cache_fp_key(rec: dict) -> tuple:
    fp = rec.get("fingerprint") or {}
    return tuple(fp.get(k) for k in perf.HARDWARE_FP_KEYS)


def find_cached(history: list[dict], *, experiment: str, key: str,
                cache_scope: str = "any",
                fingerprint: dict = None) -> Optional[dict]:
    """The resumability lookup: the most recent ledger row carrying
    this search's experiment id and this trial's key. `cache_scope=
    "device"` additionally requires the row's device fingerprint to
    match `fingerprint` (hardware objectives must not resume from a
    different machine's trials; structural objectives may)."""
    want_fp = None
    if cache_scope == "device":
        want_fp = tuple(
            (fingerprint or {}).get(k) for k in perf.HARDWARE_FP_KEYS
        )
    for rec in reversed(history):
        if rec.get("experiment") != experiment:
            continue
        if ((rec.get("extra") or {}).get("trial_key")) != key:
            continue
        if want_fp is not None and _cache_fp_key(rec) != want_fp:
            continue
        return rec
    return None


def objective_of(rec: dict, metric: str) -> Optional[float]:
    """Direction-normalized objective from one ledger row: the metric's
    value, negated when its declared direction is "lower" — the search
    maximizes unconditionally."""
    m = (rec.get("metrics") or {}).get(metric)
    if m is None:
        return None
    v = float(m["value"])
    return v if m.get("direction") == "higher" else -v


def promote_record(rec: dict) -> dict:
    """The winner, stripped of its experiment marker (and trial-key
    extra) so `perfcheck --check --accept` can admit it as a committed
    baseline row. Everything else — fingerprint, workload, knobs,
    metrics — is the trial's own measurement."""
    out = {k: v for k, v in rec.items() if k != "experiment"}
    extra = {k: v for k, v in (out.get("extra") or {}).items()
             if k != "trial_key"}
    if extra:
        out["extra"] = extra
    else:
        out.pop("extra", None)
    perf.validate_record(out)
    return out


@dataclasses.dataclass
class SearchReport:
    experiment: str
    trials: list[Trial]
    best: Optional[Trial]
    stopped: str                  # "roofline" | "exhausted" | "no_improve"
    cache_hits: int
    ran: int
    roofline: Optional[float] = None
    roofline_frac_achieved: Optional[float] = None

    def as_dict(self) -> dict:
        return {
            "experiment": self.experiment,
            "n_trials": len(self.trials),
            "cache_hits": self.cache_hits,
            "ran": self.ran,
            "stopped": self.stopped,
            "best_knobs": self.best.knobs if self.best else None,
            "best_objective": self.best.objective if self.best else None,
            "roofline": self.roofline,
            "roofline_frac_achieved": self.roofline_frac_achieved,
        }


def run_search(
    experiment: str,
    space: SearchSpace,
    run_trial: Callable[[dict], dict],
    *,
    objective_metric: str,
    ledger: str,
    cache_scope: str = "any",
    roofline_frac: float = 0.5,
    roofline_txns_per_dispatch: int = 0,
    no_improve_limit: int = 0,
    log: Callable[[str], None] = None,
) -> SearchReport:
    """Walk the grid; each point either resumes from the ledger cache
    or runs `run_trial(knobs)` (returns a schema row WITHOUT the
    experiment stamp — this function stamps experiment + trial_key and
    appends it to `ledger`).

    Stopping, in precedence order: (1) roofline — when the device peak
    and the best row's `hlo_cost` extra are both known and the best
    achieved rate reaches `roofline_frac` of `roofline_txn_s`;
    (2) no_improve_limit consecutive non-improving trials (0 = off);
    (3) grid exhaustion. A failed trial records error and continues —
    one bad knob point must not kill a resumable sweep."""
    log = log or (lambda *_: None)
    trials: list[Trial] = []
    best: Optional[Trial] = None
    cache_hits = ran = since_improve = 0
    stopped = "exhausted"
    roofline = frac = None
    # only a device-scoped cache compares fingerprints; take it in a
    # child, so this parent never holds the chip its trials need
    fingerprint = (
        perf.device_fingerprint_from_child() if cache_scope == "device"
        else None
    )
    history = perf.load_history(ledger)
    for knobs in space.points():
        key = trial_key(knobs)
        rec = find_cached(history, experiment=experiment, key=key,
                          cache_scope=cache_scope, fingerprint=fingerprint)
        cached = rec is not None
        err = None
        if cached:
            cache_hits += 1
            code_probe(True, "autotune.cache_hit")
            log(f"[cache] {key}")
        else:
            try:
                rec = run_trial(dict(knobs))
            except Exception as e:  # noqa: BLE001 — recorded, not fatal
                rec, err = None, f"{type(e).__name__}: {e}"
                log(f"[fail]  {key}: {err}")
            if rec is not None:
                rec = dict(rec)
                rec["experiment"] = experiment
                rec.setdefault("extra", {})
                rec["extra"] = {**rec["extra"], "trial_key": key}
                perf.append(rec, path=ledger)
                history.append(rec)
                ran += 1
                log(f"[trial] {key}")
        obj = objective_of(rec, objective_metric) if rec else None
        t = Trial(knobs=knobs, objective=obj, record=rec, cached=cached,
                  error=err)
        trials.append(t)
        if obj is not None and (best is None or obj > best.objective):
            best, since_improve = t, 0
        else:
            since_improve += 1
        # roofline stop: achieved rate (the objective metric must be a
        # higher-is-better rate for this to be meaningful; callers pass
        # roofline_txns_per_dispatch=0 to disable) vs the bytes-bound
        # ceiling from the winner's recorded HLO cost
        if (best is not None and roofline_txns_per_dispatch > 0
                and best.record is not None):
            hlo = dict(
                (best.record.get("extra") or {}).get("hlo_cost") or {}
            )
            if "bytes_accessed" not in hlo:
                # bench rows carry the cost model as metrics
                # (kernel_bytes_accessed, hardware tier)
                m = (best.record.get("metrics") or {}).get(
                    "kernel_bytes_accessed"
                )
                if m is not None:
                    hlo["bytes_accessed"] = float(m["value"])
            roofline = roofline_txn_s(
                hlo, best.record.get("fingerprint"),
                roofline_txns_per_dispatch,
            )
            if roofline:
                frac = best.objective / roofline
                if frac >= roofline_frac:
                    stopped = "roofline"
                    code_probe(True, "autotune.roofline_stop")
                    break
        if no_improve_limit and since_improve >= no_improve_limit:
            stopped = "no_improve"
            break
    return SearchReport(
        experiment=experiment, trials=trials, best=best, stopped=stopped,
        cache_hits=cache_hits, ran=ran, roofline=roofline,
        roofline_frac_achieved=frac,
    )

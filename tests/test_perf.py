"""The unified perf ledger (ISSUE 10): schema round-trip, fingerprint
matching, the noise-aware comparator BOTH directions, the historical
--import migration's byte stability, and every CLI's emit path.

The contract under test: all four perf CLIs emit schema-valid rows into
one ledger; scripts/perfcheck.py passes an unmodified tree against the
imported history and FAILS on an injected structural regression — the
check.sh lane's exit-code behavior, demonstrated here without the
15-second kernel_smoke run.
"""

import json
import os
import subprocess
import sys

import pytest

from foundationdb_tpu.utils import perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERFCHECK = os.path.join(REPO, "scripts", "perfcheck.py")


def _fp(**over):
    fp = {
        "backend": "cpu", "device_kind": "cpu", "device_count": 1,
        "jax_version": "0.0", "jaxlib_version": "0.0",
        "python_version": "3", "machine": "x",
    }
    fp.update(over)
    return fp


def _rec(value, *, source="t", tier="hardware", direction="higher",
         name="txn_s", fp=None, workload=None, knobs=None):
    return perf.make_record(
        source, {name: perf.metric(value, "txn/s", direction, tier=tier)},
        workload=workload or {"shape": 1}, knobs=knobs or {"k": 1},
        fingerprint=fp or _fp(), git_sha="deadbeef", timestamp=0.0,
    )


# ---------------------------------------------------------------------------
# Schema round-trip + validation.


def test_record_roundtrip_through_ledger(tmp_path):
    path = str(tmp_path / "history.jsonl")
    rec = _rec(100.0)
    perf.append(rec, path=path)
    back = perf.load_history(path)
    assert back == [rec]
    perf.validate_record(back[0])
    # the full fingerprint field set is present (the satellite fix:
    # backend alone cannot distinguish CPU-host from v5e rows)
    for key in ("backend", "device_kind", "device_count", "jax_version",
                "jaxlib_version"):
        assert key in back[0]["fingerprint"]


def test_device_fingerprint_live():
    fp = perf.device_fingerprint()
    assert fp["backend"] == "cpu"
    assert fp["device_count"] >= 1
    assert fp["jaxlib_version"]


@pytest.mark.parametrize("mutate, frag", [
    (lambda r: r["metrics"]["txn_s"].update(direction="sideways"),
     "direction"),
    (lambda r: r["metrics"]["txn_s"].update(tier="vibes"), "tier"),
    (lambda r: r["metrics"]["txn_s"].pop("unit"), "unit"),
    (lambda r: r["metrics"]["txn_s"].update(value="fast"), "number"),
    (lambda r: r.update(schema_version=99), "schema_version"),
    (lambda r: r.update(metrics={}), "metrics"),
    (lambda r: r["fingerprint"].pop("device_kind"), "device_kind"),
])
def test_validate_rejects_malformed(mutate, frag):
    rec = _rec(1.0)
    mutate(rec)
    with pytest.raises(ValueError, match=frag):
        perf.validate_record(rec)


def test_append_refuses_invalid(tmp_path):
    rec = _rec(1.0)
    rec["metrics"]["txn_s"]["direction"] = "bogus"
    with pytest.raises(ValueError):
        perf.append(rec, path=str(tmp_path / "h.jsonl"))
    assert not (tmp_path / "h.jsonl").exists()


def test_load_history_strict_on_corruption(tmp_path):
    path = tmp_path / "h.jsonl"
    path.write_text('{"ok": 1}\nnot json\n')
    with pytest.raises(ValueError, match="malformed"):
        perf.load_history(str(path))


def test_emit_honors_ledger_env(tmp_path, monkeypatch):
    path = str(tmp_path / "redirect.jsonl")
    monkeypatch.setenv("FDBTPU_PERF_LEDGER", path)
    rec = perf.emit("t", {"m": perf.metric(1, "count", "higher")})
    assert perf.load_history(path) == [rec]
    assert rec["timestamp"] is not None and rec["git_sha"]


# ---------------------------------------------------------------------------
# Fingerprint matching + baseline selection.


def test_hardware_baseline_ignores_mismatched_fingerprints():
    cand = _rec(100.0)
    same = _rec(90.0)
    other_dev = _rec(10.0, fp=_fp(device_kind="TPU v5e", backend="tpu"))
    other_jaxlib = _rec(10.0, fp=_fp(jaxlib_version="9.9"))
    other_workload = _rec(10.0, workload={"shape": 2})
    other_knobs = _rec(10.0, knobs={"k": 2})
    win = perf.baseline_window(
        [same, other_dev, other_jaxlib, other_workload, other_knobs],
        cand, tier="hardware",
    )
    assert win == [same]
    # structural matching crosses hosts (deterministic values) but
    # still keys on workload + knobs
    win_s = perf.baseline_window(
        [same, other_dev, other_jaxlib, other_workload, other_knobs],
        cand, tier="structural",
    )
    assert win_s == [same, other_dev, other_jaxlib]


def test_comparator_skips_mismatched_rows_entirely():
    """A regressed candidate PASSES when the only history rows carry a
    different fingerprint — wrong-host baselines must never gate."""
    cand = _rec(10.0)
    foreign = _rec(1000.0, fp=_fp(device_kind="TPU v5e", backend="tpu"))
    rep = perf.compare(cand, [foreign], tier="hardware")
    assert rep["baseline_rows"] == 0
    assert rep["metrics"]["txn_s"]["status"] == "new"
    assert rep["regressions"] == []


# ---------------------------------------------------------------------------
# The comparator, both directions.


def test_within_band_noise_passes():
    base = [_rec(v) for v in (95.0, 100.0, 103.0, 98.0, 101.0)]
    rep = perf.compare(_rec(93.0), base, tier="hardware")
    assert rep["metrics"]["txn_s"]["status"] == "ok"
    assert rep["regressions"] == []


def test_regression_outside_band_fails_higher_is_better():
    base = [_rec(v) for v in (95.0, 100.0, 103.0, 98.0, 101.0)]
    rep = perf.compare(_rec(50.0), base, tier="hardware")
    assert rep["metrics"]["txn_s"]["status"] == "regression"
    assert rep["regressions"] == ["txn_s"]


def test_regression_lower_is_better_direction():
    base = [_rec(v, direction="lower", name="p99_ms")
            for v in (10.0, 11.0, 10.5)]
    ok = perf.compare(_rec(10.4, direction="lower", name="p99_ms"),
                      base, tier="hardware")
    assert ok["regressions"] == []
    bad = perf.compare(_rec(30.0, direction="lower", name="p99_ms"),
                       base, tier="hardware")
    assert bad["regressions"] == ["p99_ms"]
    # an IMPROVEMENT (p99 down) never fails
    better = perf.compare(_rec(2.0, direction="lower", name="p99_ms"),
                          base, tier="hardware")
    assert better["metrics"]["p99_ms"]["status"] == "improved"
    assert better["regressions"] == []


def test_structural_tier_is_exact():
    """Structural values are deterministic: MAD 0, floor 0 — a doubled
    merge-row count fails even though it is 'only' 2x, and an
    identical value passes."""
    base = [_rec(121396, tier="structural", direction="lower",
                 name="merge_rows") for _ in range(3)]
    same = perf.compare(
        _rec(121396, tier="structural", direction="lower",
             name="merge_rows"), base, tier="structural")
    assert same["regressions"] == []
    doubled = perf.compare(
        _rec(242792, tier="structural", direction="lower",
             name="merge_rows"), base, tier="structural")
    assert doubled["regressions"] == ["merge_rows"]
    # structural compares cross-host: candidate from another machine
    cross = perf.compare(
        _rec(242792, tier="structural", direction="lower",
             name="merge_rows", fp=_fp(machine="arm64")),
        base, tier="structural")
    assert cross["regressions"] == ["merge_rows"]


def test_compare_only_reads_requested_tier():
    rec = perf.make_record(
        "t",
        {
            "rate": perf.metric(10.0, "txn/s", "higher", tier="hardware"),
            "rows": perf.metric(5, "rows", "lower", tier="structural"),
        },
        workload={"shape": 1}, knobs={}, fingerprint=_fp(),
        git_sha="d", timestamp=0.0,
    )
    base = json.loads(json.dumps(rec))
    base["metrics"]["rate"]["value"] = 1000.0  # hardware-tier collapse
    rep = perf.compare(rec, [base], tier="structural")
    assert set(rep["metrics"]) == {"rows"}
    assert rep["regressions"] == []


# ---------------------------------------------------------------------------
# --import: the historical-artifact migration.


def _perfcheck(*args, env=None):
    e = {**os.environ, "JAX_PLATFORMS": "cpu"}
    if env:
        e.update(env)
    return subprocess.run(
        [sys.executable, PERFCHECK, *args],
        capture_output=True, text=True, env=e, timeout=120,
    )


def test_import_is_byte_stable_and_reproduces_history(tmp_path):
    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    r1 = _perfcheck("--import", "--history", a)
    r2 = _perfcheck("--import", "--history", b)
    assert r1.returncode == 0 and r2.returncode == 0, (r1.stderr, r2.stderr)
    assert open(a, "rb").read() == open(b, "rb").read()
    rows = perf.load_history(a)
    assert rows, "import produced no rows"
    for rec in rows:
        perf.validate_record(rec)
        assert rec["imported_from"]
        assert rec["timestamp"] is None  # byte-stability contract
    by_src = {r["source"] for r in rows}
    assert {"bench", "bench_pipeline", "saturation", "multichip"} <= by_src
    # spot-check: BENCH_r06's primary value survives the migration
    r06 = [r for r in rows if r.get("imported_from") == "BENCH_r06.json"]
    assert len(r06) == 1
    assert r06[0]["metrics"]["txn_s"]["value"] == pytest.approx(26437.6)
    assert r06[0]["metrics"]["merge_rows_tiered_live"]["value"] == 121396
    assert r06[0]["metrics"]["merge_rows_tiered_live"]["tier"] == (
        "structural"
    )
    # SATURATION_r08: one row per admission direction, structural tier
    sat = [r for r in rows if r["source"] == "saturation"]
    assert {r["workload"]["admission"] for r in sat} == {True, False}
    # re-import refuses without --force (double-append protection)
    r3 = _perfcheck("--import", "--history", a)
    assert r3.returncode == 1 and "--force" in r3.stderr


def test_committed_ledger_matches_reimport(tmp_path):
    """perf/history.jsonl's imported rows are EXACTLY what --import
    produces from the root artifacts today — the committed ledger
    cannot drift from its source artifacts."""
    fresh = str(tmp_path / "fresh.jsonl")
    assert _perfcheck("--import", "--history", fresh).returncode == 0
    committed = [
        r for r in perf.load_history(
            os.path.join(REPO, "perf", "history.jsonl"))
        if r.get("imported_from")
    ]
    assert committed == perf.load_history(fresh)


# ---------------------------------------------------------------------------
# The perfcheck CLI gate, both directions (the check.sh lane's
# exit-code contract).


def test_perfcheck_cli_passes_clean_and_fails_injected(tmp_path):
    hist = str(tmp_path / "history.jsonl")
    cand_path = str(tmp_path / "cand.jsonl")
    base = _rec(100, tier="structural", direction="lower", name="rows",
                source="kernel_smoke")
    perf.append(base, path=hist)
    # clean candidate: identical structural value -> exit 0
    perf.append(base, path=cand_path)
    r = _perfcheck("--check", cand_path, "--tier", "structural",
                   "--history", hist)
    assert r.returncode == 0, r.stderr
    assert "perfcheck ok" in r.stdout
    # injected regression: doubled rows -> exit 1
    bad_path = str(tmp_path / "bad.jsonl")
    perf.append(
        _rec(200, tier="structural", direction="lower", name="rows",
             source="kernel_smoke"), path=bad_path)
    r = _perfcheck("--check", bad_path, "--tier", "structural",
                   "--history", hist)
    assert r.returncode == 1
    assert "REGRESSED" in r.stderr


def test_perfcheck_unmodified_tree_passes_committed_history(tmp_path):
    """The acceptance pin: a kernel_smoke-shaped candidate REPLAYED
    from the committed ledger passes against that ledger (an
    unmodified tree is green), and the same candidate with one
    structural metric doubled fails."""
    committed = os.path.join(REPO, "perf", "history.jsonl")
    rows = [r for r in perf.load_history(committed)
            if r["source"] == "kernel_smoke"]
    assert rows, "committed ledger must hold a kernel_smoke baseline row"
    cand = json.loads(json.dumps(rows[-1]))
    cand_path = str(tmp_path / "cand.jsonl")
    perf.append(cand, path=cand_path)
    r = _perfcheck("--check", cand_path, "--tier", "structural",
                   "--history", committed)
    assert r.returncode == 0, (r.stdout, r.stderr)
    # inject: doubled merge-row capacity
    cand["metrics"]["merge_rows_tiered_cap"]["value"] *= 2
    bad_path = str(tmp_path / "bad.jsonl")
    perf.append(cand, path=bad_path)
    r = _perfcheck("--check", bad_path, "--tier", "structural",
                   "--history", committed)
    assert r.returncode == 1
    assert "merge_rows_tiered_cap" in r.stderr


def test_perfcheck_accept_appends_passing_candidate(tmp_path):
    hist = str(tmp_path / "history.jsonl")
    cand_path = str(tmp_path / "cand.jsonl")
    rec = _rec(7, tier="structural", direction="lower", name="rows")
    perf.append(rec, path=cand_path)
    r = _perfcheck("--check", cand_path, "--history", hist, "--accept")
    assert r.returncode == 0, r.stderr
    assert perf.load_history(hist) == [rec]


# ---------------------------------------------------------------------------
# Emitter converters: the four CLIs' row shapes.


def test_bench_row_converter_full_fingerprint():
    row = {
        "metric": "resolver_txns_per_sec_8k_batch", "value": 26437.6,
        "vs_baseline": 0.08, "baseline_txns_per_sec": 330626.7,
        "p50_ms": 301.0, "p99_ms": 496.4, "staging": "pipelined",
        "backend": "cpu", "kernel": "tiered", "delta_capacity": 98304,
        "dedup_reads": 0, "compact_interval": 8, "fused_dispatch": 8,
        "batches": 16, "device_resident_txn_s": 27940.6,
        "ablation": {"merge_rows_tiered_per_batch_live": 121396,
                     "pack_ms_per_group": 1.8},
        "compile_cache": {"misses": 3, "backend_compiles": 28},
        "hlo_cost": {"flops": 1e9, "bytes_accessed": 2e8},
    }
    rec = perf.bench_row_to_record(row, fingerprint=_fp())
    perf.validate_record(rec)
    m = rec["metrics"]
    assert m["txn_s"]["value"] == pytest.approx(26437.6)
    assert m["merge_rows_tiered_live"]["tier"] == "structural"
    # HLO cost numbers vary with backend/jaxlib -> hardware tier
    assert m["kernel_flops"]["tier"] == "hardware"
    assert m["compile_cache_misses"]["value"] == 3
    # compile counters depend on persistent-cache warmth (a hit skips
    # the backend compile entirely) -> hardware tier, informational:
    # a cold first run on a fresh clone must not fail the exact gate
    assert m["compile_count"] == {
        "value": 28, "unit": "count", "direction": "lower",
        "tier": "hardware",
    }
    assert m["compile_cache_misses"]["tier"] == "hardware"
    assert rec["fingerprint"]["device_kind"] == "cpu"
    assert rec["knobs"]["kernel"] == "tiered"


def test_pipeline_converter_tiers_by_mode():
    row = {
        "metric": "pipeline_commit_txn_s", "spec": "config5_ycsb_a",
        "mode": "wire", "inflight": 64, "ops_per_client": 2,
        "records": 100, "batch": 64, "kernel_txns": 64,
        "kernel": "tiered",
        "backends": {"native": {
            "txn_s": 100.0, "commit_p50_ms": 1.0, "commit_p99_ms": 2.0,
            "committed": 50, "conflicted": 5, "ops": 90,
        }},
    }
    wire = perf.pipeline_row_to_records(row)[0]
    perf.validate_record(wire)
    # wire retry counts ride real asyncio timing: hardware tier
    assert wire["metrics"]["committed"]["tier"] == "hardware"
    row["mode"] = "cluster"
    cluster = perf.pipeline_row_to_records(row)[0]
    # virtual-clock sim counts are deterministic: structural tier
    assert cluster["metrics"]["committed"]["tier"] == "structural"
    assert cluster["workload"]["resolver_backend"] == "native"


def test_saturation_converter_is_structural():
    rep = json.loads(open(os.path.join(REPO, "SATURATION_r08.json"))
                     .readline())
    rec = perf.saturation_report_to_record(rep, fingerprint=_fp())
    perf.validate_record(rec)
    assert all(m["tier"] == "structural"
               for m in rec["metrics"].values())
    assert rec["metrics"]["peak_goodput_tps"]["value"] == pytest.approx(
        221.0)
    assert rec["workload"]["admission"] is True


def test_soak_emitter_and_signature_metrics(tmp_path, monkeypatch):
    from foundationdb_tpu.testing.soak import signature_metrics

    sig = (7, 12, 3, 40, 1.25, 2, ("a",), None, "ff00", 9)
    sm = signature_metrics(sig)
    assert sm["committed"] == 12 and sm["aborted"] == 3
    assert sm["trace_digest"] == "ff00" and sm["traced_commits"] == 9
    short = signature_metrics(sig[:8])
    assert "traced_commits" not in short

    path = str(tmp_path / "soak.jsonl")
    monkeypatch.setenv("FDBTPU_PERF_LEDGER", path)
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "soak_cli", os.path.join(REPO, "scripts", "soak.py"))
        soak_cli = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(soak_cli)
        soak_cli._emit_perf_row(
            "default", [0, 1, 2], 1,
            {"committed": 30, "aborted": 2, "read_checks": 99,
             "api_acked": 4},
            17,
        )
    finally:
        sys.path.pop(0)
    rows = perf.load_history(path)
    assert len(rows) == 1
    assert rows[0]["source"] == "soak"
    assert rows[0]["metrics"]["committed"]["tier"] == "structural"
    assert rows[0]["metrics"]["traced_commits"]["value"] == 17
    assert rows[0]["workload"] == {
        "spec": "default", "seeds": [0, 2], "n_seeds": 3, "perturb": 1,
    }


# ---------------------------------------------------------------------------
# Profiling hooks.


def test_profile_trace_noop_without_dir():
    with perf.profile_trace(None):
        pass
    with perf.profile_trace(""):
        pass


def test_profile_trace_captures(tmp_path):
    import jax.numpy as jnp

    d = str(tmp_path / "prof")
    with perf.profile_trace(d):
        jnp.ones((4,)).sum().block_until_ready()
    captured = []
    for root, _dirs, files in os.walk(d):
        captured.extend(files)
    assert captured, "profiler trace produced no files"


def test_device_memory_stats_shape():
    stats = perf.device_memory_stats()
    # XLA:CPU reports nothing — the contract is 'empty dict, no error';
    # any reporting backend returns normalized int fields
    for v in stats.values():
        assert isinstance(v, int)


def test_cost_analysis_of_jitted():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: (x * 2.0).sum())
    cost = perf.cost_analysis_of(fn, jnp.ones((16, 16)))
    assert cost.get("flops", 0) > 0
    assert cost.get("bytes_accessed", 0) > 0
    # failure path: a non-jitted object degrades to {}
    assert perf.cost_analysis_of(object()) == {}


def test_compile_cache_stats_surface(tmp_path, monkeypatch):
    from foundationdb_tpu.models.conflict_base import KernelStageMetrics
    from foundationdb_tpu.utils import compile_cache

    compile_cache.record_compile("sig/test", 1.25)
    st = compile_cache.stats()
    assert st["per_signature_compile_seconds"]["sig/test"] == 1.25
    before = st["cache_misses"]
    compile_cache._on_event(compile_cache._MISS_EVENT)
    compile_cache._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.5)
    st2 = compile_cache.stats()
    assert st2["cache_misses"] == before + 1
    assert st2["last_compile_seconds"] == 0.5
    # the qos surface fdbtop renders (the kernel panel fields)
    qos = KernelStageMetrics().qos()
    for key in ("compile_cache_hits", "compile_cache_misses",
                "last_compile_seconds", "stage_p99_seconds",
                "device_bytes_in_use", "device_peak_bytes"):
        assert key in qos


# ---------------------------------------------------------------------------
# ISSUE 11: the per-chip scaling view + the compile-cache host scrub.


def _scaling_row(n_devices: int, txn_s: float, committed: int = 10):
    return perf.make_record(
        "multichip",
        {
            "committed": perf.metric(committed, "txns", "higher",
                                     tier="structural"),
            "txn_s": perf.metric(txn_s, "txn/s", "higher"),
        },
        workload={"n_devices": n_devices, "kernel": "tiered_sharded",
                  "batches": 8, "txns_per_batch": 12},
        knobs={"delta_capacity": 128},
        fingerprint={
            "backend": "cpu", "device_kind": "cpu", "device_count": 8,
            "jax_version": "x", "jaxlib_version": "y",
            "python_version": "z", "machine": "m",
        },
    )


def test_perfcheck_scaling_renders_curve(tmp_path):
    """--scaling groups txn_s rows by device count at a fixed
    fingerprint and prints txn/s per device + efficiency vs the
    smallest width."""
    hist = str(tmp_path / "hist.jsonl")
    for n, rate in ((1, 1000.0), (2, 1800.0), (4, 3000.0), (8, 4400.0)):
        perf.append(_scaling_row(n, rate), path=hist)
    r = _perfcheck("--scaling", "--history", hist)
    assert r.returncode == 0, r.stderr
    out = r.stdout
    assert "efficiency" in out
    for n in (1, 2, 4, 8):
        assert f"{n} device(s)" in out
    # efficiency vs the 1-chip row: 2 devices at 1800 -> 0.90
    assert "efficiency  0.90" in out
    # a single-width-only ledger renders the empty-state hint
    hist2 = str(tmp_path / "hist2.jsonl")
    perf.append(_scaling_row(8, 4400.0), path=hist2)
    r2 = _perfcheck("--scaling", "--history", hist2)
    assert r2.returncode == 0
    assert "no ledger group" in r2.stdout


def test_perfcheck_scaling_splits_on_knobs(tmp_path):
    """A knob change is a different experiment: rows with different
    knob fingerprints must land in different scaling groups."""
    hist = str(tmp_path / "hist.jsonl")
    perf.append(_scaling_row(1, 1000.0), path=hist)
    perf.append(_scaling_row(2, 1800.0), path=hist)
    other = _scaling_row(2, 900.0)
    other["knobs"] = {"delta_capacity": 512}
    perf.append(other, path=hist)
    r = _perfcheck("--scaling", "--history", hist)
    assert r.returncode == 0, r.stderr
    # only the delta_capacity=128 group spans two widths; the 512 row
    # alone cannot form a curve
    assert r.stdout.count("==") >= 1
    assert '"delta_capacity": 512' not in r.stdout


def test_compile_cache_scrub_on_host_mismatch(tmp_path, monkeypatch):
    """A persistent-cache dir stamped by a DIFFERENT host — or holding
    entries with NO stamp at all (a container baked before the marker
    existed: it cannot be proven local) — is scrubbed, so stale
    XLA:CPU AOT entries never load (the MULTICHIP_r05 stderr-pollution
    fix); a dir stamped by THIS host is left alone; an EMPTY unstamped
    dir is just stamped."""
    from foundationdb_tpu.utils import compile_cache as cc

    d = tmp_path / "cache"
    d.mkdir()
    marker = d / "HOST_FINGERPRINT"
    # empty unstamped dir: stamp, nothing to scrub
    assert cc.scrub_on_host_mismatch(str(d)) is False
    assert marker.read_text().strip() == cc.host_fingerprint()
    # this host's stamp: untouched
    (d / "entry_a").write_bytes(b"aot blob")
    assert cc.scrub_on_host_mismatch(str(d)) is False
    assert (d / "entry_a").exists()
    # unstamped (legacy/pre-marker) dir WITH entries: provenance
    # unknown -> conservative scrub + stamp
    marker.unlink()
    assert cc.scrub_on_host_mismatch(str(d)) is True
    assert not (d / "entry_a").exists()
    assert marker.read_text().strip() == cc.host_fingerprint()
    # another host's stamp: entries scrubbed, marker re-stamped
    (d / "entry_a").write_bytes(b"aot blob")
    (d / "subdir").mkdir()
    (d / "subdir" / "entry_b").write_bytes(b"aot blob 2")
    marker.write_text("0" * 32 + "\n")
    assert cc.scrub_on_host_mismatch(str(d)) is True
    assert not (d / "entry_a").exists()
    assert not (d / "subdir").exists()
    assert marker.read_text().strip() == cc.host_fingerprint()
    # enable() routes through the scrub and still configures the cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    marker.write_text("0" * 32 + "\n")
    (d / "entry_c").write_bytes(b"stale")
    path = cc.enable(str(d))
    assert path == str(d)
    assert not (d / "entry_c").exists()

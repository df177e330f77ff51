"""Metrics-layer contracts: LatencySample's relative-error guarantee,
TraceLog sink rolling (memory AND file), and the reference-style
LatencyBands — the pieces the telemetry pipeline (ISSUE 5) leans on,
previously untested."""

import json
import math

import numpy as np
import pytest

from foundationdb_tpu.utils.metrics import (
    COMMIT_LATENCY_BANDS,
    CounterCollection,
    LatencyBands,
    LatencySample,
)
from foundationdb_tpu.utils.trace import (
    SEV_DEBUG,
    SEV_INFO,
    TraceBatch,
    TraceEvent,
    TraceLog,
)

# -- LatencySample: the DDSketch relative-error contract --------------------


def _check_quantiles(samples, eps):
    """Estimated p50/p95/p99 must sit within the sketch's relative-error
    band of the EXACT empirical quantiles. The sketch guarantees every
    recorded value lands in a bucket whose midpoint is within eps of it
    (gamma = (1+eps)/(1-eps)); rank arithmetic differences add at most
    one bucket, so 3*eps is the honest tolerance."""
    s = LatencySample("t", eps=eps)
    for v in samples:
        s.sample(float(v))
    arr = np.sort(np.asarray(samples, dtype=float))
    for q in (0.50, 0.95, 0.99):
        exact = float(arr[min(len(arr) - 1, int(q * (len(arr) - 1)))])
        est = s.quantile(q)
        assert est == pytest.approx(exact, rel=3 * eps), (
            f"q={q}: est {est} vs exact {exact} (eps={eps})"
        )


@pytest.mark.parametrize("eps", [0.01, 0.05])
def test_latency_sample_uniform_distribution(eps):
    rng = np.random.default_rng(7)
    _check_quantiles(rng.uniform(0.001, 2.0, size=20_000), eps)


@pytest.mark.parametrize("eps", [0.01, 0.05])
def test_latency_sample_lognormal_distribution(eps):
    """Heavy tail: the regime latency distributions actually live in."""
    rng = np.random.default_rng(11)
    _check_quantiles(rng.lognormal(mean=-5.0, sigma=1.5, size=20_000), eps)


def test_latency_sample_exponential_and_constant():
    rng = np.random.default_rng(13)
    _check_quantiles(rng.exponential(0.01, size=20_000), 0.01)
    # constant stream: every quantile is the constant, within eps
    s = LatencySample("c", eps=0.01)
    for _ in range(1000):
        s.sample(0.125)
    for q in (0.5, 0.95, 0.99):
        assert s.quantile(q) == pytest.approx(0.125, rel=0.03)
    assert s.mean == pytest.approx(0.125)
    assert s.min == s.max == 0.125


def test_latency_sample_zero_and_negative_values():
    s = LatencySample("z")
    for v in (0.0, -1.0, 0.0, 5.0):
        s.sample(v)
    assert s.count == 4
    # zero/negative land in the zero bucket: quantiles whose rank falls
    # inside it report 0 (floor-rank convention), the top rank reaches
    # the positive bucket
    assert s.quantile(0.25) == 0.0
    assert s.quantile(1.0) == pytest.approx(5.0, rel=0.03)
    d = s.as_dict()
    assert d["count"] == 4 and d["max"] == 5.0


def test_latency_sample_wide_dynamic_range():
    """Microseconds to minutes in one sketch: the log bucketing must
    hold the relative error across ~8 decades."""
    s = LatencySample("w", eps=0.01)
    values = [10.0 ** e for e in range(-6, 3)]
    for v in values:
        s.sample(v)
    for i, v in enumerate(values):
        q = i / (len(values) - 1)
        assert s.quantile(q) == pytest.approx(v, rel=0.05)


# -- TraceLog rolling -------------------------------------------------------


def test_trace_log_memory_rolls_at_max_events():
    log = TraceLog(max_events=100)
    for i in range(1000):
        TraceEvent("E", logger=log).detail("I", i).log()
    assert len(log.events) <= 100
    # the newest events survive the roll
    assert log.events[-1]["I"] == 999


def test_trace_log_file_sink_rolls(tmp_path):
    """The file sink rotates current -> .1 at max_events: disk stays
    bounded at ~2x max_events lines, the newest generation is always in
    `path`, and every retained line is valid JSONL."""
    path = tmp_path / "trace.jsonl"
    log = TraceLog(path=str(path), max_events=10)
    for i in range(25):
        TraceEvent("E", logger=log).detail("I", i).log()
    log.close()
    rolled = tmp_path / "trace.jsonl.1"
    assert rolled.exists()
    cur = [json.loads(line) for line in path.read_text().splitlines()]
    old = [json.loads(line) for line in rolled.read_text().splitlines()]
    assert log.rolls == 2
    # events 0-9 rolled away entirely (one generation retained), 10-19
    # live in .1, 20-24 in the current file
    assert [e["I"] for e in old] == list(range(10, 20))
    assert [e["I"] for e in cur] == list(range(20, 25))


def test_trace_log_file_sink_bytes_jsonable(tmp_path):
    path = tmp_path / "t.jsonl"
    log = TraceLog(path=str(path))
    TraceEvent("E", logger=log).detail("Key", b"\xffbin").log()
    log.close()
    (rec,) = [json.loads(line) for line in path.read_text().splitlines()]
    assert rec["Key"] == b"\xffbin".decode("latin-1")


def test_trace_batch_renders_into_logger():
    """TraceBatch with a logger lands micro-events as structured records
    with the batch's own capture Time — the commit_debug input shape."""
    clock_val = [1.5]
    log = TraceLog(min_severity=SEV_DEBUG, clock=lambda: 9.9)
    tb = TraceBatch(clock=lambda: clock_val[0], logger=log)
    tb.add_event("CommitDebug", "d1", "X.Before")
    clock_val[0] = 2.5
    tb.add_attach("CommitAttachID", "d1", "b1")
    recs = log.events
    assert [r["Type"] for r in recs] == ["CommitDebug", "CommitAttachID"]
    # the explicit batch Time wins over the sink clock
    assert recs[0]["Time"] == 1.5 and recs[1]["Time"] == 2.5
    assert recs[0]["Location"] == "X.Before"
    assert recs[1]["Location"] == "attach:b1"
    # with a logger the TraceLog is the ONE sink: the unbounded
    # in-process buffer stays empty (long traced runs must not hold the
    # stream twice)
    assert tb.dump() == []
    # without a logger the buffer serves in-process readers
    tb2 = TraceBatch()
    tb2.add_event("CommitDebug", "d2", "Y.Before")
    assert [e[3] for e in tb2.dump()] == ["Y.Before"]


# -- LatencyBands -----------------------------------------------------------


def test_latency_bands_bucketing_and_overflow():
    b = LatencyBands("commit", bands=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.05, 0.5, 5.0):
        b.add(v)
    d = b.as_dict()
    assert d["total"] == 5
    assert d["0.001"] == 1 and d["0.01"] == 1 and d["0.1"] == 1
    assert d["inf"] == 2  # past every threshold -> the overflow bucket
    assert sum(v for k, v in d.items() if k != "total") == d["total"]


def test_latency_bands_overflow_probe_fires():
    from foundationdb_tpu.utils import probes

    before = probes.snapshot().get("metrics.latency_band_overflow", 0)
    LatencyBands("x", bands=(0.001,)).add(10.0)
    after = probes.snapshot().get("metrics.latency_band_overflow", 0)
    assert after == before + 1


def test_default_band_edges_are_sorted_and_stable():
    assert list(COMMIT_LATENCY_BANDS) == sorted(COMMIT_LATENCY_BANDS)
    b = LatencyBands("c")
    assert len(b.counts) == len(COMMIT_LATENCY_BANDS) + 1


# -- KernelStageMetrics: the always-on resolver-kernel telemetry ------------


def test_kernel_stage_metrics_shape():
    from foundationdb_tpu.models.conflict_base import KernelStageMetrics

    m = KernelStageMetrics()
    d = m.as_dict()
    # counters flat, stage samples nested — the status-schema shape
    for key in ("resolveBatches", "compactions", "latchTrips",
                "exactFallbacks", "overflowRaised"):
        assert d[key] == 0
    for key in ("packSeconds", "transferSeconds", "kernelSeconds",
                "fenceSeconds", "deltaLiveBoundaries"):
        assert d[key]["count"] == 0


def test_cpu_conflict_set_counts_batches():
    from foundationdb_tpu.config import TEST_CONFIG
    from foundationdb_tpu.models.conflict_base import CpuConflictSet
    from foundationdb_tpu.models.types import CommitTransaction

    cs = CpuConflictSet(TEST_CONFIG)
    cs.resolve([CommitTransaction(write_conflict_ranges=[(b"a", b"b")])], 10)
    assert cs.metrics.counters.get("resolveBatches") == 1


@pytest.mark.kernel
def test_tpu_conflict_set_emits_stage_metrics():
    """resolve() continuously populates the pack/kernel/fence stage
    samples and the batch counter — bench.py and cluster_status read
    THESE, not private timers."""
    from foundationdb_tpu.config import TEST_CONFIG
    from foundationdb_tpu.models.conflict_set import TpuConflictSet
    from foundationdb_tpu.models.types import CommitTransaction

    cs = TpuConflictSet(TEST_CONFIG)
    for v in (10, 20, 30):
        cs.resolve(
            [CommitTransaction(
                read_conflict_ranges=[(b"k1", b"k2")],
                write_conflict_ranges=[(b"k1", b"k2")],
                read_snapshot=v - 10,
            )],
            v,
        )
    m = cs.metrics
    assert m.counters.get("resolveBatches") == 3
    assert m.pack.count == 3 and m.pack.total > 0
    assert m.kernel.count == 3 and m.kernel.total > 0
    assert m.fence.count == 3


def test_counter_flush_probe_fires():
    from foundationdb_tpu.utils import probes
    from foundationdb_tpu.utils.trace import trace_counters

    log = TraceLog(min_severity=SEV_INFO)
    c = CounterCollection("M", ["a"])
    before = probes.snapshot().get("metrics.counters_flushed", 0)
    trace_counters(log, "MetricsEvent", "r0", c)
    assert probes.snapshot()["metrics.counters_flushed"] == before + 1


# ---------------------------------------------------------------------------
# Saturation-telemetry primitives (PR 7): Smoother / TimerSmoother /
# Gauge / MetricHistory / sparkline.


def test_smoother_step_converges_to_closed_form():
    """Exponential decay vs the closed form: after a step from 0 to T,
    estimate(t) = T * (1 - exp(-t/tau)) for any sampling cadence."""
    from foundationdb_tpu.utils.metrics import Smoother

    clock = [0.0]
    for tau in (0.5, 1.0, 3.0):
        sm = Smoother(tau, clock=lambda: clock[0])
        clock[0] = 0.0
        sm.reset(0.0)
        sm.set_total(100.0)  # the step
        for t in (0.1, 0.25, tau, 2 * tau, 5 * tau):
            clock[0] = t
            want = 100.0 * (1.0 - math.exp(-t / tau))
            assert sm.smooth_total() == pytest.approx(want, rel=1e-9)
        # one folding time reflects ~63.2% of the step
        clock[0] = tau
        sm2 = Smoother(tau, clock=lambda: clock[0])
    # converged: far past tau the estimate is the total
    clock[0] = 50.0
    assert sm.smooth_total() == pytest.approx(100.0, rel=1e-6)


def test_smoother_closed_form_is_sampling_cadence_invariant():
    """Reading the estimate through many small steps must equal one
    big step (the exponential's semigroup property) — the property
    that makes status polling frequency irrelevant to the value."""
    from foundationdb_tpu.utils.metrics import Smoother

    clock = [0.0]
    a = Smoother(1.0, clock=lambda: clock[0])
    b = Smoother(1.0, clock=lambda: clock[0])
    a.set_total(42.0)
    b.set_total(42.0)
    # a: polled at every 0.01; b: read once at t=2
    for i in range(1, 201):
        clock[0] = i * 0.01
        a.smooth_total()
    assert a.smooth_total() == pytest.approx(b.smooth_total(), rel=1e-9)


def test_smoother_ramp_rate_tracks_input_rate():
    """A constant-rate ramp: smooth_rate converges to the true rate
    (the Ratekeeper's queue-bytes-per-second signal)."""
    from foundationdb_tpu.utils.metrics import Smoother

    clock = [0.0]
    sm = Smoother(1.0, clock=lambda: clock[0])
    for i in range(1, 501):
        clock[0] = i * 0.01
        sm.add_delta(5.0)  # 500/s
    assert sm.smooth_rate() == pytest.approx(500.0, rel=0.02)
    # rate decays back toward zero once input stops (exp(-10) of the
    # gap remains: ~0.023 of the 500/s peak)
    clock[0] += 10.0
    assert sm.smooth_rate() < 0.1


def test_smoother_non_advancing_clock_and_validation():
    from foundationdb_tpu.utils.metrics import Smoother

    sm = Smoother(1.0)  # default clock never advances
    sm.add_delta(10.0)
    sm.add_delta(5.0)
    assert sm.total == 15.0
    assert sm.smooth_total() == 0.0  # no time passed: no decay applied
    with pytest.raises(ValueError):
        Smoother(0.0)
    with pytest.raises(ValueError):
        Smoother(-1.0)


def test_timer_smoother_uses_wall_clock():
    import time as _time

    from foundationdb_tpu.utils.metrics import TimerSmoother

    sm = TimerSmoother(0.05)
    sm.set_total(10.0)
    _time.sleep(0.2)  # 4 folding times: ~98% reflected
    assert sm.smooth_total() > 9.0


def test_gauge_set_and_supplier():
    from foundationdb_tpu.utils.metrics import Gauge

    g = Gauge("depth")
    assert g.get() == 0.0
    g.set(7.0)
    assert g.get() == 7.0
    live = [1]
    g2 = Gauge("live", supplier=lambda: live[0] * 2.0)
    assert g2.get() == 2.0
    live[0] = 5
    assert g2.get() == 10.0


def test_metric_history_ring_wraparound():
    from foundationdb_tpu.utils.metrics import MetricHistory

    h = MetricHistory(4)
    assert len(h) == 0 and h.last() is None and h.samples() == []
    for i in range(3):
        h.append(float(i), float(i * 10))
    assert len(h) == 3
    assert h.values() == [0.0, 10.0, 20.0]
    assert h.last() == 20.0
    # wrap: capacity stays 4, oldest-first order preserved
    for i in range(3, 11):
        h.append(float(i), float(i * 10))
    assert len(h) == 4
    assert h.values() == [70.0, 80.0, 90.0, 100.0]
    assert h.samples()[0] == (7.0, 70.0)
    assert h.last() == 100.0
    with pytest.raises(ValueError):
        MetricHistory(0)


def test_sparkline_shape():
    from foundationdb_tpu.utils.metrics import sparkline

    assert sparkline([]) == ""
    assert sparkline([5.0, 5.0, 5.0]) == "▁▁▁"
    s = sparkline([0, 1, 2, 3, 4, 5, 6, 7])
    assert len(s) == 8
    assert s[0] == "▁" and s[-1] == "█"
    # width bound: only the trailing `width` samples render
    assert len(sparkline(list(range(100)), width=24)) == 24

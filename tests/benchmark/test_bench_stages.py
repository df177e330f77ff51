"""The readers of the resolver role's stage spans and byte counters: a
traced tiny run of every cell reports each metric read from the role's
status, a role that reports no stages gives nothing (and raises
nothing), and the compaction program is found in the trace by name."""

from __future__ import annotations

import os

import pytest

from runs import tiny_run
from tiny import BENCH_DIR, bench  # puts benchmark/ on the path

import harness  # noqa: E402

CELLS = [w["name"] for w in bench()["workloads"]]
#: the per-layer sources read from the role's status
STATUS = ("program_span", "program_counter")
STAGE_READERS = ("wire_codec_ms", "key_sample_ms", "pack_ms", "transfer_ms",
                 "readback_ms", "assemble_ms", "arg_mb", "device_arg_mb")


def _reader(quantity: str):
    return harness.load_module(
        os.path.join(BENCH_DIR, "metrics", quantity + ".py"))


def _window(status: dict, trace: dict | None = None, batches: int = 4):
    return harness.Window(status0=status, status1=status, batches=batches,
                          txns=0, least_bytes=0, chips=1, peaks={},
                          trace=trace)


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_every_status_metric(workload, tmp_path):
    rc, line, err = tiny_run(workload, "--trace", "1", "--keep-trace",
                             str(tmp_path / "trace"))
    assert rc == 0, err[-3000:]
    assert line["correct"] is True
    want = {m["name"] for m in bench()["per_layer"]
            if workload in m.get("workloads", []) and m["source"] in STATUS}
    assert want <= set(line["metrics"])
    values = {k.split(".")[0]: v["value"] for k, v in line["metrics"].items()}
    assert all(values[q] > 0 for q in STAGE_READERS)
    assert values["device_arg_mb"] >= values["arg_mb"]


@pytest.mark.parametrize("quantity", STAGE_READERS)
def test_stage_reader_gives_nothing_without_stages(quantity):
    """A role from before the stages (no `qos.stages`) reads as nothing."""
    status = {"qos": {"compute_time_dist": {"count": 3, "mean": 0.1}}}
    assert _reader(quantity).read(_window(status)) is None


def test_compact_device_ms_reads_the_compaction_program():
    read = _reader("compact_device_ms").read
    top = [["jit_resolve_group_tiered(11)", 0.8], ["jit_compact(12)", 0.02]]
    assert read(_window({}, {"programs_top": top})) == pytest.approx(5.0)
    assert read(_window({}, {"programs_top": top[:1]})) is None
    assert read(_window({}, {"programs_top": [["jit__unknown(3)", 1.0]]})) \
        is None
    assert read(_window({}, None)) is None

"""The reader of the rows each batch is dispatched at: nothing where the
role reports no `dispatch_rows` counter (a program that pads every batch
to the config's full shape), the window's mean where it does, and on a
tiny YCSB-A cell, whose batches hold a few txns, the small rung."""

from __future__ import annotations

import os

import pytest

from runs import tiny_run
from tiny import BENCH_DIR, TINY_KERNEL  # puts benchmark/ on the path

import harness  # noqa: E402


def _read(status0: dict, status1: dict):
    reader = harness.load_module(
        os.path.join(BENCH_DIR, "metrics", "dispatch_rows.py"))
    return reader.read(harness.Window(
        status0=status0, status1=status1, batches=4, txns=0,
        least_bytes=0, chips=1, peaks={}, trace=None))


def _status(count: int, mean: float, stages: bool = True) -> dict:
    qos = {"compute_time_dist": {"count": count, "mean": 0.1}}
    if stages:
        qos["stages"] = {"arg_bytes": {"count": count, "mean": 1e6}}
        qos["stages"]["dispatch_rows"] = {"count": count, "mean": mean}
    return {"qos": qos}


@pytest.mark.parametrize("stages", [False, True],
                         ids=["no_stages", "stages_without_dispatch_rows"])
def test_dispatch_rows_gives_nothing_without_the_counter(stages):
    s = _status(3, 0.0, stages)
    if stages:
        del s["qos"]["stages"]["dispatch_rows"]
    assert _read(s, s) is None


def test_dispatch_rows_is_the_window_mean():
    # 10 batches at 512 rows before the window; 20 more at 128 in it
    assert _read(_status(10, 512), _status(30, (10 * 512 + 20 * 128) / 30)) \
        == pytest.approx(128)
    # no batch inside the window
    assert _read(_status(10, 512), _status(10, 512)) is None


def test_ycsb_batches_dispatch_at_the_small_rung(tmp_path):
    """Batches of at most 16 one-read, one-write txns on the tiny
    (64, 256, 256) kernel take its (64, 64, 64) rung: 128 rows, not the
    full shape's 512."""
    rc, line, err = tiny_run("ycsb_a.closed64", "--trace", "1",
                             "--keep-trace", str(tmp_path / "trace"))
    assert rc == 0, err[-3000:]
    assert line["correct"] is True
    full = TINY_KERNEL["max_reads"] + TINY_KERNEL["max_writes"]
    assert full == 512
    assert line["metrics"]["dispatch_rows.ycsb"]["value"] == 128

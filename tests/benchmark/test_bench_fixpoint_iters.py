"""The reader of the fixpoint applications each batch ran: nothing
where the role reports no `fixpoint_iters` counter (a program that does
not count them), the window's mean where it does."""

from __future__ import annotations

import os

import pytest

from tiny import BENCH_DIR  # puts benchmark/ on the path

import harness  # noqa: E402


def _read(status0: dict, status1: dict):
    reader = harness.load_module(
        os.path.join(BENCH_DIR, "metrics", "fixpoint_iters.py"))
    return reader.read(harness.Window(
        status0=status0, status1=status1, batches=4, txns=0,
        least_bytes=0, chips=1, peaks={}, trace=None))


def _status(count: int, mean: float, stages: bool = True) -> dict:
    qos = {"compute_time_dist": {"count": count, "mean": 0.1}}
    if stages:
        qos["stages"] = {"dispatch_rows": {"count": count, "mean": 512}}
        qos["stages"]["fixpoint_iters"] = {"count": count, "mean": mean}
    return {"qos": qos}


@pytest.mark.parametrize("stages", [False, True],
                         ids=["no_stages", "stages_without_fixpoint_iters"])
def test_fixpoint_iters_gives_nothing_without_the_counter(stages):
    s = _status(3, 0.0, stages)
    if stages:
        del s["qos"]["stages"]["fixpoint_iters"]
    assert _read(s, s) is None


def test_fixpoint_iters_is_the_window_mean():
    # 10 batches at 3 applications before the window; 20 more at 12 in it
    assert _read(_status(10, 3), _status(30, (10 * 3 + 20 * 12) / 30)) \
        == pytest.approx(12)
    # no batch inside the window
    assert _read(_status(10, 3), _status(10, 3)) is None

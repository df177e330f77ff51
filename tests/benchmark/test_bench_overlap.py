"""The reader of the resolver role's overlap counter: nothing where the
role reports no `overlap` stage (a role from before it), and the
window's mean share in % where it does."""

from __future__ import annotations

import os

import pytest

from tiny import BENCH_DIR  # puts benchmark/ on the path

import harness  # noqa: E402


def _read(status0: dict, status1: dict):
    reader = harness.load_module(
        os.path.join(BENCH_DIR, "metrics", "overlap_pct.py"))
    return reader.read(harness.Window(
        status0=status0, status1=status1, batches=4, txns=0,
        least_bytes=0, chips=1, peaks={}, trace=None))


def _status(count: int, mean: float, stages: bool = True) -> dict:
    qos = {"compute_time_dist": {"count": count, "mean": 0.1}}
    if stages:
        qos["stages"] = {"key_sample": {"count": count, "mean": 0.01}}
        qos["stages"]["overlap"] = {"count": count, "mean": mean}
    return {"qos": qos}


@pytest.mark.parametrize("stages", [False, True],
                         ids=["no_stages", "stages_without_overlap"])
def test_overlap_pct_gives_nothing_without_the_counter(stages):
    s = _status(3, 0.0, stages)
    if stages:
        del s["qos"]["stages"]["overlap"]
    assert _read(s, s) is None


def test_overlap_pct_is_the_window_mean_in_percent():
    # 10 batches before the window, 1 of them overlapped; 40 after, 31
    assert _read(_status(10, 0.1), _status(40, 31 / 40)) == \
        pytest.approx(100 * 30 / 30)
    assert _read(_status(10, 0.1), _status(30, 11 / 30)) == \
        pytest.approx(100 * 10 / 20)
    # no batch inside the window
    assert _read(_status(10, 0.1), _status(10, 0.1)) is None

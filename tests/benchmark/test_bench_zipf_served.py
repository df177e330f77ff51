"""resolver_zipf_8r.hot_rmw on the CPU, tiny sizes: 64-txn batches of 8
zipfian reads a txn over a keyspace small enough that a transaction
often reads one key twice, served through the role at its full row
rung, answer the reference's verdicts; the control (the reference with
its intra-batch pass left out, in the program's place) does not."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from tiny import BENCH_DIR, KNOBS, REPO, bench

import harness  # noqa: E402

CELL = "resolver_zipf_8r.hot_rmw"
KERNEL = {"max_key_bytes": 16, "max_txns": 64, "max_reads": 512,
          "max_writes": 256, "history_capacity": 2048,
          "window_versions": 5000000, "delta_capacity": 2048,
          "compact_interval": 4}
FAULTY_ROLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "faulty_role.py")

#: one run in a process of its own (the harness's parent stays off JAX);
#: a traced one keeps the role's trace in argv[2] and reads a fixed
#: reduction, as on the CPU there is no device plane to reduce
_CHILD = f"""
import json, sys
sys.path[:0] = [{os.path.dirname(FAULTY_ROLE)!r}, {BENCH_DIR!r}, {REPO!r}]
import harness, run, tiny
run.PLATFORM = "cpu"
cell = harness.Cell(**json.loads(sys.argv[1]))
if cell.trace:
    harness.reduce_trace = tiny._stand_in_reduction(sys.argv[2])
print(json.dumps(run.run_cell(tiny.bench(), cell)))
"""


def tiny_cell(seed: int) -> harness.Cell:
    w = {c["name"]: c for c in bench()["workloads"]}[CELL]
    with open(os.path.join(BENCH_DIR, "configs", w["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    config.update(kernel=KERNEL, keyspace=500)
    traffic.update(txns_per_batch=64)
    return harness.Cell(name=CELL, config=config, traffic=traffic,
                        chips=w["chips"], seed=seed, seconds=2.0,
                        trace=False, root=REPO, t_start=0.0)


def serve(cell: harness.Cell, fault: str | None = None, keep: str = ""):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "FDBTPU_KNOB_OVERRIDES": KNOBS}
    if fault:
        env["FDB_BENCH_FAULT"] = fault
        cell = dataclasses.replace(cell, role_entry=FAULTY_ROLE)
    p = subprocess.run(
        [sys.executable, "-c", _CHILD, json.dumps(dataclasses.asdict(cell)),
         keep],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.fixture(scope="module")
def cell():
    return tiny_cell(2**31 + 2929)


def test_tiny_cell_is_served_as_the_reference_decides(cell, tmp_path):
    """Traced: every batch takes the full (64, 512, 256) rung, and its
    intra-batch fixpoint runs past the kernel's 3 unrolled applications."""
    line, err = serve(dataclasses.replace(cell, trace=True),
                      keep=str(tmp_path / "trace"))
    assert line["correct"] is True and line["failed"] == 0
    checks = {k: v["value"] for k, v in line["checks"].items()}
    assert checks == {"decision_mismatches": 0, "unanswered_batches": 0}
    assert line["attempted"] > 0
    assert "programs compiled or loaded inside the window: 0" in err
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["dispatch_rows.zipf"] == 512 + 256
    assert metrics["fixpoint_iters.zipf"] > 3


def test_control_is_not_correct_on_the_same_stream(cell):
    line, _err = serve(cell, fault="control")
    assert line["correct"] is False
    assert line["checks"]["decision_mismatches"]["value"] > 0

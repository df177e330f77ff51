"""Hermeticity contract for the graded multichip dryrun.

The dryrun validates on a virtual CPU mesh, and its body NEVER runs in
a process whose default backend could be anything but CPU: the caller
may hold the chip (one process per chip), and an eager jnp op (state
init, batch packing) must not land on it. It unconditionally re-execs
into a child with ``JAX_PLATFORMS=cpu``, and the child asserts its
default backend.

Reference analog: the multi-resolver split these shardings implement is
`fdbserver/CommitProxyServer.actor.cpp:1551-1567`.
"""

from __future__ import annotations

import subprocess
import sys

import pytest


def _graft():
    sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
    import __graft_entry__ as G

    return G


def test_dryrun_parent_never_runs_body_in_process(monkeypatch):
    """Without the sentinel, the parent must delegate — not build a mesh."""
    G = _graft()
    from foundationdb_tpu.parallel import mesh as M

    calls = []
    monkeypatch.delenv(M._SUBPROCESS_SENTINEL, raising=False)
    monkeypatch.setattr(
        M, "run_in_cpu_subprocess", lambda m, f, n: calls.append((m, f, n))
    )
    G.dryrun_multichip(8)
    assert calls == [("__graft_entry__", "dryrun_multichip", 8)]


def test_cpu_subprocess_env_is_hermetic(monkeypatch):
    """The child env pins CPU even when the parent's names the chip,
    sets the sentinel, and requests the right virtual device count
    (replacing, not appending to, the parent's own count)."""
    from foundationdb_tpu.parallel import mesh as M

    captured = {}

    def fake_run(cmd, env=None, **kw):
        captured["cmd"], captured["env"] = cmd, env

        class P:
            returncode = 0
            stdout = ""
            stderr = ""

        return P()

    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    M.run_in_cpu_subprocess("somemod", "somefunc", 4)

    env = captured["env"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env[M._SUBPROCESS_SENTINEL] == "1"
    assert env["XLA_FLAGS"].split() == [
        "--xla_force_host_platform_device_count=4"
    ]
    assert captured["cmd"][0] == sys.executable


@pytest.mark.kernel
def test_dryrun_end_to_end(tmp_path, monkeypatch):
    """The real thing: exactly what the driver runs, asserting rc=0.

    Cheap because the child's tiny-shape compiles hit the persistent
    per-machine compile cache after the first run. The perf ledger is
    redirected to a tempfile (the env rides into the hermetic child):
    a DRIVER dryrun must land its fingerprinted multichip row in
    perf/history.jsonl, a TEST run must not dirty the committed
    history — and the row's shape is pinned here either way.
    """
    ledger = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("FDBTPU_PERF_LEDGER", ledger)
    G = _graft()
    G.dryrun_multichip(8)
    import json

    rows = [json.loads(x) for x in open(ledger)]
    assert len(rows) == 1 and rows[0]["source"] == "multichip"
    assert rows[0]["workload"]["n_devices"] == 8
    assert rows[0]["workload"]["kernel"] == "tiered_sharded"
    assert rows[0]["metrics"]["ok"]["value"] == 1
    assert rows[0]["metrics"]["txn_s"]["tier"] == "hardware"
    assert rows[0]["metrics"]["committed"]["tier"] == "structural"

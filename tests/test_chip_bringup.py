"""Bring-up on the chip, checked on the CPU.

chip_smoke.py's phases run here at tiny sizes with the platform check
steered to "cpu" inside the test; the script itself refuses a CPU
platform. Around it: one process per chip (an import claims nothing, a
role that cannot host a device resolver never sees the chip, a device
resolver on a non-TPU platform fails at role start, a dead role fails
the connect at once) and the compile-cache location.
"""

from __future__ import annotations

import asyncio
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from foundationdb_tpu.cluster import multiprocess as mp  # noqa: E402
from foundationdb_tpu.utils import compile_cache  # noqa: E402
from foundationdb_tpu.wire import transport  # noqa: E402


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")


def test_chip_smoke_refuses_a_cpu_platform():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_phase_a_serves_through_the_device_resolver(on_cpu, monkeypatch):
    # the knob keeps a 64-txn "tpu" kernel on the device conflict set
    # (production's 64K capacity is at the threshold already); the role
    # child reads it from the environment
    monkeypatch.setenv("FDBTPU_KNOB_OVERRIDES", "RESOLVER_TPU_MIN_BATCH=64")
    res = chip_smoke.phase_a(kernel_txns=64, clients=32, ops=2,
                             records=1000, batch=64)
    kernel = res["resolver_kernel"]
    assert (kernel["conflict_set"], kernel["jax_backend"]) == ("tpu", "cpu")
    assert kernel["group_dispatches"] > 0
    assert res["acked_read_back"] == res["committed"] > 0


def test_phase_a_fails_when_the_knob_routes_to_the_host(on_cpu):
    """At 64 txns the production knob routes backend "tpu" to the CPU
    conflict set: the smoke must notice, not pass."""
    with pytest.raises(RuntimeError, match="did not serve"):
        chip_smoke.phase_a(kernel_txns=64, clients=8, ops=1, records=1000,
                           batch=64)


def test_phase_b_matches_the_skip_list(on_cpu):
    rows = chip_smoke.phase_b(
        (("uniform", 256, 16), ("zipf", 4096, 8), ("ycsb_e", 256, 8))
    )
    by = {r["mode"]: r for r in rows}
    assert by["uniform"]["compactions"] >= 1 and by["uniform"]["gc_floor"] > 0
    assert by["zipf"]["dedup_reads"] > 0
    assert by["ycsb_e"]["range_sweep"] and by["ycsb_e"]["sweepGroups"] > 0


def test_sharded_phase_matches_per_shard_skip_lists(on_cpu):
    counts = chip_smoke.phase_sharded(4, 256, 16)
    assert counts["committed"] > 0


def test_importing_the_kernels_claims_no_device():
    code = (
        "import foundationdb_tpu.ops.keys, foundationdb_tpu.ops.delta, "
        "foundationdb_tpu.models.conflict_set, "
        "foundationdb_tpu.parallel.sharding\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_compile_cache_honours_the_jax_env_dir(tmp_path, monkeypatch):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path / "env"))
    assert compile_cache.enable() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "env").exists()


def test_compile_cache_defaults_to_the_fixed_repo_dir(monkeypatch):
    import jax

    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == os.path.join(
            REPO, ".jax_compile_cache"
        )
        assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("name,backend,keeps_platform", [
    ("resolver", "tpu", True),
    ("resolver", "tpu-force", True),
    ("worker", "native", True),
    ("resolver", "native", False),
    ("resolver", "cpu", False),
    ("tlog", "native", False),
    ("storage", "native", False),
])
def test_spawn_role_leaves_the_chip_to_device_roles(
    monkeypatch, tmp_path, name, backend, keeps_platform
):
    seen = {}

    def fake_popen(cmd, env=None, **kw):
        seen["env"] = env
        return None

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    mp.spawn_role(name, str(tmp_path), backend=backend)
    assert (seen["env"]["JAX_PLATFORMS"] == "tpu") == keeps_platform
    assert seen["env"]["PYTHONPATH"].split(os.pathsep)[0] == REPO


def test_device_resolver_refuses_a_non_tpu_platform(monkeypatch):
    """Unless JAX_PLATFORMS pins the CPU explicitly (as tests do), a
    device resolver must serve from a TPU or fail at role start."""
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError):
        mp.ResolverRole(backend="tpu-force")


def test_connect_to_a_dead_role_fails_at_once(tmp_path):
    proc = subprocess.Popen([sys.executable, "-c", "raise SystemExit(3)"])
    role = mp.RoleProcess("resolver", str(tmp_path / "none.sock"), proc)

    async def go():
        with pytest.raises(transport.TransportError, match="exited"):
            await asyncio.wait_for(mp.connect(role), timeout=30)

    try:
        asyncio.run(go())
    finally:
        role.stop()

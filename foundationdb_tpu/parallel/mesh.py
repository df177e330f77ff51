"""Device-mesh construction that survives hostile backend environments.

The resolver mesh must be buildable in three worlds:

1. CI / unit tests — no accelerator; an 8-virtual-device CPU backend via
   ``--xla_force_host_platform_device_count``.
2. A host with ONE TPU chip, held by one process.  A multi-chip dryrun
   there must not touch (or, in a second process, try to claim) the
   chip.  The CPU backend coexists: ``jax.devices("cpu")`` works without
   touching the TPU.
3. A multi-chip TPU host or slice — ``jax.devices()`` has >= n chips.

Rule: never call ``jax.devices()`` (which initializes the *default*
backend) when what we need is a CPU mesh.  Ask for the CPU platform by
name, and make sure the host-device-count flag is in place before the
CPU backend's first initialization.
"""

from __future__ import annotations

import os
import re
import sys

import numpy as np

AXIS = "resolver"

_FLAG = "xla_force_host_platform_device_count"


def ensure_host_device_count(n: int) -> None:
    """Best-effort: request >= n virtual CPU devices.

    Only effective if the CPU backend has not initialized yet — callers
    that find fewer devices afterwards must fall back to a subprocess
    (see `run_in_cpu_subprocess`).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"--{_FLAG}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (flags + f" --{_FLAG}={n}").strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = re.sub(
            rf"--{_FLAG}=\d+", f"--{_FLAG}={n}", flags
        )


def cpu_devices(n: int):
    """n virtual CPU devices, never touching the default (TPU) backend."""
    ensure_host_device_count(n)
    import jax

    devs = jax.devices("cpu")
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} CPU devices but the CPU backend initialized with "
            f"{len(devs)} before --{_FLAG} could take effect; re-run in a "
            f"fresh process (see run_in_cpu_subprocess)"
        )
    return list(devs[:n])


def cpu_mesh(n: int, axis: str = AXIS):
    import jax

    return jax.sharding.Mesh(np.array(cpu_devices(n)), (axis,))


def resolver_mesh(n: int, axis: str = AXIS):
    """An n-device `resolver` mesh on the DEFAULT backend — the mesh
    TpuConflictSet builds when `config.n_shards > 1` and no explicit
    mesh is passed. On a CPU-backend host (sim/CI) this is the virtual
    CPU mesh (`--xla_force_host_platform_device_count`); on a real TPU
    slice it takes the first n accelerator devices."""
    import jax

    if jax.default_backend() == "cpu":
        return cpu_mesh(n, axis)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"resolver mesh needs {n} device(s); this host has {len(devs)}"
        )
    return jax.sharding.Mesh(np.array(devs[:n]), (axis,))


# Set in children of run_in_cpu_subprocess: a child that still can't get
# its CPU devices must fail loudly, not respawn itself forever.
_SUBPROCESS_SENTINEL = "_FDBTPU_CPU_SUBPROCESS"

def in_cpu_subprocess() -> bool:
    return bool(os.environ.get(_SUBPROCESS_SENTINEL))


def run_in_cpu_subprocess(module: str, func: str, n: int) -> None:
    """Re-exec `python -c "import module; module.func(n)"` with a clean
    CPU-only JAX: used when this process's CPU backend already
    initialized without enough virtual devices."""
    import subprocess

    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = re.sub(rf"--{_FLAG}=\d+", "", flags)
    env["XLA_FLAGS"] = (flags + f" --{_FLAG}={n}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env[_SUBPROCESS_SENTINEL] = "1"
    code = f"import {module}; {module}.{func}({n})"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
            capture_output=True,
            text=True,
            timeout=900,
        )
    except subprocess.TimeoutExpired as e:
        for stream, buf in ((sys.stdout, e.stdout), (sys.stderr, e.stderr)):
            if buf:
                stream.write(buf if isinstance(buf, str) else buf.decode(errors="replace"))
        raise
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{module}.{func}({n}) failed in CPU subprocess (rc={proc.returncode})"
        )

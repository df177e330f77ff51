"""Force tests onto a virtual 8-device CPU mesh (no TPU needed in CI).

Must set the env vars before jax is imported anywhere in the test process.
"""

import os

# Force-override: unit tests run on the virtual CPU mesh, never on a chip
# the environment may offer. Subprocesses spawned by tests (multiprocess
# roles, the hermetic dryrun child) inherit the setting.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def random_key(rng, max_len=8, alphabet=4) -> bytes:
    n = int(rng.integers(0, max_len + 1))
    return bytes(rng.integers(0, alphabet, size=n, dtype=np.uint8))


def random_range(rng, max_len=8, alphabet=4):
    while True:
        a, b = random_key(rng, max_len, alphabet), random_key(rng, max_len, alphabet)
        if a != b:
            return (min(a, b), max(a, b))

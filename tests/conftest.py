"""Test bootstrap: force an 8-device virtual CPU platform.

This is the TPU-world answer to "test distributed without a cluster"
(SURVEY §4): jax's ``--xla_force_host_platform_device_count`` gives N
fake devices on the host, so every mesh/collective codepath runs under
pytest exactly as it would on an N-chip slice.  The platform is pinned
in code as well as by the tier-1 command's ``JAX_PLATFORMS=cpu``, so a
bare ``pytest`` on a machine with a chip still tests on the CPU; both
must happen before any backend is initialised.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _no_real_data_dir(monkeypatch):
    """Synthetic-fallback tests must not pick up a machine-local dataset
    directory via $DOPT_DATA_DIR."""
    monkeypatch.delenv("DOPT_DATA_DIR", raising=False)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual CPU devices, got {devs}"
    return devs

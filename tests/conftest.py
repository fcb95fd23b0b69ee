"""Test configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding (tp/dp/sp meshes)
is exercised without TPU hardware — mirrors the reference's tier-1 strategy of
pure-host unit tests (/root/reference: SURVEY.md section 4).

Env vars must be set before the first jax import; JAX_PLATFORMS=cpu in the
environment is all it takes to hold JAX to the CPU.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    import jax

    return jax.random.PRNGKey(0)

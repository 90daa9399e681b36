"""Test environment: CPU backend with 8 virtual devices, x64 enabled.

This is the "fake backend" the reference lacks (SURVEY.md section 4): the
sharded solver's multi-chip semantics are exercised on an 8-device CPU mesh
(`--xla_force_host_platform_device_count=8`) without TPU hardware, and f64 is
available for parity against the native C++ oracle.  Pallas kernels run in
interpret mode off the TPU.

Both settings are read when the backend is first created, so they take
effect here even where the caller forgot `JAX_PLATFORMS=cpu`.  JAX's
persistent compilation cache is off, here and (through the environment)
in every process a test starts: no test reads a program that another
process or an earlier run compiled.
"""

import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

from wavetpu.core.problem import Problem  # noqa: E402


def pytest_sessionstart(session):
    devs = jax.devices()
    assert devs[0].platform == "cpu", f"suite must run on CPU, got {devs}"
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"


@pytest.fixture(scope="session")
def small_problem():
    return Problem(N=16, Np=1, Lx=1.0, Ly=1.0, Lz=1.0, T=1.0, timesteps=10)


@pytest.fixture(scope="session")
def medium_problem():
    return Problem(N=32, Np=1, Lx=1.0, Ly=1.0, Lz=1.0, T=1.0, timesteps=20)

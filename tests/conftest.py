"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see the 1 real CPU
device; only launch/dryrun.py forces 512 host devices (per the brief)."""

import numpy as np
import pytest

import jax


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def kernel_stages(monkeypatch):
    """Take the apps' TPU branch here: the Pallas stages, under the Pallas
    interpreter (the platform decides the kernel mode; tests steer the
    helper, and clear JAX's trace caches so no program traced for the
    other branch is reused)."""
    from repro.kernels import mode
    monkeypatch.setattr(mode, "use_kernels", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")

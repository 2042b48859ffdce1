"""Tier-1 runs the cases of benchmark/tests/test_reference.py, each
under its own name: the module's tests, re-exported."""

import jax
import pytest

from benchmark.tests.test_reference import *  # noqa: F401,F403


@pytest.fixture(autouse=True)
def four_devices(monkeypatch):
    """benchmark/tests/conftest.py gives these cases four virtual
    devices and the sharding case says so; this suite's conftest.py
    gives eight."""
    devices = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a: devices)

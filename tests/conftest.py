"""Test configuration: the suite runs on a virtual 8-device CPU mesh.

JAX reads its platform and the host device count when it is first
imported, and nothing imports it before this file, so setting the
environment here is enough — and every process a test starts inherits
it. (The real TPU is exercised by ``chip_smoke.py``, not by this
suite.)
"""

import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from kubeflow_tpu.vmeshenv import virtual_mesh_env  # noqa: E402

assert "jax" not in sys.modules, "jax imported before tests/conftest.py"
os.environ.update(virtual_mesh_env(8))
# test_benchmark_rehearsal_*.py import their cases from there.
pytest.register_assert_rewrite("benchmark.tests")


@pytest.fixture
def interpret_flash(monkeypatch):
    """Run the Pallas flash kernels in the interpreter: this suite's
    backend is the CPU, which cannot compile them. The program never
    makes that choice itself (ops/flash_attention.INTERPRET)."""
    from kubeflow_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "INTERPRET", True)

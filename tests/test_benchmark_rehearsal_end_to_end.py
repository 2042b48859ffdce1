"""Tier-1 runs the cases of benchmark/tests/test_end_to_end.py, each under
its own name: the module's tests and fixtures, re-exported."""

from benchmark.tests.test_end_to_end import *  # noqa: F401,F403

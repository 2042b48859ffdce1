"""Tier-1 runs the cases of benchmark/tests/test_mixedctx.py, each under
its own name: the module's tests and fixtures, re-exported."""

from benchmark.tests.test_mixedctx import *  # noqa: F401,F403

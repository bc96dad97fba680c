"""Collects the benchmark's tests of the granite-4.0-h-small cell
(``benchmark/tests/test_hybrid.py``) in tier-1."""
from benchmark.tests.test_hybrid import *  # noqa: F401,F403

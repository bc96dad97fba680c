"""Collects the benchmark's tests of the ouro-2.6b cell
(``benchmark/tests/test_loop.py``) in tier-1."""
from benchmark.tests.test_loop import *  # noqa: F401,F403

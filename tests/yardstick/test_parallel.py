"""Collects the benchmark's tests of the falcon-h1-34b-instruct cell
(``benchmark/tests/test_parallel.py``) in tier-1."""
from benchmark.tests.test_parallel import *  # noqa: F401,F403

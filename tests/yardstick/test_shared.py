"""Collects the benchmark's tests of the phi-4-mini-flash-reasoning cell
(``benchmark/tests/test_shared.py``) in tier-1."""
from benchmark.tests.test_shared import *  # noqa: F401,F403

"""Collects the benchmark's tests of the laguna-xs.2 train cell
(``benchmark/tests/test_expert_train.py``) in tier-1."""
from benchmark.tests.test_expert_train import *  # noqa: F401,F403

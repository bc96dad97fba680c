"""Collects the benchmark's tests of the gigachat3.1-702b-a36b cell
(``benchmark/tests/test_latent.py``) in tier-1."""
from benchmark.tests.test_latent import *  # noqa: F401,F403

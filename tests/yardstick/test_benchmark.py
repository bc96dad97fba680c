from benchmark.tests.test_benchmark import *  # noqa: F401,F403

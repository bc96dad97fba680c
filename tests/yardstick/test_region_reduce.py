from benchmark.tests.test_region_reduce import *  # noqa: F401,F403

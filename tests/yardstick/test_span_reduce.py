from benchmark.tests.test_span_reduce import *  # noqa: F401,F403

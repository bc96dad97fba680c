from benchmark.tests.test_step_log import *  # noqa: F401,F403

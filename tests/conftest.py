"""Test configuration: run all tests on an 8-device virtual CPU mesh so that
every distributed feature is exercised without TPU hardware, mirroring the
reference's gloo/CPU multi-process harness (reference: realhf/base/testing.py).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests run on the virtual 8-device CPU mesh and never open a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests excluded from the tier-1 budget "
        "(run with `-m slow` or no marker filter)",
    )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Tier-1 per-test runtime guard: a PASSING non-``slow`` test whose
    call phase ran past the per-test budget becomes a loud failure
    naming the offender, instead of silently pushing the suite toward
    its 870 s hard timeout (tests/helpers/runtime_guard.py)."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when != "call" or not rep.passed:
        return
    from tests.helpers.runtime_guard import over_budget_message

    msg = over_budget_message(
        item.nodeid, call.duration, is_slow="slow" in item.keywords
    )
    if msg is not None:
        rep.outcome = "failed"
        rep.longrepr = msg


@pytest.fixture(autouse=True)
def _fresh_globals():
    """Reset process-global state between tests."""
    from areal_tpu.base import constants, name_resolve

    yield
    name_resolve.reset()
    constants.reset()
    from areal_tpu.models import transformer

    transformer.set_ambient_mesh(None)
    from areal_tpu.observability import set_registry

    set_registry(None)  # fresh metric series per test

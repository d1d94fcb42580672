import os
import sys

import pytest

# tests run against the real single CPU device (the multi-device tests start
# their own processes); keep determinism + quiet logs
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from _harness import TEST_TIME_LIMIT_S, time_limit  # noqa: E402


# A hung test fails alone after TEST_TIME_LIMIT_S instead of eating the run's
# time.  Setup is limited too: module fixtures can train models.
@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with time_limit(TEST_TIME_LIMIT_S, f"setup of {item.nodeid}"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with time_limit(TEST_TIME_LIMIT_S, item.nodeid):
        return (yield)

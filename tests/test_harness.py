"""The suite's per-test time limit (``_harness.time_limit``)."""
import signal
import time

import pytest

from _harness import time_limit


def test_time_limit_fails_an_overrunning_body():
    start = time.monotonic()
    with pytest.raises(pytest.fail.Exception, match="sleeper exceeded its time limit of 0.2 s"):
        with time_limit(0.2, "sleeper"):
            time.sleep(2)
    assert time.monotonic() - start < 1.0


def test_time_limit_disarms_after_a_body_that_finishes():
    # suspend this test's own limit, so that any timer left is the block's
    outer = signal.setitimer(signal.ITIMER_REAL, 0)
    try:
        with time_limit(5):
            time.sleep(0.01)
        assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    finally:
        signal.setitimer(signal.ITIMER_REAL, *outer)

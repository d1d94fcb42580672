"""Test harness shared by the suite: a per-test time limit, and a runner for
test bodies that need a mesh of fake CPU devices.

XLA locks the device count when the backend first initialises, so the main
pytest process (whose other tests need one device) cannot host a mesh.  A
multi-device body is a module-level function of a test module; the runner
imports it in a fresh interpreter with ``--xla_force_host_platform_device_count``
set and calls it there.
"""
import contextlib
import os
import signal
import subprocess
import sys
import time

import pytest

# Per test, and per subprocess a test starts.  The slowest in-process test
# takes ~95 s alone on the CPU; this leaves room for a loaded 6-worker run.
TEST_TIME_LIMIT_S = 300

_TESTS = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_TESTS, "..", "src")


@contextlib.contextmanager
def time_limit(seconds: float, what: str = "the block"):
    """Fail the enclosed block if it runs longer than ``seconds``.

    Uses ``SIGALRM``, so it only works in the main thread.  An enclosing
    limit is suspended while the block runs and re-armed with what it had
    left when the block ends.
    """

    def expire(signum, frame):
        pytest.fail(f"{what} exceeded its time limit of {seconds:g} s", pytrace=False)

    previous = signal.signal(signal.SIGALRM, expire)
    outer_left, _ = signal.setitimer(signal.ITIMER_REAL, seconds)
    start = time.monotonic()
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        if outer_left:
            left = outer_left - (time.monotonic() - start)
            signal.setitimer(signal.ITIMER_REAL, max(left, 1e-3))


def run_multidevice(fn, *args, devices: int = 8) -> None:
    """Call ``fn(*args)`` in a fresh CPU process with ``devices`` fake devices.

    ``fn`` is a module-level function of a module in ``tests/`` and ``args``
    are literals (``repr`` round-trips them).  Fails with the child's
    stdout and the tail of its stderr unless the call returns.
    """
    call = f"from {fn.__module__} import {fn.__name__} as f; f(*{args!r})"
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join((_SRC, _TESTS))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", call],
        capture_output=True,
        text=True,
        env=env,
        timeout=TEST_TIME_LIMIT_S,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"

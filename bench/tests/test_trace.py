"""The trace reduction on a small trace recorded on a TPU v5 lite by
``record_trace.py``: four calls of a program with a ``local_step`` scope (a
scan of three matmuls) and a ``round_set`` scope (a Pallas kernel, a sum, a
divide), each after a ``feed`` program.  The expected times were read off
the trace's events by hand (nanoseconds)."""
import pathlib

import pytest

from harness import spec, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
# per call: copy-start + convert (w to bf16) + copy-done + the while loop
LOCAL_STEP_NS = (6 + 6015 + 5582 + 36242) + (6 + 5970 + 5713 + 36242) + (
    6 + 6021 + 5593 + 36242) + (6 + 5961 + 5560 + 36241)
# per call: the kernel + reduce_sum + broadcast_divide_fusion
ROUND_SET_NS = (687 + 1692 + 7160) + (687 + 1691 + 7237) + (687 + 1691 + 7028) + (686 + 1691 + 7256)
KERNEL_NS = 687 + 687 + 687 + 686
FEED_NS = 7185 + 7188 + 7012 + 6986
WINDOW_NS = 4256229  # the bench.window host span


@pytest.fixture(scope="module")
def reading():
    return trace.read(
        str(DATA), [(DATA / "call.hlo.txt").read_text()], n_calls=4, window_s=WINDOW_NS * 1e-9
    )


def test_time_by_layer(reading):
    assert reading.scope_s["local_step"] == pytest.approx(LOCAL_STEP_NS * 1e-9, rel=1e-4)
    assert reading.scope_s["round_set"] == pytest.approx(ROUND_SET_NS * 1e-9, rel=1e-4)
    assert reading.per_call_ms("round_set") == pytest.approx(ROUND_SET_NS * 1e-6 / 4, rel=1e-4)
    assert set(reading.scope_s) == {"local_step", "round_set"}


def test_kernel_and_feed_ops(reading):
    ops = dict(reading.breakdown()["device_ops"])
    assert ops["jit_call:round_set/probe_kernel"] == pytest.approx(KERNEL_NS * 1e-9, rel=1e-4)
    assert ops["jit_feed:other/fusion"] == pytest.approx(FEED_NS * 1e-9, rel=1e-4)
    assert ops["jit_call:local_step/convolution_tanh_fusion"] == pytest.approx(
        12 * 11063e-9, rel=1e-3
    )


def test_busy_and_idle(reading):
    # every op ran back to back inside its program; the programs never overlap
    assert reading.busy_s == pytest.approx((LOCAL_STEP_NS + ROUND_SET_NS + FEED_NS) * 1e-9, rel=1e-3)
    gaps = reading.breakdown()["idle_gaps"]
    assert sum(s for _, s in gaps) == pytest.approx(reading.window_s - reading.busy_s, rel=1e-6)
    assert {name for name, _ in gaps} <= {"bench.select", "bench.dispatch", "bench.sync", "host loop"}
    idle = spec.metric_reader("device.idle_share").read(reading)
    assert idle == pytest.approx(100 * (1 - reading.busy_s / reading.window_s))


def test_device_clock_shift():
    data = trace.load(str(DATA))
    (dev,) = data["devices"].values()
    # the largest (host enqueue - device start) over the 8 programs: run 16
    assert trace.clock_shift(dev["modules"], data["host"]) == pytest.approx(50384578 - 48682398, abs=2)


def test_exclusive_time_of_nested_ops():
    ev = lambda a, b: trace.Event(a, b, "x", {})
    assert trace.exclusive([ev(0, 10), ev(1, 3), ev(4, 9), ev(5, 6)]) == [3, 2, 4, 1]

"""``correct`` fails each planted fault and the control, and passes a sound
run, at tiny sizes on the CPU under each cell's own limits."""
import dataclasses
import time

import pytest

import controls
from harness import check, spec
from harness.faults import FAULTS
from harness.run import run
from tiny import tiny_cell

CELLS = ["resnet20-k16.every-step", "resnet20-k16.paper-epoch", "danube3-4b-1l-k2.every-step"]


def _cell(name):
    return tiny_cell(name, limits=spec.find_cell(name).limits, use_kernels=False)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_run_with_a_fault_is_not_correct(name, fault):
    result = run(_cell(name), 2**31 + 3, 0.0, False, time.perf_counter(), FAULTS[fault])
    assert result["correct"] is False, result["checks"]
    assert list(result)[-1] == "checks"


def test_a_sound_run_is_correct():
    """One cell only.  Over the paper epoch's 33 local steps the tiny
    model's training is chaotic: float32 rounding differences between the
    program and the reference grow past the limits set at the cell's size,
    where the chip's sound runs read well under them.  At the tiny LM's
    widths bfloat16's rounding is a larger share of the loss than at the
    published widths, past that cell's loss limit."""
    name = "resnet20-k16.every-step"
    result = run(_cell(name), 2**31 + 3, 0.0, False, time.perf_counter())
    assert result["correct"] is True, result["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    cell = _cell(name)
    r = controls.readings(cell, [4], control_seeds=1)
    assert not check.judge(r["control"][4], cell.limits), r["control"]

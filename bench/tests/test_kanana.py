"""The kanana cell on the CPU at a tiny size: ``correct`` fails each planted
fault and the control under the cell's own limits, and the held experts'
metric readers give the hand-computed values."""
import dataclasses
import time

import pytest

import controls
from harness import check, spec, trace
from harness.faults import FAULTS
from harness.run import run

NAME = "kanana2-30b-a3b-5l-k2.every-step"
TINY = dict(
    hidden_size=64, num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    router_experts=16, n_routed_experts=4, num_experts_per_tok=4, vocab_size=128, seq_len=32,
)


def _cell():
    cell = spec.find_cell(NAME)
    return dataclasses.replace(
        cell, config=dict(cell.config, **TINY), traffic=dict(cell.traffic, use_kernels=False)
    )


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_run_with_a_fault_is_not_correct(fault):
    result = run(_cell(), 2**31 + 3, 0.0, False, time.perf_counter(), FAULTS[fault])
    assert result["correct"] is False, result["checks"]


def test_the_control_is_not_correct():
    cell = _cell()
    r = controls.readings(cell, [4], control_seeds=1)
    assert not check.judge(r["control"][4], cell.limits), r["control"]


def _reading():
    return trace.Reading(
        window_s=1.0, busy_s=0.9, n_calls=4, scope_s={"local_step": 2.0},
        op_s={
            "jit_call:local_step/moe_gmm": 0.02,
            "jit_call:local_step/moe_tgmm": 0.01,
            "jit_call:local_step/fusion": 1.0,
            "jit_call:round_set/slab_combine": 0.5,
            "jit_call:other/moe_gmm": 5.0,
        },
        gap_s={}, counts={"K": 2, "D": 424_960_512, "model_flops_per_call": 45e12},
        device_kind="TPU v5 lite",
    )


def test_the_held_experts_metrics_read_the_kernels():
    """(0.02 + 0.01) s over 4 calls is 7.5 ms a call.  The least time is
    the FLOPs' bound: 18 x 2048 x 768 x 6144 pairs x 4 layers = 695.78
    GFLOP at 197 TFLOP/s, 3.5319 ms, against 3.3647 ms for the 2.7557 GB."""
    r = _reading()
    assert spec.metric_reader("moe.device_ms").read(r) == pytest.approx(7.5)
    flops = 18 * 2048 * 768 * 6144 * 4
    assert spec.metric_reader("moe.roofline_share").read(r) == pytest.approx(
        100 * flops / 197e12 / 7.5e-3
    )
    r.op_s = {"jit_call:local_step/fusion": 1.0}
    assert spec.metric_reader("moe.device_ms").read(r) is None
    assert spec.metric_reader("moe.roofline_share").read(r) is None

"""The FLOP and byte counts, and the table of peaks."""
import math

import jax
import pytest

from harness import peaks, spec
from harness.run import counts


def _params(cell):
    p0 = jax.eval_shape(cell.model.init_params(cell.config), jax.random.key(0))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(p0))


def test_resnet20_counts():
    cell = spec.find_cell("resnet20-k16.paper-epoch")
    assert _params(cell) == 308_890
    # 40.8M multiply-adds forward over the published model; x2 FLOPs, x3 training
    assert cell.model.flops_per_sample(cell.config) == 6 * 40_813_184
    assert cell.model.samples_per_call(cell.config, cell.traffic) == 11 * 16 * 128


def test_danube_counts():
    cell = spec.find_cell("danube3-4b-1l-k2.every-step")
    assert _params(cell) == 400_600_320
    cfg = cell.config
    matmul = 277_708_800  # one layer's projections and MLP, plus the head
    per_token = cell.model.flops_per_sample(cfg) / cfg["seq_len"]
    assert per_token == pytest.approx(3 * (2 * matmul + 4 * 3840 * 512.5), rel=1e-12)
    assert per_token == pytest.approx(1.69e9, rel=0.01)


def test_bench_weights_have_the_program_layout():
    for name in ("resnet20-k16.every-step", "danube3-4b-1l-k2.every-step"):
        cell = spec.find_cell(name)
        ours = jax.eval_shape(cell.model.init_params(cell.config), jax.random.key(0))
        theirs = jax.eval_shape(cell.model.program_init(cell.config), jax.random.key(0))
        assert jax.tree.structure(ours) == jax.tree.structure(theirs)
        assert [x.shape for x in jax.tree.leaves(ours)] == [x.shape for x in jax.tree.leaves(theirs)]


def test_round_set_roofline_is_bound_by_hbm():
    reader = spec.metric_reader("round_set.roofline_share")
    c = counts(spec.find_cell("resnet20-k16.every-step"))
    least, by = reader.bound(c, peaks.lookup("TPU v5 lite"))
    assert by == "hbm" and least == pytest.approx(2 * 16 * 308_890 * 4 / 819e9)


def test_an_unknown_device_kind_is_an_error():
    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v9 imaginary")

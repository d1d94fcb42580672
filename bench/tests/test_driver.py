"""The timed call is the program's own entries, bit for bit."""
import jax
import numpy as np
import pytest

from harness.driver import make_driver
from harness.program import build_trainer, make_state_fn
from harness.run import build
from tiny import tiny_cell


def _same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _setup(name):
    cell = tiny_cell(name)
    p = build(cell)
    root = jax.random.key(5)
    data = p.data_fn(jax.random.key(6)) if p.data_fn else None
    batches, keys = p.feed(data, root, np.int32(0))
    return cell, p, batches, keys


def test_driver_is_trainer_epoch():
    cell, p, batches, _ = _setup("resnet20-k16.paper-epoch")
    assert cell.traffic["local_steps"] == 11 and cell.traffic["telemetry"]
    rng = jax.random.key(9)
    keys = jax.random.split(rng, cell.traffic["local_steps"])
    got, _ = p.driver(p.state_fn(jax.random.key(1)), batches, keys)
    want, _ = jax.jit(p.trainer.epoch)(p.state_fn(jax.random.key(1)), batches, rng)
    _same(got, want)


@pytest.mark.parametrize("name", ["resnet20-k16.every-step", "danube3-4b-1l-k2.every-step"])
def test_driver_is_one_step_of_make_many_steps(name):
    cell, p, batches, keys = _setup(name)
    assert cell.traffic["local_steps"] == 1 and not cell.traffic["telemetry"]
    got, losses = p.driver(p.state_fn(jax.random.key(1)), batches, keys)
    want, metrics = p.trainer.make_many_steps()(p.state_fn(jax.random.key(1)), batches, keys)
    _same(got, want)
    _same(losses, metrics["loss"])


def test_uncompiled_driver_matches_compiled():
    cell, p, batches, keys = _setup("resnet20-k16.every-step")
    trainer = build_trainer(cell)
    state_fn = make_state_fn(trainer, cell.model.init_params(cell.config))
    got, _ = make_driver(trainer, False)(state_fn(jax.random.key(1)), batches, keys)
    want, _ = p.driver(p.state_fn(jax.random.key(1)), batches, keys)
    _same(got, want)

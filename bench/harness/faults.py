"""Faults planted under the timed call, to show that the check fails them.

Each is ``fault(trainer, telemetry) -> body``: the driver's un-jitted call
with one thing broken.  The training cells can have three: a call that
returns its state unchanged, half of each batch left out (the mean taken
over the rest), and the exchange between agents (the round-set) left out.
"""
from __future__ import annotations

import jax

from harness.driver import driver_body


def frozen(trainer, telemetry):
    call = driver_body(trainer, telemetry)

    def body(state, batches, keys):
        _, losses = call(state, batches, keys)
        return state, losses

    return body


def _half(x):
    """The first half of the batch axis, or of the sequence where the batch
    holds one row."""
    axis = 2 if x.shape[2] > 1 else 3
    return jax.lax.slice_in_dim(x, 0, (x.shape[axis] + 1) // 2, axis=axis)


def half_batch(trainer, telemetry):
    call = driver_body(trainer, telemetry)

    def body(state, batches, keys):
        return call(state, jax.tree.map(_half, batches), keys)

    return body


class _NoExchange:
    """The trainer with its round-set left out."""

    def __init__(self, trainer):
        self.local_step = trainer.local_step

    def consensus(self, state, obs=None):
        return (state,)


def no_exchange(trainer, telemetry):
    return driver_body(_NoExchange(trainer), telemetry)


FAULTS = {"frozen": frozen, "half_batch": half_batch, "no_exchange": no_exchange}

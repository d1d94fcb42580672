"""The call the window drives: ``L`` local steps, then one round-set.

With ``L`` an epoch and ``telemetry=True`` it is ``trainer.epoch``; with
``L = 1`` and ``telemetry=False`` it is one step of
``trainer.make_many_steps`` (``bench/tests/test_driver.py`` checks both bit
for bit).  The named scopes are metadata only: the trace reduction splits
device time by them.
"""
from __future__ import annotations

import jax

LOCAL_STEP_SCOPE = "local_step"
ROUND_SET_SCOPE = "round_set"


def driver_body(trainer, telemetry: bool):
    """``(state, batches (L, K, ...), keys (L,)) -> (state, losses (L,))``,
    un-jitted, so that a test can plant a fault around it."""
    from repro.obs import ObsConfig

    obs = ObsConfig() if telemetry else None

    def call(state, batches, keys):
        def body(st, inp):
            st, metrics = trainer.local_step(st, *inp)
            return st, metrics["loss"]

        with jax.named_scope(LOCAL_STEP_SCOPE):
            state, losses = jax.lax.scan(body, state, (batches, keys))
        with jax.named_scope(ROUND_SET_SCOPE):
            state = trainer.consensus(state, obs=obs)[0]
        return state, losses

    return call


def make_driver(trainer, telemetry: bool, body=None):
    """The jitted call with the state donated, as ``make_many_steps`` does."""
    return jax.jit(body or driver_body(trainer, telemetry), donate_argnums=(0,))

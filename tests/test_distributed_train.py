"""Decentralized train steps on a mesh of fake CPU devices: the sharded
step against its single-device result, the donated multi-step driver
against single steps, and the permute engine's codec state.

Each ``_case_*`` function runs in its own process (``run_multidevice``).
"""
import jax
import jax.numpy as jnp
import numpy as np

from _harness import run_multidevice


def _case_sharded_step():
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import ring
    from repro.core.decentralized import TrainerConfig
    from repro.launch import sharding as shr
    from repro.launch.train import init_train_state, make_train_step
    from repro.models import get_bundle
    from repro.optim import momentum

    K = 4
    mesh = jax.make_mesh((K, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    bundle = get_bundle("qwen3-4b-smoke", num_agents=K)
    opt = momentum(0.05, 0.9)
    step = make_train_step(bundle, ring(K), opt, TrainerConfig(algorithm="drt"))
    state = init_train_state(bundle, opt, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (K, 2, 33), 0, bundle.cfg.vocab)
    batch = {"tokens": tokens}

    # reference: single-logical-device execution
    ref_state, ref_metrics = jax.jit(step)(state, batch, jax.random.key(2))

    p_specs = shr.param_pspecs(bundle.cfg, state.params, mesh, with_agents=True)
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    o_specs = {"m": p_specs}
    st_specs = type(state)(named(p_specs), named(o_specs), NamedSharding(mesh, P()))
    b_specs = named(shr.train_batch_pspecs(bundle.cfg, batch, mesh))
    state_s = jax.device_put(state, st_specs)
    batch_s = jax.device_put(batch, b_specs)
    out_state, metrics = jax.jit(step, in_shardings=(st_specs, b_specs, None),
                                 out_shardings=(st_specs, None))(state_s, batch_s, jax.random.key(2))
    np.testing.assert_allclose(float(metrics["loss"]), float(ref_metrics["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(out_state.params), jax.tree.leaves(ref_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-4)


def test_sharded_train_step_executes():
    """A decentralized train step (local grads + DRT consensus) EXECUTES on
    a (4 agents x 2 model) mesh with sharded params and matches the
    single-device result."""
    run_multidevice(_case_sharded_step)


def _case_many_steps():
    from repro.core import ring
    from repro.core.decentralized import TrainerConfig
    from repro.launch.train import init_train_state, make_train_many_steps, make_train_step
    from repro.models.registry import get_bundle
    from repro.optim import momentum

    K = 4
    bundle = get_bundle("qwen3-8b-smoke", num_agents=K)
    opt = momentum(0.05, 0.9)
    codec = "topk:0.1"
    tcfg = TrainerConfig(codec=codec, schedule="periodic:ring,hypercube",
                         consensus_steps=3)
    step = jax.jit(make_train_step(bundle, ring(K), opt, tcfg, consensus_rounds=2))
    many = jax.jit(make_train_many_steps(bundle, ring(K), opt, tcfg,
                                         consensus_rounds=2, donate=False))

    state = init_train_state(bundle, opt, jax.random.key(0), codec=codec)
    n = 4
    tokens = [jax.random.randint(jax.random.key(100 + i), (K, 2, 17), 0,
                                 bundle.cfg.vocab) for i in range(n)]
    keys = [jax.random.key(i) for i in range(n)]

    s_single = state
    for i in range(n):
        s_single, _ = step(s_single, {"tokens": tokens[i]}, keys[i])

    s_many, metrics = many(state, {"tokens": jnp.stack(tokens)}, jnp.stack(keys))
    assert metrics["loss"].shape == (n,)
    assert int(s_many.step) == n
    for a, b in zip(jax.tree.leaves(s_single), jax.tree.leaves(s_many)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # ragged chunking (1 + 3) — chunk boundaries are invisible
    s_a, _ = many(state, {"tokens": jnp.stack(tokens[:1])}, jnp.stack(keys[:1]))
    s_b, _ = many(s_a, {"tokens": jnp.stack(tokens[1:])}, jnp.stack(keys[1:]))
    for a, b in zip(jax.tree.leaves(s_single), jax.tree.leaves(s_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # donated driver: chaining invalidates the input, reuses buffers
    manyd = make_train_many_steps(bundle, ring(K), opt, tcfg, consensus_rounds=2)
    sd, _ = manyd(state, {"tokens": jnp.stack(tokens)}, jnp.stack(keys))
    assert jax.tree.leaves(state.params)[0].is_deleted()
    assert int(sd.step) == n


def test_train_many_steps_bitwise_matches_single_steps():
    """The pod-runtime donated multi-step driver (make_train_many_steps)
    produces BIT-identical state to n single make_train_step calls —
    including the top-k EF residual and a dynamic schedule's round indices
    (round t = step * consensus_rounds + r derives from the CARRIED step) —
    and a ragged chunk split (1 + 3) matches the single 4-chunk."""
    run_multidevice(_case_many_steps, devices=1)


def _case_permute_codec_state():
    from repro.core import ring
    from repro.core.decentralized import TrainerConfig
    from repro.launch import sharding as shr
    from repro.launch.train import init_train_state, make_train_step
    from repro.models import get_bundle
    from repro.optim import momentum

    K = 4
    mesh = jax.make_mesh((K, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    bundle = get_bundle("qwen3-4b-smoke", num_agents=K)
    opt = momentum(0.05, 0.9)
    codec = "topk:0.1"
    tcfg = TrainerConfig(algorithm="drt", codec=codec)
    state = init_train_state(bundle, opt, jax.random.key(0), codec=codec)
    assert len(jax.tree.leaves(state.comm)) > 0
    p_specs = shr.param_pspecs(bundle.cfg, state.params, mesh, with_agents=True)
    step = jax.jit(make_train_step(bundle, ring(K), opt, tcfg,
                                   consensus_impl="permute",
                                   mesh=mesh, param_specs=p_specs))
    tokens = jax.random.randint(jax.random.key(1), (K, 2, 33), 0, bundle.cfg.vocab)
    s1, m1 = step(state, {"tokens": tokens}, jax.random.key(2))
    # residual is non-trivial after one round and evolves on the next
    nz = sum(float(jnp.sum(jnp.abs(r))) for r in jax.tree.leaves(s1.comm))
    assert nz > 0, nz
    s2, m2 = step(s1, {"tokens": tokens}, jax.random.key(3))
    moved = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                zip(jax.tree.leaves(s1.comm), jax.tree.leaves(s2.comm)))
    assert moved > 0
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))


def test_permute_train_step_threads_codec_state():
    """End-to-end: the permute engine inside shard_map threads the top-k
    error-feedback residual through TrainState.comm, sharded like params."""
    run_multidevice(_case_permute_codec_state)

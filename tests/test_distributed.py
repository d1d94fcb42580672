"""Distributed-path tests: run in a subprocess with 8 fake CPU devices
(XLA locks the device count at first init, so the main pytest process —
which other tests need at 1 device — cannot host these)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_permute_consensus_matches_gather_engine():
    """The optimized ppermute neighbour-exchange engine produces the SAME
    mixing weights and combined parameters as the paper-faithful all-gather
    engine (ring and hypercube), executed on a real 8-device mesh."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax import shard_map
        from repro.core import ring, hypercube, DRTConfig
        from repro.core.consensus import PermuteConsensus, gather_consensus_step
        from repro.utils.pytree import LayerPartition

        K = 8
        mesh = jax.make_mesh((K,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

        def tree_init(k):
            k1, k2 = jax.random.split(k)
            return {"embed": {"w": jax.random.normal(k1, (4, 8))},
                    "blocks": {"w": jax.random.normal(k2, (3, 8, 8))}}

        pK = jax.vmap(tree_init)(jax.random.split(jax.random.key(0), K))
        part = LayerPartition.build(jax.tree.map(lambda x: x[0], pK))

        for topo in (ring(K), hypercube(K)):
            cfg = DRTConfig()
            C = jnp.asarray(topo.c_matrix(), jnp.float32)
            want, _ = gather_consensus_step(part, pK, C, cfg, algorithm="drt")

            eng = PermuteConsensus(part, topo, cfg, axis_name="data")
            specs = jax.tree.map(lambda _: P("data"), pK)
            def body(local):
                sq = jax.tree.map(lambda x: x[0], local)      # strip leading 1
                out = eng(sq)
                return jax.tree.map(lambda x: x[None], out)
            f = shard_map(body, mesh=mesh, in_specs=(specs,), out_specs=specs)
            got = f(pK)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)
            # classical engine too
            wantc, _ = gather_consensus_step(part, pK, C, cfg, algorithm="classical",
                metropolis=jnp.asarray(topo.metropolis(), jnp.float32))
            engc = PermuteConsensus(part, topo, cfg, axis_name="data", algorithm="classical")
            def bodyc(local):
                sq = jax.tree.map(lambda x: x[0], local)
                out = engc(sq)
                return jax.tree.map(lambda x: x[None], out)
            gotc = shard_map(bodyc, mesh=mesh, in_specs=(specs,), out_specs=specs)(pK)
            for a, b in zip(jax.tree.leaves(gotc), jax.tree.leaves(wantc)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)
        print("ENGINES-MATCH")
    """)
    assert "ENGINES-MATCH" in out


def test_sharded_train_step_executes():
    """A decentralized train step (local grads + DRT consensus) EXECUTES on
    a (4 agents x 2 model) mesh with sharded params and matches the
    single-device result."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ring
        from repro.core.decentralized import TrainerConfig
        from repro.launch.train import make_train_step, init_train_state
        from repro.launch import sharding as shr
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
        print("SHARDED-STEP-OK", float(metrics["loss"]))
    """)
    assert "SHARDED-STEP-OK" in out


def test_engines_agree_under_every_codec():
    """Acceptance: gather and permute engines produce matching combined
    parameters (within codec tolerance) for EVERY registered codec on
    ring / hypercube / torus2d, on both the slab hot path and the per-leaf
    tree oracle, including multi-round round-sets.  Both engines share the
    fold_in(fold_in(rng, round), agent) key derivation, so stochastic codecs
    emit identical wire slabs/trees and the engines agree to
    collective-reduction-order noise, not codec noise."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import ring, hypercube, torus2d, DRTConfig
        from repro.core.consensus import PermuteConsensus, gather_consensus_rounds
        from repro.utils.pytree import LayerPartition

        K = 4
        mesh = jax.make_mesh((K,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

        def tree_init(k):
            k1, k2 = jax.random.split(k)
            return {"embed": {"w": jax.random.normal(k1, (4, 8))},
                    "blocks": {"w": jax.random.normal(k2, (3, 8, 8))}}

        pK = jax.vmap(tree_init)(jax.random.split(jax.random.key(0), K))
        part = LayerPartition.build(jax.tree.map(lambda x: x[0], pK))
        rng = jax.random.key(7)
        specs = jax.tree.map(lambda _: P("data"), pK)

        for topo in (ring(K), hypercube(K), torus2d(K)):
            cfg = DRTConfig()
            C = jnp.asarray(topo.c_matrix(), jnp.float32)
            for codec in ("identity", "bf16", "f16", "int8", "topk:0.25"):
                for path, rounds in (("slab", 1), ("slab", 3), ("tree", 1)):
                    want, A, _ = gather_consensus_rounds(
                        part, pK, C, cfg, algorithm="drt", codec=codec,
                        rng=rng, rounds=rounds, path=path)
                    eng = PermuteConsensus(part, topo, cfg, axis_name="data",
                                           codec=codec, path=path)
                    def body(local):
                        sq = jax.tree.map(lambda x: x[0], local)
                        out, _ = eng(sq, rng=rng, rounds=rounds)
                        return jax.tree.map(lambda x: x[None], out)
                    got = shard_map(body, mesh=mesh, in_specs=(specs,),
                                    out_specs=specs, check_vma=False)(pK)
                    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                        np.testing.assert_allclose(
                            np.asarray(a, np.float32), np.asarray(b, np.float32),
                            rtol=2e-4, atol=2e-5,
                            err_msg=f"{topo.name}/{codec}/{path}/r{rounds}")
        print("CODEC-ENGINES-MATCH")
    """, devices=4)
    assert "CODEC-ENGINES-MATCH" in out


def test_engines_agree_under_dynamic_schedule_every_codec():
    """Satellite parity matrix under a CHANGING per-round mixing matrix:
    the permute engine (re-deriving its decomposition per round, masking
    churn-dropped agents) matches the gather engine driven by the same
    schedule's (C_t, metropolis_t) stacks — every codec, slab and tree
    paths, 3-round round-sets starting mid-sequence."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import (ring, hypercube, DRTConfig, PeriodicSchedule,
                                ChurnSchedule)
        from repro.core.consensus import PermuteConsensus, gather_consensus_rounds
        from repro.utils.pytree import LayerPartition

        K = 4
        mesh = jax.make_mesh((K,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

        def tree_init(k):
            k1, k2 = jax.random.split(k)
            return {"embed": {"w": jax.random.normal(k1, (4, 8))},
                    "blocks": {"w": jax.random.normal(k2, (3, 8, 8))}}

        pK = jax.vmap(tree_init)(jax.random.split(jax.random.key(0), K))
        part = LayerPartition.build(jax.tree.map(lambda x: x[0], pK))
        rng = jax.random.key(7)
        specs = jax.tree.map(lambda _: P("data"), pK)

        sched = ChurnSchedule(PeriodicSchedule((ring(K), hypercube(K))),
                              agent_drop=0.25, seed=9)
        Cs, Ms = sched.mixing_stacks(2, 3)
        for codec in ("identity", "bf16", "f16", "int8", "topk:0.25"):
            for path in ("slab", "tree"):
                want, A, _ = gather_consensus_rounds(
                    part, pK, Cs, DRTConfig(), algorithm="drt", metropolis=Ms,
                    codec=codec, rng=rng, rounds=3, path=path)
                eng = PermuteConsensus(part, ring(K), DRTConfig(),
                                       axis_name="data", codec=codec,
                                       path=path, schedule=sched)
                def body(local):
                    sq = jax.tree.map(lambda x: x[0], local)
                    out, _ = eng(sq, rng=rng, rounds=3, start_round=2)
                    return jax.tree.map(lambda x: x[None], out)
                got = shard_map(body, mesh=mesh, in_specs=(specs,),
                                out_specs=specs, check_vma=False)(pK)
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                    np.testing.assert_allclose(
                        np.asarray(a, np.float32), np.asarray(b, np.float32),
                        rtol=2e-4, atol=2e-5, err_msg=f"{codec}/{path}")
        # classical too (identity wire): churned Metropolis agrees
        want, A, _ = gather_consensus_rounds(
            part, pK, Cs, DRTConfig(), algorithm="classical", metropolis=Ms,
            rounds=3, path="slab")
        eng = PermuteConsensus(part, ring(K), DRTConfig(), axis_name="data",
                               algorithm="classical", schedule=sched)
        def bodyc(local):
            sq = jax.tree.map(lambda x: x[0], local)
            return jax.tree.map(lambda x: x[None], eng(sq, rounds=3, start_round=2))
        got = shard_map(bodyc, mesh=mesh, in_specs=(specs,),
                        out_specs=specs, check_vma=False)(pK)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        print("DYNAMIC-ENGINES-MATCH")
    """, devices=4)
    assert "DYNAMIC-ENGINES-MATCH" in out


def test_permute_engine_whole_slab_kernels_match_gather():
    """PermuteConsensus(use_kernels=True) routes its {self}+neighbour combine
    through the ONE-launch ``slab_source_combine`` grid (instead of one
    ``weighted_combine`` per (group, slot)); interpret-mode results match
    the gather engine for exact and int8 exchanges over multi-round sets."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import ring, DRTConfig
        from repro.core.consensus import PermuteConsensus, gather_consensus_rounds
        from repro.utils.pytree import LayerPartition

        K = 4
        mesh = jax.make_mesh((K,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

        def tree_init(k):
            k1, k2 = jax.random.split(k)
            return {"embed": {"w": jax.random.normal(k1, (4, 8))},
                    "blocks": {"w": jax.random.normal(k2, (3, 8, 8))}}

        pK = jax.vmap(tree_init)(jax.random.split(jax.random.key(0), K))
        part = LayerPartition.build(jax.tree.map(lambda x: x[0], pK))
        specs = jax.tree.map(lambda _: P("data"), pK)
        rng = jax.random.key(7)
        topo = ring(K)
        C = jnp.asarray(topo.c_matrix(), jnp.float32)

        for codec in (None, "int8"):
            want, _, _ = gather_consensus_rounds(
                part, pK, C, DRTConfig(), rounds=3, codec=codec,
                rng=rng if codec else None)
            eng = PermuteConsensus(part, topo, DRTConfig(), axis_name="data",
                                   codec=codec, use_kernels=True)
            def body(local):
                sq = jax.tree.map(lambda x: x[0], local)
                if codec:
                    out, _ = eng(sq, rng=rng, rounds=3)
                else:
                    out = eng(sq, rounds=3)
                return jax.tree.map(lambda x: x[None], out)
            got = shard_map(body, mesh=mesh, in_specs=(specs,),
                            out_specs=specs, check_vma=False)(pK)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-4, atol=2e-5,
                                           err_msg=str(codec))
        print("PERMUTE-SLAB-KERNELS-OK")
    """, devices=4)
    assert "PERMUTE-SLAB-KERNELS-OK" in out


def test_train_many_steps_bitwise_matches_single_steps():
    """The pod-runtime donated multi-step driver (make_train_many_steps)
    produces BIT-identical state to n single make_train_step calls —
    including the top-k EF residual and a dynamic schedule's round indices
    (round t = step * consensus_rounds + r derives from the CARRIED step) —
    and a ragged chunk split (1 + 3) matches the single 4-chunk."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import ring
        from repro.core.decentralized import TrainerConfig
        from repro.launch.train import (init_train_state, make_train_step,
                                        make_train_many_steps)
        from repro.models.registry import get_bundle
        from repro.optim import momentum

        K = 4
        bundle = get_bundle("qwen3-8b-smoke", num_agents=K)
        opt = momentum(0.05, 0.9)
        codec = "topk:0.1"
        tcfg = TrainerConfig(codec=codec, schedule="periodic:ring,hypercube",
                             consensus_steps=3)
        step = jax.jit(make_train_step(bundle, ring(K), opt, tcfg,
                                       consensus_rounds=2))
        many = make_train_many_steps(bundle, ring(K), opt, tcfg,
                                     consensus_rounds=2, donate=False)
        many = jax.jit(many)

        state = init_train_state(bundle, opt, jax.random.key(0), codec=codec)
        n = 4
        tokens = [jax.random.randint(jax.random.key(100 + i), (K, 2, 17), 0,
                                     bundle.cfg.vocab) for i in range(n)]
        keys = [jax.random.key(i) for i in range(n)]

        s_single = state
        for i in range(n):
            s_single, _ = step(s_single, {"tokens": tokens[i]}, keys[i])

        s_many, metrics = many(state, {"tokens": jnp.stack(tokens)},
                               jnp.stack(keys))
        assert metrics["loss"].shape == (n,)
        assert int(s_many.step) == n
        for a, b in zip(jax.tree.leaves(s_single), jax.tree.leaves(s_many)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # ragged chunking (1 + 3) — chunk boundaries are invisible
        s_a, _ = many(state, {"tokens": jnp.stack(tokens[:1])},
                      jnp.stack(keys[:1]))
        s_b, _ = many(s_a, {"tokens": jnp.stack(tokens[1:])},
                      jnp.stack(keys[1:]))
        for a, b in zip(jax.tree.leaves(s_single), jax.tree.leaves(s_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # donated driver: chaining invalidates the input, reuses buffers
        manyd = make_train_many_steps(bundle, ring(K), opt, tcfg,
                                      consensus_rounds=2)
        sd, _ = manyd(state, {"tokens": jnp.stack(tokens)}, jnp.stack(keys))
        assert jax.tree.leaves(state.params)[0].is_deleted()
        assert int(sd.step) == n
        print("MANY-STEPS-BITWISE-OK")
    """, devices=1)
    assert "MANY-STEPS-BITWISE-OK" in out


def test_permute_telemetry_matches_analytic_and_direct():
    """PermuteConsensus(obs=...) on a real 4-device mesh: the per-agent
    runtime wire counters equal ``comm.accounting.collective_bytes_per_step``
    for every codec — including the chain graph, whose analytic row now uses
    the same greedy matching decomposition the engine actually runs — and
    the (psum'd, agent-replicated) global disagreement matches the direct
    mean_k |x_k - xbar|^2 of the round output."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import ring, chain, DRTConfig
        from repro.core.consensus import PermuteConsensus
        from repro.comm.accounting import collective_bytes_per_step
        from repro.obs.metrics import ObsConfig
        from repro.utils.pytree import LayerPartition

        K = 4
        mesh = jax.make_mesh((K,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

        def tree_init(k):
            k1, k2 = jax.random.split(k)
            return {"embed": {"w": jax.random.normal(k1, (4, 8))},
                    "blocks": {"w": jax.random.normal(k2, (3, 8, 8))}}

        pK = jax.vmap(tree_init)(jax.random.split(jax.random.key(0), K))
        part = LayerPartition.build(jax.tree.map(lambda x: x[0], pK))
        specs = jax.tree.map(lambda _: P("data"), pK)
        rng = jax.random.key(7)
        template = jax.tree.map(lambda x: x[0], pK)

        for topo in (ring(K), chain(K)):
            for codec in (None, "int8", "topk:0.1:0"):
                for path in ("slab", "tree"):
                    eng = PermuteConsensus(part, topo, DRTConfig(),
                                           axis_name="data", codec=codec,
                                           path=path)
                    def body(local):
                        sq = jax.tree.map(lambda x: x[0], local)
                        if codec:
                            out, _, cm = eng(sq, rng=rng, rounds=2,
                                             obs=ObsConfig())
                        else:
                            out, cm = eng(sq, rounds=2, obs=ObsConfig())
                        return (jax.tree.map(lambda x: x[None], out),
                                jax.tree.map(lambda x: x[None], cm))
                    out, cm = shard_map(
                        body, mesh=mesh, in_specs=(specs,),
                        out_specs=(specs, P("data")), check_vma=False)(pK)
                    tag = f"{topo.name}/{codec}/{path}"
                    assert cm.disagreement.shape == (K, 2), tag
                    d = np.asarray(cm.disagreement)
                    assert np.allclose(d, d[0:1], rtol=1e-6), tag
                    leaves = jax.tree.leaves(out)
                    dis = sum(float(jnp.sum(jnp.square(
                        l - jnp.mean(l, 0, keepdims=True)))) for l in leaves) / K
                    np.testing.assert_allclose(float(d[0, -1]), dis,
                                               rtol=1e-3, atol=1e-5,
                                               err_msg=tag)
                    acc = collective_bytes_per_step(topo, template,
                                                    "permute", codec)
                    got = np.asarray(cm.wire_recv_bytes)
                    np.testing.assert_allclose(got, float(acc["recv_bytes"]),
                                               err_msg=tag)
                    np.testing.assert_allclose(np.asarray(cm.wire_send_bytes),
                                               float(acc["recv_bytes"]),
                                               err_msg=tag)
                    want_edges = float(np.sum(topo.adjacency)) / 2
                    np.testing.assert_allclose(np.asarray(cm.edges),
                                               want_edges, err_msg=tag)
        print("PERMUTE-TELEMETRY-OK")
    """, devices=4)
    assert "PERMUTE-TELEMETRY-OK" in out


def test_permute_train_step_threads_codec_state():
    """End-to-end: the permute engine inside shard_map threads the top-k
    error-feedback residual through TrainState.comm, sharded like params."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import ring
        from repro.core.decentralized import TrainerConfig
        from repro.launch.train import make_train_step, init_train_state
        from repro.launch import sharding as shr
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
        print("PERMUTE-CODEC-STATE-OK")
    """)
    assert "PERMUTE-CODEC-STATE-OK" in out


def test_permute_consensus_control():
    """Consensus control on the ppermute engine, real 8-device mesh:
    momentum=0 / round_tol=None match the control-free engine bitwise,
    momentum accelerates ring mixing, and the adaptive gate freezes the
    iterate with a correct effective_rounds count."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import ring, DRTConfig
        from repro.core.consensus import PermuteConsensus
        from repro.obs.metrics import ObsConfig
        from repro.utils.pytree import LayerPartition

        K = 8
        mesh = jax.make_mesh((K,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

        def tree_init(k):
            k1, k2 = jax.random.split(k)
            return {"embed": {"w": jax.random.normal(k1, (4, 8))},
                    "blocks": {"w": jax.random.normal(k2, (3, 8, 8))}}

        pK = jax.vmap(tree_init)(jax.random.split(jax.random.key(0), K))
        part = LayerPartition.build(jax.tree.map(lambda x: x[0], pK))
        topo = ring(K)
        spec = jax.tree.map(lambda _: P("data"), pK)

        def dis(tree_K):
            return sum(
                float(np.sum(np.square(
                    np.asarray(l, np.float64)
                    - np.asarray(l, np.float64).mean(0, keepdims=True))))
                for l in jax.tree.leaves(tree_K)) / K

        def apply(eng, rounds, obs=None):
            def body(local):
                sq = jax.tree.map(lambda x: x[0], local)
                if obs is None:
                    out = eng(sq, rounds=rounds)
                    return jax.tree.map(lambda x: x[None], out)
                out, cm = eng(sq, rounds=rounds, obs=obs)
                return (jax.tree.map(lambda x: x[None], out),
                        jax.tree.map(lambda x: x[None], cm))
            out_specs = spec if obs is None else (spec, P("data"))
            return shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=out_specs, check_vma=False)(pK)

        base = PermuteConsensus(part, topo, DRTConfig(), axis_name="data")
        zero = PermuteConsensus(part, topo, DRTConfig(), axis_name="data",
                                momentum=0.0, round_tol=None)
        w_base = apply(base, 6)
        w_zero = apply(zero, 6)
        for a, b in zip(jax.tree.leaves(w_base), jax.tree.leaves(w_zero)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        mom = PermuteConsensus(part, topo, DRTConfig(), axis_name="data",
                               momentum=0.4)
        w_mom = apply(mom, 6)
        assert dis(w_mom) < 0.5 * dis(w_base), (dis(w_mom), dis(w_base))

        tol = dis(w_base) * 4
        adapt = PermuteConsensus(part, topo, DRTConfig(), axis_name="data",
                                 round_tol=tol)
        w_ad, cm = apply(adapt, 6, obs=ObsConfig())
        eff = np.asarray(cm.effective_rounds)[0]  # agent 0's view
        assert 1 <= eff[-1] < 6, eff
        assert dis(w_ad) <= tol
        print("PERMUTE-CONTROL-OK")
    """)
    assert "PERMUTE-CONTROL-OK" in out


@pytest.mark.slow
def test_dryrun_entrypoint_smoke():
    """The real dry-run entry point lowers+compiles one (arch x shape) on the
    production 16x16 mesh inside this subprocess (512 fake devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "hymba-1.5b",
         "--shape", "decode_32k", "--out", "/tmp/_dryrun_test.json"],
        capture_output=True, text=True, env=env, timeout=1800,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert out.returncode == 0, out.stdout + out.stderr[-2000:]
    row = json.load(open("/tmp/_dryrun_test.json"))[0]
    assert row["status"] == "OK"
    assert row["chips"] == 256
    assert row["t_compute_s"] > 0 and row["hlo_flops_per_dev"] > 0


def test_shard_edge_round_matches_unsharded_kernel():
    """``shard_edge_round`` (destination-sharded self slab / CSR tables /
    output, replicated wire + edge list, per-shard dst_base offset) is
    bit-identical to the unsharded wire-resident kernel on a real 8-way
    data mesh, and the combined slab comes back sharded along agents."""
    out = _run("""
        import os, sys
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        import repro  # namespace package: locate the repo via __path__
        _root = os.path.dirname(os.path.dirname(
            os.path.abspath(list(repro.__path__)[0])))
        sys.path.insert(0, os.path.join(_root, "tests"))
        from test_edge import _stack
        from repro.core import (DRTConfig, ring, edge_stacks_from_topology,
                                max_in_degree_from_topology)
        from repro.core.dynamic import csr_from_edges
        from repro.core import packing
        from repro.core.consensus import _layout_col_maps
        from repro.kernels import slab_edge_encode_combine
        from repro.launch.sharding import shard_edge_round

        K = 8
        pK, part, layout = _stack(K=K)
        regions = layout.pack_regions(pK)
        topo = ring(K)
        edges = edge_stacks_from_topology(topo, 1)
        src, dst, w = edges.src[0], edges.dst[0], edges.w[0]
        dmax = max_in_degree_from_topology(topo)
        nbr, pos, valid, _ = csr_from_edges(src, dst, w, K, dmax)
        bl = jnp.asarray(layout.block_layer)
        slab = layout.join(regions)

        codec = packing.Int8StochasticCodec()
        keys = jax.vmap(jax.random.fold_in, (None, 0))(
            jax.random.key(0), jnp.arange(K))
        wire, _ = packing.slab_encode_batched(codec, layout, regions, (), keys)
        col_seg, _, _ = _layout_col_maps(layout)
        wire_ops = (layout.join(wire.q), wire.s, col_seg)
        cfg = DRTConfig()
        kw = dict(mode="int8", algorithm="drt",
                  num_layers=layout.num_layers, kappa=cfg.kappa,
                  N_clip=cfg.resolve_N(K), weight_mode=cfg.weight_mode,
                  lane=layout.lane)

        ref = slab_edge_encode_combine(
            bl, slab, wire_ops, src, dst, w, nbr, pos, valid, **kw)
        mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
        got = shard_edge_round(
            mesh, bl, slab, wire_ops, src, dst, w, nbr, pos, valid, **kw)
        for r, g, n in zip(ref, got, ("out", "As", "Ae")):
            err = float(jnp.max(jnp.abs(r - g)))
            assert err == 0.0, (n, err)
        assert "data" in str(got[0].sharding.spec)
        print("SHARD-EDGE-OK")
    """)
    assert "SHARD-EDGE-OK" in out

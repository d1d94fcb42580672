"""Engine parity on a real mesh: the ppermute neighbour-exchange engine
(``PermuteConsensus`` inside ``shard_map``) against the all-gather engine,
one agent per fake CPU device.

Each ``_case_*`` function runs in its own process (``run_multidevice``).
Both engines run under ``jax.jit``, as ``make_train_step`` and the trainer
run them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from _harness import run_multidevice

CODECS = ("identity", "bf16", "f16", "int8", "topk:0.25")


def _agents(K):
    """K agents of a two-layer tree, one per device of a K-device mesh."""
    from repro.utils.pytree import LayerPartition

    mesh = jax.make_mesh((K,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))

    def tree_init(k):
        k1, k2 = jax.random.split(k)
        return {"embed": {"w": jax.random.normal(k1, (4, 8))},
                "blocks": {"w": jax.random.normal(k2, (3, 8, 8))}}

    pK = jax.vmap(tree_init)(jax.random.split(jax.random.key(0), K))
    part = LayerPartition.build(jax.tree.map(lambda x: x[0], pK))
    return mesh, pK, part


def _per_agent(mesh, pK, fn, out_specs=None, check_vma=False):
    """``fn`` (one agent's tree -> outputs) over every agent, jitted
    ``shard_map`` on ``mesh``; outputs come back agent-stacked."""
    specs = jax.tree.map(lambda _: P("data"), pK)

    def body(local):
        out = fn(jax.tree.map(lambda x: x[0], local))
        return jax.tree.map(lambda x: x[None], out)

    return jax.jit(shard_map(body, mesh=mesh, in_specs=(specs,),
                             out_specs=specs if out_specs is None else out_specs,
                             check_vma=check_vma))(pK)


def _assert_close(got, want, msg=""):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-5, err_msg=msg)


def _case_permute_matches_gather():
    from repro.core import DRTConfig, hypercube, ring
    from repro.core.consensus import PermuteConsensus, gather_consensus_step

    K = 8
    mesh, pK, part = _agents(K)
    cfg = DRTConfig()
    for topo in (ring(K), hypercube(K)):
        C = jnp.asarray(topo.c_matrix(), jnp.float32)
        metro = jnp.asarray(topo.metropolis(), jnp.float32)
        want = jax.jit(lambda p: gather_consensus_step(part, p, C, cfg, algorithm="drt")[0])(pK)
        eng = PermuteConsensus(part, topo, cfg, axis_name="data")
        _assert_close(_per_agent(mesh, pK, eng, check_vma=True), want)
        # classical engine too
        wantc = jax.jit(lambda p: gather_consensus_step(
            part, p, C, cfg, algorithm="classical", metropolis=metro)[0])(pK)
        engc = PermuteConsensus(part, topo, cfg, axis_name="data", algorithm="classical")
        _assert_close(_per_agent(mesh, pK, engc, check_vma=True), wantc)


def test_permute_consensus_matches_gather_engine():
    """The optimized ppermute neighbour-exchange engine produces the SAME
    mixing weights and combined parameters as the paper-faithful all-gather
    engine (ring and hypercube), executed on a real 8-device mesh."""
    run_multidevice(_case_permute_matches_gather)


def _case_every_codec(codec):
    from repro.core import DRTConfig, hypercube, ring, torus2d
    from repro.core.consensus import PermuteConsensus, gather_consensus_rounds

    K = 4
    mesh, pK, part = _agents(K)
    rng = jax.random.key(7)
    cfg = DRTConfig()
    for topo in (ring(K), hypercube(K), torus2d(K)):
        C = jnp.asarray(topo.c_matrix(), jnp.float32)
        for path, rounds in (("slab", 1), ("slab", 3), ("tree", 1)):
            want = jax.jit(lambda p: gather_consensus_rounds(
                part, p, C, cfg, algorithm="drt", codec=codec,
                rng=rng, rounds=rounds, path=path)[0])(pK)
            eng = PermuteConsensus(part, topo, cfg, axis_name="data",
                                   codec=codec, path=path)
            got = _per_agent(mesh, pK, lambda sq: eng(sq, rng=rng, rounds=rounds)[0])
            _assert_close(got, want, f"{topo.name}/{codec}/{path}/r{rounds}")


@pytest.mark.parametrize("codec", CODECS)
def test_engines_agree_under_every_codec(codec):
    """Acceptance: gather and permute engines produce matching combined
    parameters (within codec tolerance) for EVERY registered codec on
    ring / hypercube / torus2d, on both the slab hot path and the per-leaf
    tree oracle, including multi-round round-sets.  Both engines share the
    fold_in(fold_in(rng, round), agent) key derivation, so stochastic codecs
    emit identical wire slabs/trees and the engines agree to
    collective-reduction-order noise, not codec noise."""
    run_multidevice(_case_every_codec, codec, devices=4)


def _case_dynamic_schedule(codec):
    from repro.core import ChurnSchedule, DRTConfig, PeriodicSchedule, hypercube, ring
    from repro.core.consensus import PermuteConsensus, gather_consensus_rounds

    K = 4
    mesh, pK, part = _agents(K)
    rng = jax.random.key(7)
    sched = ChurnSchedule(PeriodicSchedule((ring(K), hypercube(K))),
                          agent_drop=0.25, seed=9)
    Cs, Ms = sched.mixing_stacks(2, 3)
    if codec == "classical":
        # classical too (identity wire): churned Metropolis agrees
        want = jax.jit(lambda p: gather_consensus_rounds(
            part, p, Cs, DRTConfig(), algorithm="classical", metropolis=Ms,
            rounds=3, path="slab")[0])(pK)
        eng = PermuteConsensus(part, ring(K), DRTConfig(), axis_name="data",
                               algorithm="classical", schedule=sched)
        got = _per_agent(mesh, pK, lambda sq: eng(sq, rounds=3, start_round=2))
        _assert_close(got, want)
        return
    for path in ("slab", "tree"):
        want = jax.jit(lambda p: gather_consensus_rounds(
            part, p, Cs, DRTConfig(), algorithm="drt", metropolis=Ms,
            codec=codec, rng=rng, rounds=3, path=path)[0])(pK)
        eng = PermuteConsensus(part, ring(K), DRTConfig(), axis_name="data",
                               codec=codec, path=path, schedule=sched)
        got = _per_agent(mesh, pK, lambda sq: eng(sq, rng=rng, rounds=3, start_round=2)[0])
        _assert_close(got, want, f"{codec}/{path}")


@pytest.mark.parametrize("codec", CODECS + ("classical",))
def test_engines_agree_under_dynamic_schedule_every_codec(codec):
    """Satellite parity matrix under a CHANGING per-round mixing matrix:
    the permute engine (re-deriving its decomposition per round, masking
    churn-dropped agents) matches the gather engine driven by the same
    schedule's (C_t, metropolis_t) stacks — every codec, slab and tree
    paths, 3-round round-sets starting mid-sequence; the ``classical`` case
    is the classical algorithm on the identity wire."""
    run_multidevice(_case_dynamic_schedule, codec, devices=4)


def _case_telemetry():
    from repro.comm.accounting import collective_bytes_per_step
    from repro.core import DRTConfig, chain, ring
    from repro.core.consensus import PermuteConsensus
    from repro.obs.metrics import ObsConfig

    K = 4
    mesh, pK, part = _agents(K)
    rng = jax.random.key(7)
    template = jax.tree.map(lambda x: x[0], pK)
    specs = jax.tree.map(lambda _: P("data"), pK)

    for topo in (ring(K), chain(K)):
        for codec in (None, "int8", "topk:0.1:0"):
            for path in ("slab", "tree"):
                eng = PermuteConsensus(part, topo, DRTConfig(), axis_name="data",
                                       codec=codec, path=path)

                def fn(sq):
                    if codec:
                        out, _, cm = eng(sq, rng=rng, rounds=2, obs=ObsConfig())
                    else:
                        out, cm = eng(sq, rounds=2, obs=ObsConfig())
                    return out, cm

                out, cm = _per_agent(mesh, pK, fn, out_specs=(specs, P("data")))
                tag = f"{topo.name}/{codec}/{path}"
                assert cm.disagreement.shape == (K, 2), tag
                d = np.asarray(cm.disagreement)
                assert np.allclose(d, d[0:1], rtol=1e-6), tag
                leaves = jax.tree.leaves(out)
                dis = sum(float(jnp.sum(jnp.square(
                    l - jnp.mean(l, 0, keepdims=True)))) for l in leaves) / K
                np.testing.assert_allclose(float(d[0, -1]), dis,
                                           rtol=1e-3, atol=1e-5, err_msg=tag)
                acc = collective_bytes_per_step(topo, template, "permute", codec)
                np.testing.assert_allclose(np.asarray(cm.wire_recv_bytes),
                                           float(acc["recv_bytes"]), err_msg=tag)
                np.testing.assert_allclose(np.asarray(cm.wire_send_bytes),
                                           float(acc["recv_bytes"]), err_msg=tag)
                want_edges = float(np.sum(topo.adjacency)) / 2
                np.testing.assert_allclose(np.asarray(cm.edges), want_edges,
                                           err_msg=tag)


def test_permute_telemetry_matches_analytic_and_direct():
    """PermuteConsensus(obs=...) on a real 4-device mesh: the per-agent
    runtime wire counters equal ``comm.accounting.collective_bytes_per_step``
    for every codec — including the chain graph, whose analytic row now uses
    the same greedy matching decomposition the engine actually runs — and
    the (psum'd, agent-replicated) global disagreement matches the direct
    mean_k |x_k - xbar|^2 of the round output."""
    run_multidevice(_case_telemetry, devices=4)


def _case_control():
    from repro.core import DRTConfig, ring
    from repro.core.consensus import PermuteConsensus
    from repro.obs.metrics import ObsConfig

    K = 8
    mesh, pK, part = _agents(K)
    topo = ring(K)
    specs = jax.tree.map(lambda _: P("data"), pK)

    def dis(tree_K):
        return sum(
            float(np.sum(np.square(
                np.asarray(l, np.float64)
                - np.asarray(l, np.float64).mean(0, keepdims=True))))
            for l in jax.tree.leaves(tree_K)) / K

    def apply(eng, rounds, obs=None):
        if obs is None:
            return _per_agent(mesh, pK, lambda sq: eng(sq, rounds=rounds))
        return _per_agent(mesh, pK, lambda sq: eng(sq, rounds=rounds, obs=obs),
                          out_specs=(specs, P("data")))

    base = PermuteConsensus(part, topo, DRTConfig(), axis_name="data")
    zero = PermuteConsensus(part, topo, DRTConfig(), axis_name="data",
                            momentum=0.0, round_tol=None)
    w_base = apply(base, 6)
    w_zero = apply(zero, 6)
    for a, b in zip(jax.tree.leaves(w_base), jax.tree.leaves(w_zero)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    mom = PermuteConsensus(part, topo, DRTConfig(), axis_name="data", momentum=0.4)
    w_mom = apply(mom, 6)
    assert dis(w_mom) < 0.5 * dis(w_base), (dis(w_mom), dis(w_base))

    tol = dis(w_base) * 4
    adapt = PermuteConsensus(part, topo, DRTConfig(), axis_name="data", round_tol=tol)
    w_ad, cm = apply(adapt, 6, obs=ObsConfig())
    eff = np.asarray(cm.effective_rounds)[0]  # agent 0's view
    assert 1 <= eff[-1] < 6, eff
    assert dis(w_ad) <= tol


def test_permute_consensus_control():
    """Consensus control on the ppermute engine, real 8-device mesh:
    momentum=0 / round_tol=None match the control-free engine bitwise,
    momentum accelerates ring mixing, and the adaptive gate freezes the
    iterate with a correct effective_rounds count."""
    run_multidevice(_case_control)

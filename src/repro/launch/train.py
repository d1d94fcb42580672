"""Distributed decentralized training step + driver.

``make_train_step`` builds the pure step function the pod runtime and the
multi-pod dry-run lower: one local SGD step per agent (vmapped over the
agent-stacked tree, sharded over the mesh ``data`` axis) followed by
``consensus_rounds`` DRT/classical combination rounds (the paper's cadence —
a local epoch then 3 rounds — is a driver-level choice; the lowered step uses
1 round, representative of the per-step production cadence, configurable).

``make_train_many_steps`` scans that step ``n_steps`` times inside ONE
jitted, buffer-donated device program — per-step host dispatch is paid once
per chunk, state buffers are reused in place, and the result is
bit-identical to per-step calls (``--steps-per-call`` on the CLI).

Run it CPU-locally (simulator): ``python -m repro.launch.train --help``.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import WireCodec, init_comm_state, make_codec
from repro.core import consensus as _consensus
from repro.core.consensus import gather_consensus_rounds
from repro.core.decentralized import TrainerConfig
from repro.core.dynamic import (
    edge_stacks_from_topology,
    make_round_policy,
    make_schedule,
    max_in_degree_from_topology,
)
from repro.core.packing import (
    build_slab_layout,
    slab_codec_supported,
    slab_template_supported,
)
from repro.core.topology import Topology, make_topology
from repro.models.registry import ModelBundle
from repro.optim.optimizers import Optimizer
from repro.utils.pytree import LayerPartition

PyTree = Any
SDS = jax.ShapeDtypeStruct


class TrainState(NamedTuple):
    params: PyTree  # leading agent axis K
    opt_state: PyTree
    step: jax.Array
    comm: PyTree = ()  # per-agent wire-codec state (error-feedback residuals)


def _resolve_train_codec(codec) -> "WireCodec | None":
    return None if codec is None else make_codec(codec)


def abstract_train_state(
    bundle: ModelBundle, optimizer: Optimizer, codec=None
) -> TrainState:
    """Allocation-free state template (ShapeDtypeStructs)."""
    K = bundle.cfg.num_agents
    p1 = jax.eval_shape(bundle.init, jax.random.key(0))
    params = jax.tree.map(lambda s: SDS((K, *s.shape), s.dtype), p1)
    opt_state = jax.eval_shape(optimizer.init, params)
    comm = jax.eval_shape(lambda p: init_comm_state(codec, p), params)
    return TrainState(params, opt_state, SDS((), jnp.int32), comm)


def init_train_state(
    bundle: ModelBundle, optimizer: Optimizer, key, codec=None
) -> TrainState:
    K = bundle.cfg.num_agents
    p1 = bundle.init(key)
    params = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (K, *x.shape)).copy(), p1)
    return TrainState(
        params, optimizer.init(params), jnp.zeros((), jnp.int32), init_comm_state(codec, params)
    )


def build_partition(bundle: ModelBundle) -> LayerPartition:
    p1 = jax.eval_shape(bundle.init, jax.random.key(0))
    return LayerPartition.build(p1)


def make_train_step(
    bundle: ModelBundle,
    topology: Topology,
    optimizer: Optimizer,
    tcfg: TrainerConfig = TrainerConfig(),
    consensus_rounds: int = 1,
    consensus_impl: str = "gather",
    exchange_dtype=None,
    codec=None,
    mesh=None,
    param_specs=None,
    obs=None,
):
    """Returns step(state, batch_K, key) -> (state, metrics).

    Consensus engines (§Perf beyond-paper optimizations):
      * ``gather``  — paper-faithful baseline: all-gather + masked einsums.
      * ``permute`` — neighbour-only ``ppermute`` exchange inside shard_map
        (requires ``mesh`` + ``param_specs``; K must equal the data-axis
        size).  Collective volume scales with n_k instead of K.

    ``codec`` (a ``repro.comm`` codec name or instance, also settable via
    ``tcfg.codec``) compresses the consensus exchange of either engine;
    stateful codecs (top-k error feedback) thread their per-agent residual
    through ``state.comm``.  ``exchange_dtype`` is the deprecated spelling of
    ``codec='bf16'``.

    On ``tcfg.consensus_path="slab"`` (the default) both engines pack the
    parameters into the flat slab ONCE per step, run every consensus round on
    it, and unpack once — see :mod:`repro.core.packing`;
    ``tcfg.use_kernels=True`` routes the slab inner loops through the Pallas
    kernels.

    ``tcfg.schedule`` (a :class:`repro.core.dynamic.TopologySchedule` or spec
    string) makes the communication graph time varying: consensus round ``r``
    of step ``s`` mixes over graph ``s * consensus_rounds + r``.  The gather
    engine realizes the schedule as traced per-round ``(C_t, metropolis_t)``
    stacks indexed by ``state.step``; the permute engine re-derives its
    ppermute decomposition on the HOST and therefore cannot follow a dynamic
    schedule from inside a jitted step — pass ``consensus_impl="gather"``
    (static schedules are folded into the topology and remain fine).

    Consensus control: ``tcfg.consensus_momentum`` adds heavy-ball momentum
    across the combination rounds of either engine, and ``tcfg.rounds_policy``
    (``fixed:<n>`` / ``adaptive:<tol>:<max>``) overrides ``consensus_rounds``
    — an adaptive policy still traces ``max`` rounds (compile O(1) in
    rounds) but gates each on the carried disagreement.  Both default off
    and then trace today's exact program.

    ``obs`` (an :class:`repro.obs.ObsConfig`) threads in-graph consensus
    telemetry through the step: ``metrics["consensus"]`` carries a
    per-round :class:`repro.obs.ConsensusMetrics` stack (gather: global
    ``(rounds, ...)`` leaves; permute: per-agent ``(K, rounds, ...)``
    leaves).  ``obs=None`` (default) traces the exact pre-telemetry step —
    telemetry is zero-cost when disabled.
    """
    cfg = bundle.cfg
    K = cfg.num_agents
    if topology.num_agents != K:
        raise ValueError(f"topology K={topology.num_agents} != cfg K={K}")
    policy = make_round_policy(tcfg.rounds_policy)
    if policy is not None:
        # the policy owns the round budget; consensus_rounds stays the legacy
        # fixed-count spelling
        consensus_rounds = policy.max_rounds
    round_tol = policy.tol if policy is not None else None
    if consensus_rounds < 1:
        raise ValueError(
            f"make_train_step needs consensus_rounds >= 1, got "
            f"{consensus_rounds}"
        )
    partition = build_partition(bundle)
    schedule = (
        make_schedule(tcfg.schedule, K) if tcfg.schedule is not None else None
    )
    if schedule is not None and schedule.static:
        # a static schedule IS a static topology: fold it in and take the
        # schedule-free (bit-identical) path on the schedule's graph
        topology = schedule.topology_at(0)
        schedule = None
    from repro.faults import DropSchedule, make_fault_plan

    fault_plan = make_fault_plan(
        K,
        byzantine=tcfg.byzantine,
        fault_model=tcfg.fault_model,
        stale=tcfg.stale,
        seed=tcfg.fault_seed,
    )
    use_faults = fault_plan is not None or tcfg.drop > 0.0
    if consensus_impl == "permute" and (use_faults or tcfg.combine != "drt"):
        raise ValueError(
            "fault injection and the robust combines are gather-engine "
            "features (the permute engine never holds the (K, D) stack to "
            "mask); use consensus_impl='gather' — trust_clip/trust_temp "
            "work on either engine"
        )
    if tcfg.drop > 0.0:
        from repro.core.dynamic import StaticSchedule

        schedule = DropSchedule(
            schedule if schedule is not None else StaticSchedule(topology),
            tcfg.drop,
            seed=tcfg.fault_seed,
        )
    C = jnp.asarray(topology.c_matrix(), jnp.float32)
    metro = jnp.asarray(topology.metropolis(), jnp.float32)
    if codec is None:
        codec = tcfg.codec
    wire_codec = _resolve_train_codec(codec)
    if wire_codec is not None and exchange_dtype is not None:
        raise ValueError("pass either codec or (deprecated) exchange_dtype, not both")

    if consensus_impl == "permute":
        if schedule is not None:
            raise ValueError(
                "the permute engine re-derives its ppermute decomposition on "
                "the host and cannot follow a dynamic schedule from a jitted "
                "step; use consensus_impl='gather' (or drive "
                "PermuteConsensus(schedule=...) with a concrete start_round "
                "outside jit)"
            )
        if tcfg.consensus_path == "edge":
            raise ValueError(
                "the edge-list path is a gather-engine hot path (the permute "
                "engine already exchanges neighbour-only traffic); use "
                "consensus_impl='gather' with consensus_path='edge'"
            )
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from repro.core.consensus import PermuteConsensus

        if mesh is None or param_specs is None:
            raise ValueError("permute consensus needs mesh + param_specs")
        if K != dict(zip(mesh.axis_names, mesh.devices.shape))["data"]:
            raise ValueError("permute consensus requires K == |data| (one agent/shard)")
        inner_axes = tuple(a for a in mesh.axis_names if a not in ("data", "pod"))
        engine = PermuteConsensus(
            partition,
            topology,
            tcfg.drt,
            axis_name="data",
            algorithm=tcfg.algorithm,
            norm_reduce_axes=inner_axes,
            exchange_dtype=exchange_dtype,
            codec=wire_codec,
            path=tcfg.consensus_path,
            use_kernels=tcfg.use_kernels,
            momentum=tcfg.consensus_momentum,
            round_tol=round_tol,
            trust_clip=tcfg.trust_clip,
            trust_temp=tcfg.trust_temp,
        )
        # codec state mirrors the params leaf-for-leaf -> identical sharding
        comm_specs = (
            param_specs if wire_codec is not None and wire_codec.stateful else ()
        )

        if wire_codec is None:

            # pack once, run ALL rounds on the slab inside one shard_map call
            def consensus(params, comm, ckey, step):
                def body(local):
                    sq = jax.tree.map(lambda x: x[0], local)
                    if obs is None:
                        out = engine(sq, rounds=consensus_rounds)
                        return jax.tree.map(lambda x: x[None], out)
                    out, cm = engine(sq, rounds=consensus_rounds, obs=obs)
                    return (
                        jax.tree.map(lambda x: x[None], out),
                        jax.tree.map(lambda x: x[None], cm),
                    )

                if obs is None:
                    new = shard_map(
                        body, mesh=mesh, in_specs=(param_specs,),
                        out_specs=param_specs, check_vma=False,
                    )(params)
                    return new, comm, None
                # metrics come back as per-agent (K, rounds, ...) stacks:
                # each shard emits its local view with a leading length-1
                # agent axis, gathered over the data mesh axis
                new, cm = shard_map(
                    body, mesh=mesh, in_specs=(param_specs,),
                    out_specs=(param_specs, P("data")), check_vma=False,
                )(params)
                return new, comm, cm

        else:

            def consensus(params, comm, ckey, step):
                def body(local, lcomm, k):
                    sq = jax.tree.map(lambda x: x[0], local)
                    sc = jax.tree.map(lambda x: x[0], lcomm)
                    if obs is None:
                        out, nc = engine(
                            sq, codec_state=sc, rng=k, rounds=consensus_rounds
                        )
                        return (
                            jax.tree.map(lambda x: x[None], out),
                            jax.tree.map(lambda x: x[None], nc),
                        )
                    out, nc, cm = engine(
                        sq, codec_state=sc, rng=k, rounds=consensus_rounds,
                        obs=obs,
                    )
                    return (
                        jax.tree.map(lambda x: x[None], out),
                        jax.tree.map(lambda x: x[None], nc),
                        jax.tree.map(lambda x: x[None], cm),
                    )

                if obs is None:
                    new, nc = shard_map(
                        body,
                        mesh=mesh,
                        in_specs=(param_specs, comm_specs, P()),
                        out_specs=(param_specs, comm_specs),
                        check_vma=False,
                    )(params, comm, ckey)
                    return new, nc, None
                return shard_map(
                    body,
                    mesh=mesh,
                    in_specs=(param_specs, comm_specs, P()),
                    out_specs=(param_specs, comm_specs, P("data")),
                    check_vma=False,
                )(params, comm, ckey)

    else:
        # the deprecated exchange_dtype spelling resolves to the cast codec
        # here (warning once, at build time); the key flow below still follows
        # the original wire_codec so stochastic-codec rng handling is unchanged
        effective_codec = (
            _consensus._resolve_codec(None, exchange_dtype)
            if exchange_dtype is not None
            else wire_codec
        )
        layout = None
        p1_template = jax.eval_shape(bundle.init, jax.random.key(0))
        if (
            tcfg.consensus_path in ("slab", "edge")
            and slab_codec_supported(effective_codec)
            and slab_template_supported(p1_template)
        ):
            layout = build_slab_layout(partition, p1_template)

        def consensus(params, comm, ckey, step):
            if schedule is None:
                C_t, metro_t = C, metro
            else:
                # per-round graph stacks, traced off the step counter
                C_t, metro_t = schedule.mixing_stacks(
                    step * consensus_rounds, consensus_rounds
                )
            edges = None
            max_in_degree = None
            if tcfg.consensus_path == "edge":
                # the sparse view of the SAME round-set graphs (bit
                # consistent with the dense stacks by the schedule contract);
                # the host Dmax bound keys the gather-only CSR combine
                if schedule is None:
                    edges = edge_stacks_from_topology(topology, consensus_rounds)
                    max_in_degree = max_in_degree_from_topology(topology)
                else:
                    edges = schedule.edge_stacks(
                        step * consensus_rounds, consensus_rounds
                    )
                    max_in_degree = schedule.max_in_degree
            out = gather_consensus_rounds(
                partition,
                params,
                C_t,
                tcfg.drt,
                rounds=consensus_rounds,
                algorithm=tcfg.algorithm,
                metropolis=metro_t,
                codec=effective_codec,
                codec_state=comm,
                rng=ckey,
                layout=layout,
                path=tcfg.consensus_path,
                edges=edges,
                max_in_degree=max_in_degree,
                use_kernels=tcfg.use_kernels,
                momentum=tcfg.consensus_momentum,
                round_tol=round_tol,
                faults=(
                    fault_plan.realize(
                        step * consensus_rounds, consensus_rounds
                    )
                    if fault_plan is not None
                    else None
                ),
                trust_clip=tcfg.trust_clip,
                trust_temp=tcfg.trust_temp,
                combine=tcfg.combine,
                obs=obs,
            )
            if obs is None:
                new, _, new_comm = out
                cm = None
            else:
                new, _, new_comm, cm = out
            return new, comm if effective_codec is None else new_comm, cm

    def step(state: TrainState, batch_K, key):
        if wire_codec is None:
            lkey = ckey = key  # identical key flow to the pre-codec step
        else:
            lkey, ckey = jax.random.split(key)
        keys = jax.random.split(lkey, K)
        losses, grads = jax.vmap(jax.value_and_grad(bundle.loss))(
            state.params, batch_K, keys
        )
        params, opt_state = optimizer.update(
            grads, state.opt_state, state.params, state.step
        )
        comm = state.comm
        if (
            wire_codec is not None
            and wire_codec.stateful
            and (comm is None or comm == ())
        ):
            # state was built without the codec (init_train_state codec kwarg
            # not passed): initialize the residual here, matching the gather
            # engine's auto-init, instead of tripping a shard_map spec mismatch
            comm = init_comm_state(wire_codec, params)
        params, comm, cm = consensus(params, comm, ckey, state.step)
        metrics = {"loss": jnp.mean(losses)}
        if cm is not None:
            metrics["consensus"] = cm
        return TrainState(params, opt_state, state.step + 1, comm), metrics

    return step


def make_train_many_steps(
    bundle: ModelBundle,
    topology: Topology,
    optimizer: Optimizer,
    tcfg: TrainerConfig = TrainerConfig(),
    consensus_rounds: int = 1,
    consensus_impl: str = "gather",
    codec=None,
    mesh=None,
    param_specs=None,
    donate: bool = True,
    obs=None,
):
    """Donated multi-step driver: a CHUNK of train steps as ONE device program.

    Returns ``many(state, batches_K, keys) -> (state, {"loss": (n,)})`` where
    ``batches_K`` leaves carry a leading ``(n_steps, K, ...)`` step axis and
    ``keys`` is the ``(n_steps,)`` stack of exactly the per-step keys the
    single-step driver would pass.  The body is :func:`make_train_step`'s
    step scanned ``n_steps`` times, so the result is bit-identical to
    ``n_steps`` successive single-step calls — the consensus rng and a
    dynamic schedule's round indices derive from the CARRIED ``state.step``
    (round ``t = step * consensus_rounds + r``), which makes chunk
    boundaries, ragged tails and checkpoint resume mid-chunk invisible to
    the math.  Combined with the scanned round-sets inside each consensus
    call, a whole chunk traces/compiles O(1) in both ``n_steps`` and
    ``consensus_rounds`` and issues ONE host dispatch.

    ``donate=True`` (default) returns the function jitted with
    ``donate_argnums=(0,)``: XLA reuses the state buffers (params, optimizer
    state, EF residuals) across the chunk instead of allocating a fresh copy
    per step — at large K x D the allocator traffic per step drops to zero.
    Pass ``donate=False`` to get the plain function (e.g. to compose it
    under an outer jit or shard_map with explicit shardings).

    With ``obs`` set the result gains ``metrics["consensus"]``: the per-step
    :class:`repro.obs.ConsensusMetrics` stacks, scanned into leaves with a
    leading ``(n_steps,)`` axis (slice step ``j`` off with
    ``jax.tree.map(lambda x: x[j], cm)`` before handing it to
    :func:`repro.obs.consensus_records`).
    """
    step = make_train_step(
        bundle,
        topology,
        optimizer,
        tcfg,
        consensus_rounds=consensus_rounds,
        consensus_impl=consensus_impl,
        codec=codec,
        mesh=mesh,
        param_specs=param_specs,
        obs=obs,
    )

    def many(state: TrainState, batches_K, keys):
        def body(st, inp):
            batch, key = inp
            st, metrics = step(st, batch, key)
            if obs is None:
                return st, metrics["loss"]
            return st, (metrics["loss"], metrics["consensus"])

        state, ys = jax.lax.scan(body, state, (batches_K, keys))
        if obs is None:
            return state, {"loss": ys}
        losses, cm = ys
        return state, {"loss": losses, "consensus": cm}

    return jax.jit(many, donate_argnums=(0,)) if donate else many


# ---------------------------------------------------------------------------
# CPU driver (simulator-scale presets)
# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    from repro.data.synthetic import SyntheticTokenStream, TokenStreamConfig
    from repro.models.registry import get_bundle
    from repro.optim import momentum
    from repro.utils.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description="decentralized LM training (CPU simulator)")
    ap.add_argument("--arch", default="qwen3-8b-smoke")
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--algorithm", default="drt", choices=["drt", "classical"])
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--consensus-rounds", type=int, default=1)
    ap.add_argument(
        "--consensus-momentum", type=float, default=0.0,
        help="heavy-ball momentum beta on the combination rounds: "
             "x_{t+1} = mix(x_t) + beta (x_t - x_{t-1}); 0.0 (default) "
             "traces the momentum-free program bit-identically",
    )
    ap.add_argument(
        "--rounds-policy", default=None,
        help="per-step round budget: 'fixed:<n>' or 'adaptive:<tol>:<max>' "
             "(stop early once per-round disagreement drops below tol; extra "
             "rounds become in-graph no-ops, compile stays O(1) in rounds); "
             "overrides --consensus-rounds",
    )
    ap.add_argument(
        "--steps-per-call", type=int, default=1,
        help="train steps fused into ONE jitted, buffer-donated device "
             "program (make_train_many_steps); amortizes per-step host "
             "dispatch — bit-identical to per-step calls (a ragged final "
             "chunk recompiles once for its smaller length)",
    )
    ap.add_argument(
        "--consensus-path", default="slab", choices=["slab", "tree", "edge"],
        help="consensus hot path: 'slab' = dense flat-slab rounds (default), "
             "'edge' = sparse O(|E| D) edge-list rounds over the realized "
             "graph (the large-K path), 'tree' = per-leaf reference oracle",
    )
    ap.add_argument(
        "--codec", default=None,
        help="wire codec for the consensus exchange: identity|bf16|f16|int8|"
             "topk[:frac[:sample]] (default: exact f32 exchange; "
             "topk:0.1:0 = exact full-leaf thresholds instead of the "
             "subsampled default)",
    )
    ap.add_argument(
        "--schedule", default=None,
        help="time-varying communication graph: a topology name, "
             "'periodic:<a>,<b>[@n]', 'gossip[:p]' or 'onepeer' "
             "(default: the static --topology graph)",
    )
    ap.add_argument(
        "--agent-dropout", type=float, default=0.0,
        help="per-round probability an agent drops all its edges (it keeps "
             "its own iterate); wraps the schedule in a churn injector",
    )
    ap.add_argument(
        "--edge-dropout", type=float, default=0.0,
        help="per-round probability each surviving edge drops (symmetric)",
    )
    ap.add_argument("--schedule-seed", type=int, default=0,
                    help="seed for gossip draws and churn failures")
    ap.add_argument(
        "--byzantine", type=float, default=0.0,
        help="Byzantine agent fraction: floor(f * K) seeded agents publish "
             "through --fault-model every consensus round (requires "
             "--fault-model; 0.0 = off)",
    )
    ap.add_argument(
        "--fault-model", default=None,
        help="attack applied to Byzantine publications before encode: "
             "sign_flip | gauss:<sigma> | cgauss:<sigma> (colluding: one "
             "shared draw) | scale:<c> | constant[:<v>]",
    )
    ap.add_argument(
        "--fault-seed", type=int, default=0,
        help="seed for Byzantine membership, stochastic attacks and wire-"
             "fault tables (independent of the codec rng)",
    )
    ap.add_argument(
        "--drop", type=float, default=0.0,
        help="per-round probability each edge drops its message (symmetric, "
             "seeded by --fault-seed; composes with any --schedule)",
    )
    ap.add_argument(
        "--stale", type=float, default=0.0,
        help="per-round probability an agent's neighbours receive its "
             "previous-round iterate instead of the fresh one",
    )
    ap.add_argument(
        "--trust-clip", type=float, default=None,
        help="cap any neighbour's mixing weight at this value (excess trust "
             "moves to the agent's own iterate) — the DRT Byzantine defense",
    )
    ap.add_argument(
        "--trust-temp", type=float, default=None,
        help="temperature on the off-diagonal mixing weights (<1 sharpens "
             "trust differences, >1 flattens them)",
    )
    ap.add_argument(
        "--combine", default="drt",
        help="combine rule: 'drt' (default, weighted eq.12-14 mixing) | "
             "'trimmed:<f>' (coordinate-wise trimmed mean) | 'median' — the "
             "robust non-DRT baselines",
    )
    ap.add_argument(
        "--metrics-jsonl", default=None,
        help="enable in-graph consensus telemetry (repro.obs) and append one "
             "JSON record per consensus round to this file: disagreement "
             "mean|x_i - xbar|^2, per-layer DRT distance mean/max, mixing-"
             "weight entropy, error-feedback residual norm, wire send/recv "
             "bytes, compression ratio and live edge count, keyed by "
             "step/round; a console summary table prints at the end",
    )
    ap.add_argument(
        "--profile-dir", default=None,
        help="write a jax.profiler trace of the whole run to this directory "
             "(view in Perfetto / TensorBoard) and turn on named consensus "
             "spans (consensus.pack/encode/combine/unpack) so rounds are "
             "attributable in the timeline; implies telemetry on",
    )
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    if args.consensus_rounds < 1:
        ap.error(
            f"--consensus-rounds must be >= 1 (got {args.consensus_rounds}); "
            "the consensus engines refuse a zero-round exchange rather than "
            "silently no-op"
        )
    if not 0.0 <= args.consensus_momentum < 1.0:
        ap.error(
            f"--consensus-momentum must be in [0, 1) (got "
            f"{args.consensus_momentum}); the heavy-ball recurrence diverges "
            "at beta >= 1"
        )

    enable_compile_cache()
    bundle = get_bundle(args.arch, num_agents=args.agents)
    topo = make_topology(args.topology, args.agents)
    opt = momentum(args.lr, 0.9)
    schedule = make_schedule(
        args.schedule
        if args.schedule is not None
        else (args.topology if (args.agent_dropout or args.edge_dropout) else None),
        args.agents,
        agent_drop=args.agent_dropout,
        edge_drop=args.edge_dropout,
        seed=args.schedule_seed,
    )
    tcfg = TrainerConfig(
        algorithm=args.algorithm, codec=args.codec, schedule=schedule,
        consensus_path=args.consensus_path,
        consensus_momentum=args.consensus_momentum,
        rounds_policy=args.rounds_policy,
        byzantine=args.byzantine,
        fault_model=args.fault_model,
        fault_seed=args.fault_seed,
        stale=args.stale,
        drop=args.drop,
        trust_clip=args.trust_clip,
        trust_temp=args.trust_temp,
        combine=args.combine,
    )
    state = init_train_state(bundle, opt, jax.random.key(0), codec=args.codec)
    stream = SyntheticTokenStream(
        TokenStreamConfig(vocab=bundle.cfg.vocab, seq_len=args.seq)
    )

    from repro import obs as repro_obs
    from repro.obs.metrics import ObsConfig

    obs = (
        ObsConfig(annotate=args.profile_dir is not None)
        if (args.metrics_jsonl or args.profile_dir)
        else None
    )
    sink = repro_obs.JsonlSink(args.metrics_jsonl) if args.metrics_jsonl else None
    thru = repro_obs.Throughput()
    tokens_per_step = args.agents * args.batch * args.seq

    def emit(cm, step_idx: int) -> None:
        if sink is not None and cm is not None:
            for rec in repro_obs.consensus_records(cm, step=step_idx):
                sink.write(rec)

    # close the sink even when the loop raises (keyboard interrupt, OOM):
    # line-buffered JSONL means every completed round's record survives
    try:
        with repro_obs.trace(args.profile_dir):
            if args.steps_per_call > 1:
                many = make_train_many_steps(
                    bundle, topo, opt, tcfg,
                    consensus_rounds=args.consensus_rounds, obs=obs,
                )
                i = 0
                while i < args.steps:
                    n = min(args.steps_per_call, args.steps - i)
                    tokens = jnp.stack([
                        jnp.asarray(stream.agent_batches(args.batch, args.agents, step=j))
                        for j in range(i, i + n)
                    ])  # (n, K, batch, seq)
                    keys = jnp.stack([jax.random.key(j) for j in range(i, i + n)])
                    with repro_obs.annotation(f"train.chunk[{i}:{i + n}]"):
                        state, metrics = many(state, {"tokens": tokens}, keys)
                        losses = jax.device_get(metrics["loss"])  # syncs the chunk
                    rate = thru.update(n, n * tokens_per_step)
                    last = i + n - 1
                    print(
                        f"steps {i:4d}..{last:4d}  "
                        f"loss mean {float(losses.mean()):.4f} "
                        f"last {float(losses[-1]):.4f}  "
                        f"{rate.steps_per_s:7.2f} steps/s  "
                        f"{rate.tokens_per_s:9.0f} tok/s  ({n} steps/call)"
                    )
                    if obs is not None:
                        cm = jax.device_get(metrics["consensus"])
                        for j in range(n):
                            emit(jax.tree.map(lambda x: x[j], cm), i + j)
                    i += n
            else:
                step = jax.jit(
                    make_train_step(bundle, topo, opt, tcfg,
                                    consensus_rounds=args.consensus_rounds, obs=obs)
                )
                for i in range(args.steps):
                    batch = {"tokens": jnp.asarray(
                        stream.agent_batches(args.batch, args.agents, step=i))}
                    with repro_obs.annotation(f"train.step[{i}]"):
                        state, metrics = step(state, batch, jax.random.key(i))
                        loss = float(metrics["loss"])  # syncs the step
                    rate = thru.update(1, tokens_per_step)
                    emit(metrics.get("consensus"), i)
                    if i % 10 == 0 or i == args.steps - 1:
                        print(f"step {i:4d}  loss {loss:.4f}  "
                              f"{rate.steps_per_s:7.2f} steps/s  "
                              f"{rate.tokens_per_s:9.0f} tok/s")
    finally:
        if sink is not None:
            sink.close()
    life = thru.lifetime()
    print(f"total: {life.steps} steps in {life.seconds:.1f}s  "
          f"{life.steps_per_s:.2f} steps/s  {life.tokens_per_s:.0f} tok/s")
    if sink is not None:
        print(repro_obs.format_summary(
            repro_obs.summarize(repro_obs.read_jsonl(args.metrics_jsonl))))
    if args.ckpt_dir:
        from repro.ckpt import save_train_state

        path = save_train_state(args.ckpt_dir, state)
        print(f"saved {path}")


if __name__ == "__main__":
    main()

"""Decentralized trainer: local SGD steps + (DRT | classical) consensus.

Implements the paper's training loop (§IV.A): each agent runs local mini-batch
SGD on its own non-IID shard, then the network performs ``consensus_steps``
combination rounds (the paper uses 3, after [12]).

Two runtimes share this module:

* **simulator** — single device; the agent axis is a plain leading K axis and
  local steps run under ``vmap``.  Used by the paper-reproduction experiments,
  examples and tests (CPU).
* **pod runtime** — the same step functions called under ``jit`` with the
  agent axis sharded over the mesh ``data`` axis (see ``repro.launch``); the
  consensus step lowers to real collectives.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import WireCodec, init_comm_state, make_codec
from repro.core.consensus import Algorithm, ConsensusPath, gather_consensus_rounds
from repro.core.drt import DRTConfig
from repro.core.dynamic import (
    StaticSchedule,
    edge_stacks_from_topology,
    make_round_policy,
    make_schedule,
    max_in_degree_from_topology,
)
from repro.core.packing import SlabLayout, build_slab_layout, slab_template_supported
from repro.core.topology import Topology
from repro.obs.metrics import ObsConfig
from repro.optim.optimizers import Optimizer
from repro.utils.pytree import LayerPartition

PyTree = Any
LossFn = Callable[[PyTree, Any, jax.Array], jax.Array]  # (params, batch, rng) -> loss


class DecentralizedState(NamedTuple):
    params: PyTree  # leading agent axis K on every leaf
    opt_state: PyTree
    step: jax.Array
    comm: PyTree = ()  # per-agent codec state (error-feedback residuals)


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    algorithm: Algorithm = "drt"
    consensus_steps: int = 3
    drt: DRTConfig = DRTConfig()
    same_init: bool = True  # all agents start from identical parameters
    # wire codec for the consensus exchange: a repro.comm codec name
    # ("identity", "bf16", "f16", "int8", "topk", "topk:<frac>") or a
    # WireCodec instance; None keeps the exact full-precision exchange
    codec: "WireCodec | str | None" = None
    # "slab" (default) packs the agent-stacked tree once per consensus
    # round-set and runs every round on the flat (K, D) slab; "edge" runs the
    # sparse O(|E| D) edge-list rounds over the realized graph (dense slab
    # stays the parity oracle); "tree" is the per-leaf reference oracle
    consensus_path: ConsensusPath = "slab"
    # run the slab combine/stats through the Pallas kernels (interpret mode
    # on CPU, real kernels on TPU)
    use_kernels: bool = False
    # time-varying communication graph: a repro.core.dynamic.TopologySchedule
    # (or a spec string resolved against the trainer's K, e.g.
    # "periodic:ring,hypercube" or "gossip:0.3").  None keeps the static
    # topology — bit-identical to pre-schedule behavior.  Consensus round t
    # of step s mixes over graph ``s * consensus_steps + t``.
    schedule: object | None = None
    # heavy-ball momentum on the combination rounds:
    # x_{t+1} = A_t-mix(x_t) + beta (x_t - x_{t-1}); 0.0 (default) traces the
    # momentum-free program bit-identically
    consensus_momentum: float = 0.0
    # per-round-set budget: a repro.core.dynamic.RoundPolicy or spec string
    # ("fixed:<n>" / "adaptive:<tol>:<max>").  None keeps ``consensus_steps``
    # fixed rounds; an adaptive policy still traces max_rounds (compile O(1)
    # in rounds) but gates each round on the carried disagreement
    rounds_policy: object | None = None
    # -- robustness (repro.faults) -----------------------------------------
    # Byzantine agent fraction (floor(byzantine * K) seeded victims publish
    # through fault_model every round) and the attack spec ("sign_flip",
    # "gauss:<sigma>", "cgauss:<sigma>", "scale:<c>", "constant[:<v>]").
    # Both default off; byzantine > 0 requires a fault_model and vice versa.
    byzantine: float = 0.0
    fault_model: str | None = None
    # seed for fault membership / stochastic attacks / wire-fault tables
    # (independent of the codec rng)
    fault_seed: int = 0
    # wire faults: per-agent stale-iterate delivery probability and per-edge
    # symmetric message-drop probability (drop wraps the schedule in a
    # repro.faults.DropSchedule; dropped edges renormalize like churn)
    stale: float = 0.0
    drop: float = 0.0
    # trust reweighting of the mixing weights (clip caps any neighbour's
    # column entry, excess to self; temp < 1 sharpens) and the combine rule
    # ("drt" | "trimmed:<f>" | "median") — all default off / "drt" and then
    # trace today's exact program
    trust_clip: float | None = None
    trust_temp: float | None = None
    combine: str = "drt"

    def __post_init__(self):
        if not 0.0 <= float(self.consensus_momentum) < 1.0:
            raise ValueError(
                "consensus_momentum must be in [0, 1), got "
                f"{self.consensus_momentum}; the heavy-ball recurrence "
                "diverges at beta >= 1"
            )


class DecentralizedTrainer:
    """Couples a loss function, an optimizer, a topology and a consensus rule."""

    def __init__(
        self,
        loss_fn: LossFn,
        init_fn: Callable[[jax.Array], PyTree],
        optimizer: Optimizer,
        topology: Topology,
        cfg: TrainerConfig = TrainerConfig(),
        stacked_keys: tuple[str, ...] = (),
    ):
        self.loss_fn = loss_fn
        self.init_fn = init_fn
        self.optimizer = optimizer
        self.topology = topology
        self.cfg = cfg
        self.stacked_keys = stacked_keys
        self.K = topology.num_agents
        self.codec: WireCodec | None = (
            make_codec(cfg.codec) if cfg.codec is not None else None
        )
        self.schedule = (
            make_schedule(cfg.schedule, self.K) if cfg.schedule is not None else None
        )
        policy = make_round_policy(cfg.rounds_policy)
        # the policy (when set) owns the round budget; consensus_steps remains
        # the legacy fixed-count spelling
        self._rounds = policy.max_rounds if policy is not None else cfg.consensus_steps
        self._round_tol = policy.tol if policy is not None else None
        mix_topo = topology
        if self.schedule is not None and self.schedule.static:
            # a static schedule IS a static topology: take the schedule-free
            # fast path (bit-identical) on the schedule's graph
            mix_topo = self.schedule.topology_at(0)
            self.schedule = None
        # deferred import: repro.faults.wire subclasses TopologySchedule, so
        # a module-level import here would close a cycle through
        # repro.core.__init__
        from repro.faults import DropSchedule, make_fault_plan

        self.faults = make_fault_plan(
            self.K,
            byzantine=cfg.byzantine,
            fault_model=cfg.fault_model,
            stale=cfg.stale,
            seed=cfg.fault_seed,
        )
        if cfg.drop > 0.0:
            # message drop is a schedule transform: wrap whatever graph
            # sequence is in force (the static topology included) so the
            # engines renormalize dropped edges exactly like churn
            base = (
                self.schedule
                if self.schedule is not None
                else StaticSchedule(mix_topo)
            )
            self.schedule = DropSchedule(base, cfg.drop, seed=cfg.fault_seed)
        self._C = jnp.asarray(mix_topo.c_matrix(), jnp.float32)
        self._metropolis = jnp.asarray(mix_topo.metropolis(), jnp.float32)
        self._mix_topo = mix_topo
        self._partition: LayerPartition | None = None
        self._layout: SlabLayout | None = None

    # -- initialization -------------------------------------------------------

    def init(self, rng: jax.Array) -> DecentralizedState:
        if self.cfg.same_init:
            p0 = self.init_fn(rng)
            params = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (self.K, *x.shape)).copy(), p0
            )
        else:
            keys = jax.random.split(rng, self.K)
            params = jax.vmap(self.init_fn)(keys)
        self.build_partition(params)
        opt_state = self.optimizer.init(params)
        comm = self.init_comm(params)
        return DecentralizedState(params, opt_state, jnp.zeros((), jnp.int32), comm)

    def init_comm(self, params_K) -> PyTree:
        """Per-agent codec state (K-stacked); ``()`` for stateless codecs."""
        return init_comm_state(self.codec, params_K)

    @property
    def partition(self) -> LayerPartition:
        if self._partition is None:
            raise RuntimeError("call init() first")
        return self._partition

    def build_partition(self, params_K) -> LayerPartition:
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), params_K
        )
        self._partition = LayerPartition.build(template, stacked_keys=self.stacked_keys)
        self._layout = (
            build_slab_layout(self._partition, template)
            if self.cfg.consensus_path in ("slab", "edge")
            and slab_template_supported(template)
            else None  # non-float leaves: consensus falls back to the oracle
        )
        return self._partition

    # -- step functions (pure; jit/vmap-friendly) ------------------------------

    def local_step(self, state: DecentralizedState, batch_K, rng: jax.Array):
        """One local SGD step per agent (eq. 3a / first line of (11)).

        A loss marked ``has_aux`` (``repro.models.registry.with_stats``)
        returns ``(loss, stats)``; the metrics then carry the per-agent
        stats under ``"stats"``."""
        keys = jax.random.split(rng, self.K)
        has_aux = getattr(self.loss_fn, "has_aux", False)

        def one(params, batch, key):
            return jax.value_and_grad(self.loss_fn, has_aux=has_aux)(params, batch, key)

        out, grads = jax.vmap(one)(state.params, batch_K, keys)
        losses, stats = out if has_aux else (out, None)
        new_params, new_opt = self.optimizer.update(
            grads, state.opt_state, state.params, state.step
        )
        state = DecentralizedState(new_params, new_opt, state.step + 1, state.comm)
        metrics = {"loss": jnp.mean(losses)}
        if has_aux:
            metrics["stats"] = stats
        return state, metrics

    def consensus(
        self,
        state: DecentralizedState,
        rng: jax.Array | None = None,
        obs: "ObsConfig | None" = None,
    ):
        """``consensus_steps`` combination rounds (eq. 3b / second line of (11)).

        ``cfg.rounds_policy`` (when set) overrides the count: ``fixed:<n>``
        runs n rounds; ``adaptive:<tol>:<max>`` traces max rounds but gates
        each on the carried disagreement.  ``cfg.consensus_momentum`` adds
        heavy-ball momentum across rounds — both knobs default off and then
        trace today's exact program.

        DRT recomputes the mixing matrices each round (they are time varying);
        classical diffusion reuses the static Metropolis matrix.  With a
        configured wire codec the exchange is compressed and any per-agent
        error-feedback residual is threaded through ``state.comm``; ``rng``
        seeds stochastic codecs (defaults to a step-derived key).

        On the default ``consensus_path="slab"`` the agent-stacked tree is
        packed once, all rounds run on the flat (K, D) slab, and the tree is
        unpacked once at the end (see :mod:`repro.core.packing`).

        With a dynamic ``cfg.schedule`` round ``t`` of this round-set mixes
        over graph ``state.step * consensus_steps + t`` — a deterministic
        function of the step, so checkpoint resume replays the sequence.

        With ``obs=`` an :class:`~repro.obs.ObsConfig`, returns
        ``(state, A_last, metrics)`` where ``metrics`` is the per-round
        :class:`~repro.obs.ConsensusMetrics` stack; ``obs=None`` keeps the
        two-tuple return and today's exact jaxpr.
        """
        if self.codec is not None and rng is None:
            rng = jax.random.fold_in(jax.random.key(0), state.step)
        C, metropolis = self._C, self._metropolis
        rounds = self._rounds
        if self.schedule is not None:
            C, metropolis = self.schedule.mixing_stacks(
                state.step * rounds, rounds
            )
        edges = None
        max_in_degree = None
        if self.cfg.consensus_path == "edge":
            # the sparse view of the SAME round-set graphs the dense stacks
            # above realize (bit-consistent by the schedule contract); the
            # host Dmax bound keys the gather-only CSR combine
            if self.schedule is not None:
                edges = self.schedule.edge_stacks(
                    state.step * rounds, rounds
                )
                max_in_degree = self.schedule.max_in_degree
            else:
                edges = edge_stacks_from_topology(self._mix_topo, rounds)
                max_in_degree = max_in_degree_from_topology(self._mix_topo)
        out = gather_consensus_rounds(
            self.partition,
            state.params,
            C,
            self.cfg.drt,
            rounds=rounds,
            algorithm=self.cfg.algorithm,
            metropolis=metropolis,
            codec=self.codec,
            codec_state=state.comm,
            rng=rng,
            layout=self._layout,
            path=self.cfg.consensus_path,
            edges=edges,
            max_in_degree=max_in_degree,
            use_kernels=self.cfg.use_kernels,
            momentum=self.cfg.consensus_momentum,
            round_tol=self._round_tol,
            faults=(
                self.faults.realize(state.step * rounds, rounds)
                if self.faults is not None
                else None
            ),
            trust_clip=self.cfg.trust_clip,
            trust_temp=self.cfg.trust_temp,
            combine=self.cfg.combine,
            obs=obs,
        )
        if obs is None:
            params, A_last, comm = out
            return DecentralizedState(params, state.opt_state, state.step, comm), A_last
        params, A_last, comm, metrics = out
        return (
            DecentralizedState(params, state.opt_state, state.step, comm),
            A_last,
            metrics,
        )

    def disagreement(self, params_K) -> jax.Array:
        """sum_k || w_k - w_bar ||^2 (cf. Lemma 3's LHS with the plain mean)."""
        mean = jax.tree.map(lambda x: jnp.mean(x, axis=0, keepdims=True), params_K)
        diff = jax.tree.map(lambda x, m: x - m, params_K, mean)
        per_leaf = jax.tree.map(
            lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), diff
        )
        return jnp.sum(jnp.stack(jax.tree.leaves(per_leaf)))

    # -- convenience epoch driver (simulator) ----------------------------------

    def make_many_steps(self, *, donate: bool = True, obs: "ObsConfig | None" = None):
        """One jitted, buffer-donated program for a CHUNK of training steps.

        Returns ``many(state, batches_K, keys) -> (state, {"loss": (n,)})``
        scanning ``n = batches.shape[0]`` iterations of local-step +
        consensus inside a single device program — the per-step host
        dispatch (and per-call argument processing) is paid once per chunk
        instead of once per step.  ``batches_K`` leaves carry a leading
        ``(n, K, ...)`` step axis; ``keys`` is the ``(n,)`` stack of exactly
        the per-step keys the single-step driver would pass, so the result
        is bit-identical to ``n`` successive ``local_step`` + ``consensus``
        calls: the consensus rng and any schedule's round indices derive
        from the CARRIED ``state.step``, which makes chunk boundaries (and
        checkpoint resume mid-chunk) invisible to the math.

        ``donate=True`` (default) donates the state argument so XLA updates
        params / optimizer state / EF residuals in place across the chunk.

        With ``obs=``, the metrics dict gains ``"consensus"`` — the
        per-step :class:`~repro.obs.ConsensusMetrics` stacks riding the scan
        ys with leading ``(n, rounds)`` axes.
        """

        def many(state: DecentralizedState, batches_K, keys):
            def body(st, inp):
                batch, key = inp
                st, metrics = self.local_step(st, batch, key)
                if obs is None:
                    st, _ = self.consensus(st)
                    return st, metrics["loss"]
                st, _, cm = self.consensus(st, obs=obs)
                return st, (metrics["loss"], cm)

            state, ys = jax.lax.scan(body, state, (batches_K, keys))
            if obs is None:
                return state, {"loss": ys}
            losses, cm = ys
            return state, {"loss": losses, "consensus": cm}

        return jax.jit(many, donate_argnums=(0,)) if donate else many

    def epoch(self, state: DecentralizedState, batches_K, rng: jax.Array):
        """Scan over an epoch of per-agent batches, then run consensus.

        ``batches_K``: pytree of arrays with leading (n_batches, K, ...) axes.

        ``metrics["disagreement"]`` is the post-consensus network
        disagreement read from the :class:`~repro.obs.ConsensusMetrics`
        telemetry (``mean_k ||x_k - x_bar||^2`` after the last round) — the
        SAME quantity, from the same code path, that ``launch.train`` and
        ``benchmarks/scenario_matrix`` report.  The legacy
        :meth:`disagreement` (sum over agents) remains for direct use.
        """
        n_batches = jax.tree.leaves(batches_K)[0].shape[0]
        keys = jax.random.split(rng, n_batches)

        def body(st, inp):
            batch, key = inp
            st, metrics = self.local_step(st, batch, key)
            return st, metrics["loss"]

        state, losses = jax.lax.scan(body, state, (batches_K, keys))
        if self._rounds > 0:
            state, _, cm = self.consensus(state, obs=ObsConfig())
            dis = cm.disagreement[-1]
            eff = cm.effective_rounds[-1]
        else:
            # zero consensus rounds: the engines (correctly) refuse a
            # rounds=0 call, so skip the exchange entirely and report the
            # same per-agent-mean disagreement the telemetry would
            dis = self.disagreement(state.params) / self.K
            eff = jnp.zeros((), jnp.float32)
        return state, {
            "loss": jnp.mean(losses),
            "disagreement": dis,
            "effective_rounds": eff,
        }

"""Time-varying topologies: mixing-structure schedules for dynamic graphs.

The paper's experiments (and the static :class:`~repro.core.topology.Topology`
plumbing) fix one communication graph for the whole run.  Real decentralized
deployments are time varying — gossip schedules, stragglers, agents joining
and leaving — and consensus distance under *changing* graphs is what governs
convergence (Kong et al., Consensus Control for Decentralized Deep Learning;
Balu et al., Momentum-Accelerated Consensus).  A
:class:`TopologySchedule` maps a global consensus-round index ``t`` to the
round's mixing structure and realizes whole round-sets as stacked per-round
``(C_t, metropolis_t)`` arrays that both consensus engines consume.

Two views of every schedule, guaranteed consistent:

* ``mixing_stacks(start_round, rounds)`` — the *traced* view: pure jax, so
  ``start_round`` may be a traced scalar (jitted train steps index schedules
  with ``state.step``).  Feeds ``gather_consensus_rounds`` (slab Gram
  recurrence ``G' = A_t^T G A_t`` included) as ``(rounds, K, K)`` stacks.
* ``topology_at(t)`` — the *host* view for a concrete Python round index:
  a realized :class:`Topology` whose adjacency matches round ``t`` of the
  traced view bit for bit.  Feeds ``PermuteConsensus`` (which re-derives its
  per-round ppermute decomposition from it), property tests and benchmarks.

Churn semantics (``ChurnSchedule``): a dropped agent loses every incident
edge for that round but RETAINS its self loop — it keeps its own iterate
exactly (Metropolis column becomes ``e_k``; the DRT support ``C_t`` shrinks
to ``c_kk`` and the DRT normalization renormalizes the surviving
neighbourhood automatically).  Dropped edges are removed symmetrically.

Schedules are stateless: everything is a deterministic function of
``(seed, t)``, so checkpoint resume (which restores only ``step``) replays
the exact graph sequence.  The randomized schedules (gossip, churn) realize
a seeded ``cycle`` of draws in numpy at construction and repeat it with
period ``cycle`` — that keeps the host and traced views bit-identical (the
traced view is a table lookup at ``t % cycle``) without host callbacks from
inside traces; raise ``cycle`` for longer unique sequences.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.drt import EdgeSegments
from repro.core.topology import Topology, make_topology


class EdgeStacks(NamedTuple):
    """Per-round padded DIRECTED edge lists — the sparse view of a schedule.

    Every undirected edge {l, k} of round r appears twice: once as
    ``(src=l, dst=k)`` and once as ``(src=k, dst=l)``; entries are sorted by
    ``(dst, src)`` so each destination agent's incoming edges are contiguous
    (the segment order the dst-partitioned sharding of
    :mod:`repro.launch.sharding` relies on).  Rounds are padded to a common
    ``E_max`` with ``src = dst = 0`` and ``w = 0`` — padding is numerically
    inert on the edge consensus path (weights are masked on ``w > 0`` and
    scatter-adds contribute exact zeros).

    ``w`` carries the support weight of the edge (the off-diagonal ``C``
    entry — 1.0 for every built-in topology); degrees and Metropolis/DRT
    segment weights are derived from the list in-graph
    (:func:`metropolis_edge_weights`, :func:`repro.core.drt.drt_edge_mixing`).
    """

    src: jax.Array  # (rounds, E_max) int32
    dst: jax.Array  # (rounds, E_max) int32
    w: jax.Array  # (rounds, E_max) float32; 0.0 marks padding


def metropolis_edge_weights(
    src: jax.Array, dst: jax.Array, w: jax.Array, K: int, dense: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Metropolis-Hastings weights (eq. 5) on a padded directed edge list.

    Returns ``(m_self (K,), m_e (E,))`` — the edge-list factorization of
    :func:`metropolis_from_adjacency`'s column: ``m_e[e]`` is the weight
    agent ``dst[e]`` applies to ``src[e]``'s iterate, ``m_self[k]`` the
    diagonal.  Padding edges (``w == 0``) get weight 0 and an isolated agent
    keeps the identity column, matching the dense construction.
    ``dense=True`` runs the gathers and segment sums as masked reductions
    (:class:`repro.core.drt.EdgeSegments`, inside a Pallas kernel).
    """
    seg = EdgeSegments(src, dst, K, dense)
    mask = (jnp.asarray(w, jnp.float32) > 0.0).astype(jnp.float32)
    deg = seg.segment_sum(mask, 1.0)  # n_k incl. self loop
    m_e = jnp.where(
        mask > 0.0, 1.0 / jnp.maximum(seg.at_src(deg), seg.at_dst(deg)), 0.0
    )
    m_self = 1.0 - seg.segment_sum(m_e)
    return m_self, m_e


def csr_from_edges(
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    K: int,
    max_in_degree: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-DESTINATION CSR view of a padded (dst, src)-sorted edge list —
    the D-free index algebra behind the gather-only combine.

    The edge-table contract (real edges sorted ascending by ``(dst, src)``,
    padding ``w == 0`` rows trailing) means each destination's incoming
    edges are contiguous, so one ``searchsorted`` per bound recovers the
    segment offsets without any scatter.  Returns

      nbr   (K, Dmax) int32  source agent of the j-th in-edge (0 when padded)
      pos   (K, Dmax) int32  that edge's row in the edge list (clipped)
      valid (K, Dmax) bool   j < in_degree(k)
      rank  (E,)      int32  each edge's CSR slot index within its dst segment

    ``rank`` maps per-edge quantities (L, E) to CSR layout ``(L, K, Dmax)``
    and back: edge ``e`` lives at ``[dst[e], rank[e]]``.  All outputs are
    traced-compatible; ``max_in_degree`` must be a static host bound (see
    ``TopologySchedule.max_in_degree``).
    """
    E = src.shape[0]
    mask = jnp.asarray(w, jnp.float32) > 0.0
    # padding rows carry dst = 0; remap them past every real key so the
    # composite stays sorted and searchsorted sees clean segments
    key = jnp.where(mask, dst, K)
    ks = jnp.arange(K)
    offs = jnp.searchsorted(key, ks, side="left")
    deg = jnp.searchsorted(key, ks, side="right") - offs
    j = jnp.arange(max_in_degree)
    pos = jnp.clip(offs[:, None] + j[None, :], 0, E - 1)  # (K, Dmax)
    valid = j[None, :] < deg[:, None]
    nbr = jnp.where(valid, src[pos], 0)
    rank = jnp.clip(jnp.arange(E) - offs[jnp.clip(dst, 0, K - 1)], 0,
                    max_in_degree - 1)
    return nbr, pos, valid, rank


def c_from_adjacency(adj: jax.Array) -> jax.Array:
    """The paper's support matrix C from a (…, K, K) 0/1 adjacency: edges
    plus the always-retained self loops."""
    adj = jnp.asarray(adj, jnp.float32)
    K = adj.shape[-1]
    eye = jnp.eye(K, dtype=adj.dtype)
    return jnp.where(eye > 0, 1.0, adj)


def metropolis_from_adjacency(adj: jax.Array) -> jax.Array:
    """Metropolis-Hastings weights (eq. 5) from a (…, K, K) 0/1 adjacency,
    traced-compatible.  Doubly stochastic for every realization; an isolated
    agent (churn) gets the identity column — it keeps its own iterate."""
    adj = jnp.asarray(adj, jnp.float32)
    deg = jnp.sum(adj, axis=-1) + 1.0  # n_k includes the self loop
    n_max = jnp.maximum(deg[..., :, None], deg[..., None, :])
    A = adj / n_max
    K = adj.shape[-1]
    eye = jnp.eye(K, dtype=adj.dtype)
    diag = 1.0 - jnp.sum(A, axis=-2)  # column sums (symmetric anyway)
    return A + eye * diag[..., None, :]


def _stacks_from_adjacency(adj_stack: jax.Array) -> tuple[jax.Array, jax.Array]:
    return c_from_adjacency(adj_stack), metropolis_from_adjacency(adj_stack)


class TopologySchedule:
    """Base class: a deterministic map from round index to communication graph.

    Subclasses implement :meth:`adjacency_at`; the default
    :meth:`mixing_stacks` / :meth:`topology_at` derive both views from it.
    """

    #: True when every round realizes the same graph (the engines keep their
    #: static fast paths; ``make_train_step`` allows the permute engine).
    static: bool = False

    @property
    def num_agents(self) -> int:
        raise NotImplementedError

    def adjacency_at(self, t) -> jax.Array:
        """(K, K) float 0/1 adjacency of round ``t`` (``t`` may be traced)."""
        raise NotImplementedError

    def mixing_stacks(self, start_round, rounds: int) -> tuple[jax.Array, jax.Array]:
        """Per-round mixing structures for one round-set.

        Returns ``(C_stack, metropolis_stack)``, both ``(rounds, K, K)``
        float32; ``start_round`` may be a traced scalar (e.g.
        ``state.step * consensus_steps``).
        """
        ts = jnp.asarray(start_round) + jnp.arange(rounds)
        adj = jax.vmap(self.adjacency_at)(ts)
        return _stacks_from_adjacency(adj)

    def edge_counts(self, start_round, rounds: int) -> jax.Array:
        """Per-round realized undirected edge counts, ``(rounds,)`` float32.

        The schedule-density ground truth for the telemetry's
        ``ConsensusMetrics.edges`` field (cross-checked in tests): an
        agent-drop or edge-drop schedule shows up here round by round.
        """
        ts = jnp.asarray(start_round) + jnp.arange(rounds)
        adj = jax.vmap(self.adjacency_at)(ts)
        return jnp.sum(jnp.asarray(adj, jnp.float32), axis=(-2, -1)) / 2.0

    def topology_at(self, t: int) -> Topology:
        """Concrete host-side realization of round ``t`` (Python int).

        Must be pure host Python/numpy: the permute engine calls it while
        tracing a ``shard_map`` body, where any jax op — even on constants —
        is lifted into the trace.  The built-ins realize from numpy tables;
        subclasses with a jax-level ``adjacency_at`` must override this with
        a matching host computation.
        """
        raise NotImplementedError

    # -- sparse (edge-list) view ----------------------------------------------

    def _host_edge_period(self) -> int:
        """Host period of the realized graph sequence: ``topology_at(t)``
        repeats with this period.  Subclasses with a finite cycle implement
        it; the base raises so a custom aperiodic schedule fails loudly
        rather than silently truncating its edge view."""
        raise NotImplementedError(
            f"{type(self).__name__} does not expose a host edge period; "
            "implement _host_edge_period() to enable the sparse "
            "edge_stacks() view"
        )

    @functools.cached_property
    def _edge_table(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(P, E_max) numpy (src, dst, w) tables realized ONCE on the host
        from the same canonical graph sequence as ``topology_at`` /
        ``mixing_stacks`` (both views read the same seeded cycle tables, so
        the sparse view is bit-consistent with the dense stacks).  Directed
        edges sorted by (dst, src); padding entries are ``src = dst = 0``
        with ``w = 0``."""
        P = self._host_edge_period()
        per_round = []
        for t in range(P):
            adj = np.asarray(self.topology_at(t).adjacency, dtype=bool)
            # np.nonzero walks row-major: taking the FIRST axis as dst yields
            # the canonical (dst, src) sort without an extra argsort
            d, s = np.nonzero(adj)
            per_round.append((s.astype(np.int32), d.astype(np.int32)))
        E_max = max(max((len(s) for s, _ in per_round), default=0), 1)
        src = np.zeros((P, E_max), np.int32)
        dst = np.zeros((P, E_max), np.int32)
        w = np.zeros((P, E_max), np.float32)
        for t, (s, d) in enumerate(per_round):
            src[t, : len(s)] = s
            dst[t, : len(d)] = d
            w[t, : len(s)] = 1.0
        return src, dst, w

    @property
    def max_edges(self) -> int:
        """Padded DIRECTED edge count ``E_max`` per round (2x the undirected
        count of the densest round in the period)."""
        return int(self._edge_table[0].shape[1])

    @property
    def max_in_degree(self) -> int:
        """Host bound on any agent's in-degree over the schedule period —
        the static ``Dmax`` of the CSR (gather-only) combine; see
        :func:`csr_from_edges`."""
        _, dst, w = self._edge_table
        m = 1
        for t in range(dst.shape[0]):
            real = w[t] > 0.0
            if real.any():
                m = max(m, int(np.bincount(dst[t][real]).max()))
        return m

    def edge_stacks(self, start_round, rounds: int) -> EdgeStacks:
        """Per-round padded edge lists for one round-set — the sparse
        counterpart of :meth:`mixing_stacks` (same rounds, same graphs, bit
        consistent: both realize from the same host tables).

        Returns an :class:`EdgeStacks` with ``(rounds, E_max)`` leaves;
        ``start_round`` may be a traced scalar.  This is what
        ``gather_consensus_rounds(..., path="edge", edges=...)`` scans
        instead of the dense ``(rounds, K, K)`` mixing stacks.
        """
        src, dst, w = self._edge_table
        P = src.shape[0]
        ts = (jnp.asarray(start_round) + jnp.arange(rounds)) % P
        return EdgeStacks(
            jnp.asarray(src)[ts], jnp.asarray(dst)[ts], jnp.asarray(w)[ts]
        )


@dataclasses.dataclass(frozen=True)
class StaticSchedule(TopologySchedule):
    """Today's behavior as a schedule: the same graph every round.

    ``mixing_stacks`` broadcasts the topology's own (float64-derived)
    ``c_matrix``/``metropolis`` so a static schedule is bit-identical to the
    schedule-free path."""

    topology: Topology
    static: bool = dataclasses.field(default=True, init=False)

    @property
    def num_agents(self) -> int:
        return self.topology.num_agents

    def adjacency_at(self, t) -> jax.Array:
        del t
        return jnp.asarray(self.topology.adjacency, jnp.float32)

    def mixing_stacks(self, start_round, rounds: int):
        C = jnp.asarray(self.topology.c_matrix(), jnp.float32)
        M = jnp.asarray(self.topology.metropolis(), jnp.float32)
        K = self.topology.num_agents
        return (
            jnp.broadcast_to(C, (rounds, K, K)),
            jnp.broadcast_to(M, (rounds, K, K)),
        )

    def topology_at(self, t: int) -> Topology:
        del t
        return self.topology

    def _host_edge_period(self) -> int:
        return 1


@dataclasses.dataclass(frozen=True)
class PeriodicSchedule(TopologySchedule):
    """Cycle through a topology list: round ``t`` uses
    ``topologies[(t // rounds_per_topology) % len(topologies)]``.

    Mixing matrices are precomputed on the host per phase (full float64
    Metropolis, like the static path) and gathered by traced round index."""

    topologies: tuple[Topology, ...]
    rounds_per_topology: int = 1

    def __post_init__(self):
        if not self.topologies:
            raise ValueError("PeriodicSchedule needs at least one topology")
        object.__setattr__(self, "topologies", tuple(self.topologies))
        Ks = {t.num_agents for t in self.topologies}
        if len(Ks) != 1:
            raise ValueError(f"topologies disagree on K: {sorted(Ks)}")
        if self.rounds_per_topology < 1:
            raise ValueError("rounds_per_topology must be >= 1")

    @property
    def num_agents(self) -> int:
        return self.topologies[0].num_agents

    def _phase(self, t):
        return (t // self.rounds_per_topology) % len(self.topologies)

    # the per-phase tables are pure functions of the (frozen) topology list;
    # realizing them once as host numpy keeps every trace of adjacency_at /
    # mixing_stacks (one per jitted step or scanned chunk) from re-running
    # the float64 Metropolis construction per topology per trace
    @functools.cached_property
    def _adj_table(self) -> np.ndarray:
        return np.stack(
            [np.asarray(tp.adjacency, np.float32) for tp in self.topologies]
        )

    @functools.cached_property
    def _C_table(self) -> np.ndarray:
        return np.stack(
            [np.asarray(tp.c_matrix(), np.float32) for tp in self.topologies]
        )

    @functools.cached_property
    def _M_table(self) -> np.ndarray:
        return np.stack(
            [np.asarray(tp.metropolis(), np.float32) for tp in self.topologies]
        )

    def adjacency_at(self, t) -> jax.Array:
        return jnp.asarray(self._adj_table)[self._phase(jnp.asarray(t))]

    def mixing_stacks(self, start_round, rounds: int):
        phases = self._phase(jnp.asarray(start_round) + jnp.arange(rounds))
        return (
            jnp.asarray(self._C_table)[phases],
            jnp.asarray(self._M_table)[phases],
        )

    def topology_at(self, t: int) -> Topology:
        return self.topologies[int(self._phase(int(t)))]

    def _host_edge_period(self) -> int:
        return self.rounds_per_topology * len(self.topologies)


@dataclasses.dataclass(frozen=True)
class RandomGossipSchedule(TopologySchedule):
    """Seeded random gossip: round ``t`` is an independent Erdos-Renyi
    ``G(K, p)`` draw (deterministic per ``(seed, t % cycle)``).  Single
    rounds may be disconnected — connectivity only needs to hold jointly over
    time (Assumption 1 in expectation), which is the regime consensus-control
    papers study."""

    K: int
    p: float = 0.5
    seed: int = 0
    cycle: int = 64  # draws repeat after this many rounds (see module doc)

    def __post_init__(self):
        if self.K < 2:
            raise ValueError(f"gossip needs K >= 2, got {self.K}")
        if not 0.0 < self.p <= 1.0:
            raise ValueError(f"gossip edge probability must be in (0, 1], got {self.p}")
        if self.cycle < 1:
            raise ValueError(f"cycle must be >= 1, got {self.cycle}")

    @property
    def num_agents(self) -> int:
        return self.K

    @functools.cached_property
    def _table(self) -> np.ndarray:
        """(cycle, K, K) bool: the realized graph sequence (host canonical)."""
        out = np.zeros((self.cycle, self.K, self.K), dtype=bool)
        for t in range(self.cycle):
            rng = np.random.default_rng(
                np.random.SeedSequence(entropy=self.seed, spawn_key=(t,))
            )
            upper = np.triu(rng.random((self.K, self.K)) < self.p, k=1)
            out[t] = upper | upper.T
        return out

    def adjacency_at(self, t) -> jax.Array:
        table = jnp.asarray(self._table, jnp.float32)
        return table[jnp.asarray(t) % self.cycle]

    def topology_at(self, t: int) -> Topology:
        return Topology(f"gossip@{int(t)}", self._table[int(t) % self.cycle])

    def _host_edge_period(self) -> int:
        return self.cycle


def one_peer_exponential(K: int) -> PeriodicSchedule:
    """One-peer exponential graphs (Assran et al., SGP): round ``t`` pairs
    agent ``i`` with ``i XOR 2^(t mod log2 K)`` — perfect matchings cycling
    through the hypercube dimensions.  Each round every agent talks to exactly
    ONE peer; the union over ``log2 K`` rounds is the full hypercube."""
    d = K.bit_length() - 1
    if K < 2 or (1 << d) != K:
        raise ValueError(f"one-peer exponential needs K a power of two, got {K}")
    topos = []
    for b in range(d):
        A = np.zeros((K, K), dtype=bool)
        for i in range(K):
            A[i, i ^ (1 << b)] = True
        topos.append(Topology(f"onepeer2^{b}", A))
    return PeriodicSchedule(tuple(topos))


@dataclasses.dataclass(frozen=True)
class ChurnSchedule(TopologySchedule):
    """Per-round agent/edge failure injector wrapping a base schedule.

    Each round, every agent independently drops with probability
    ``agent_drop`` (losing ALL incident edges — but keeping its self loop, so
    it carries its iterate unchanged through the round) and every surviving
    edge independently drops with probability ``edge_drop`` (symmetrically).
    Deterministic per ``(seed, t % cycle)``."""

    base: TopologySchedule
    agent_drop: float = 0.0
    edge_drop: float = 0.0
    seed: int = 0
    cycle: int = 64  # failure draws repeat after this many rounds

    def __post_init__(self):
        for name in ("agent_drop", "edge_drop"):
            v = getattr(self, name)
            if not 0.0 <= v < 1.0:
                raise ValueError(f"{name} must be in [0, 1), got {v}")
        if self.cycle < 1:
            raise ValueError(f"cycle must be >= 1, got {self.cycle}")

    @property
    def num_agents(self) -> int:
        return self.base.num_agents

    @functools.cached_property
    def _mask_table(self) -> np.ndarray:
        """(cycle, K, K) bool edge-survival masks (host canonical): the
        agent-drop outer product AND the symmetric edge-drop keep mask."""
        K = self.base.num_agents
        out = np.zeros((self.cycle, K, K), dtype=bool)
        for t in range(self.cycle):
            # spawn_key tagged (1, t): distinct stream from RandomGossip's
            # (t,), so churn failures stay independent of the base graph's
            # draws even when both share one user-facing seed
            rng = np.random.default_rng(
                np.random.SeedSequence(entropy=self.seed, spawn_key=(1, t))
            )
            alive = rng.random(K) >= self.agent_drop
            keep_u = np.triu(rng.random((K, K)) >= self.edge_drop, k=1)
            out[t] = (keep_u | keep_u.T) & alive[:, None] & alive[None, :]
        return out

    def adjacency_at(self, t) -> jax.Array:
        adj = self.base.adjacency_at(t)
        mask = jnp.asarray(self._mask_table, jnp.float32)
        return adj * mask[jnp.asarray(t) % self.cycle]

    def topology_at(self, t: int) -> Topology:
        base_adj = self.base.topology_at(int(t)).adjacency
        adj = base_adj & self._mask_table[int(t) % self.cycle]
        return Topology(f"churn({self.base.topology_at(int(t)).name})@{int(t)}", adj)

    def _host_edge_period(self) -> int:
        return math.lcm(self.base._host_edge_period(), self.cycle)


# ---------------------------------------------------------------------------
# spec parser (CLI / TrainerConfig convenience)
# ---------------------------------------------------------------------------


def make_schedule(
    spec: "str | TopologySchedule | Topology | None",
    K: int,
    *,
    agent_drop: float = 0.0,
    edge_drop: float = 0.0,
    seed: int = 0,
) -> "TopologySchedule | None":
    """Build a schedule from a spec string (the ``launch.train`` CLI surface).

    Specs::

        <topology-name>                 static graph (e.g. "ring")
        static:<topology-name>          same, explicit
        periodic:<a>,<b>[,...][@n]     cycle the named topologies, n rounds
                                        per topology (default 1)
        gossip[:p]                      per-round Erdos-Renyi G(K, p) draw
        onepeer                         one-peer exponential matchings

    ``agent_drop``/``edge_drop`` > 0 wrap the result in a
    :class:`ChurnSchedule`.  ``None`` stays ``None`` unless churn is
    requested (then the caller must name a base graph).  A ``Topology`` or
    ``TopologySchedule`` passes through (churn-wrapped if requested).
    """
    sched: TopologySchedule | None
    if spec is None:
        sched = None
    elif isinstance(spec, TopologySchedule):
        sched = spec
    elif isinstance(spec, Topology):
        sched = StaticSchedule(spec)
    elif isinstance(spec, str):
        head, _, rest = spec.partition(":")
        if head == "static":
            sched = StaticSchedule(make_topology(rest, K))
        elif head == "periodic":
            names, _, rpt = rest.partition("@")
            topos = tuple(make_topology(n.strip(), K) for n in names.split(",") if n.strip())
            sched = PeriodicSchedule(topos, rounds_per_topology=int(rpt) if rpt else 1)
        elif head == "gossip":
            sched = RandomGossipSchedule(K, p=float(rest) if rest else 0.5, seed=seed)
        elif head == "onepeer":
            sched = one_peer_exponential(K)
        else:
            try:
                sched = StaticSchedule(make_topology(spec, K))
            except KeyError:
                raise ValueError(
                    f"unknown schedule spec {spec!r}; expected a topology name, "
                    "'static:<name>', 'periodic:<a>,<b>[@n]', 'gossip[:p]' or "
                    "'onepeer'"
                ) from None
    else:
        raise TypeError(f"cannot build a schedule from {type(spec).__name__}")

    if agent_drop or edge_drop:
        if sched is None:
            raise ValueError("churn (agent/edge drop) needs a base schedule or topology")
        sched = ChurnSchedule(sched, agent_drop=agent_drop, edge_drop=edge_drop, seed=seed)
    if sched is not None and sched.num_agents != K:
        raise ValueError(f"schedule has K={sched.num_agents}, expected {K}")
    return sched


@dataclasses.dataclass(frozen=True)
class RoundPolicy:
    """Per-round-set budget policy for the consensus engines.

    ``fixed`` (``tol is None``): always run ``max_rounds`` rounds — the
    historical behaviour.  ``adaptive``: still *trace* ``max_rounds`` rounds
    (compile stays O(1) in rounds), but inside the compiled scan each round
    first checks the carried disagreement against ``tol`` and becomes an
    identity no-op once it drops below — consensus control in the sense of
    Kong et al. (arXiv 2102.04828), spending wire bytes only while measured
    disagreement warrants them.  The gate is sticky: once a round-set goes
    inactive it stays inactive for the remaining traced rounds.
    """

    max_rounds: int
    tol: float | None = None

    def __post_init__(self):
        if self.max_rounds < 1:
            raise ValueError(
                f"RoundPolicy needs max_rounds >= 1, got {self.max_rounds}"
            )
        if self.tol is not None and not self.tol > 0.0:
            raise ValueError(f"RoundPolicy needs tol > 0, got {self.tol}")

    @property
    def adaptive(self) -> bool:
        return self.tol is not None


def make_round_policy(spec: "str | int | RoundPolicy | None") -> "RoundPolicy | None":
    """Build a :class:`RoundPolicy` from a spec (the ``--rounds-policy`` CLI
    surface and the ``TrainerConfig.rounds_policy`` field).

    Specs::

        fixed:<n>               always run n rounds
        adaptive:<tol>:<max>    run up to max rounds, stop once the measured
                                per-round disagreement drops below tol
        <n>                     bare int / digit string, same as fixed:<n>

    ``None`` and an existing :class:`RoundPolicy` pass through.
    """
    if spec is None or isinstance(spec, RoundPolicy):
        return spec
    if isinstance(spec, int):
        return RoundPolicy(max_rounds=spec)
    if isinstance(spec, str):
        head, _, rest = spec.partition(":")
        if head == "fixed":
            return RoundPolicy(max_rounds=int(rest))
        if head == "adaptive":
            tol_s, sep, max_s = rest.partition(":")
            if not sep:
                raise ValueError(
                    f"adaptive policy spec {spec!r} needs 'adaptive:<tol>:<max>'"
                )
            return RoundPolicy(max_rounds=int(max_s), tol=float(tol_s))
        if spec.lstrip("+-").isdigit():
            return RoundPolicy(max_rounds=int(spec))
        raise ValueError(
            f"unknown rounds policy spec {spec!r}; expected 'fixed:<n>', "
            "'adaptive:<tol>:<max>' or a bare round count"
        )
    raise TypeError(f"cannot build a round policy from {type(spec).__name__}")


def edge_stacks_from_topology(topology: Topology, rounds: int) -> EdgeStacks:
    """Static-graph convenience: the topology's edge list broadcast over a
    round-set (what ``path="edge"`` consumes when no schedule is set)."""
    return StaticSchedule(topology).edge_stacks(0, rounds)


def max_in_degree_from_topology(topology: Topology) -> int:
    """Static-graph convenience: the host ``Dmax`` bound for the CSR
    (gather-only) edge combine — see :func:`csr_from_edges`."""
    return StaticSchedule(topology).max_in_degree


def schedule_graph_stats(
    schedule: TopologySchedule, *, rounds: "int | None" = None,
    wire_itemsize: int = 1,
) -> dict:
    """Realized graph statistics over one host period (dryrun surface).

    Returns a plain dict: ``K``, ``E_max`` (padded directed width),
    per-round undirected edge counts (min/mean/max over the sampled rounds),
    degree min/mean/max (self loop excluded), and two dense-vs-edge cost
    ratios for one coded consensus round (> 1 means the edge path is
    cheaper):

    * ``dense_vs_edge_flop_ratio`` — ``K^2 / mean directed |E|`` (dense
      stats + combine are each O(K^2 D); the edge path's are each
      O(|E_directed| D)).  Scales with graph sparsity.
    * ``dense_vs_edge_byte_ratio`` — per-slab-element HBM bytes, dense fused
      round over wire-resident edge round (``repro.kernels.traffic`` model,
      leading order in D): dense streams 3 f32 passes (self x2 + out) =
      12 B; the edge round streams self + out in f32 and the compact wire
      once per phase = ``8 + 2 * wire_itemsize`` B.  ``wire_itemsize`` is
      the codec's wire bytes/element (default 1, the int8 codec).  Unlike
      FLOPs this is graph-INDEPENDENT: the replicated wire is streamed
      whole per phase whatever |E| is — sparsity buys FLOPs, the in-kernel
      decode buys the bytes.
    """
    K = schedule.num_agents
    src, dst, w = schedule._edge_table
    P = src.shape[0]
    n = P if rounds is None else min(rounds, P)
    directed = w[:n].sum(axis=1)  # real (non-padding) directed edges per round
    degs = []
    for t in range(n):
        counts = np.bincount(dst[t][w[t] > 0], minlength=K)
        degs.append(counts)
    degs = np.stack(degs) if degs else np.zeros((1, K), np.int64)
    mean_directed = float(directed.mean()) if n else 0.0
    return {
        "K": K,
        "period": P,
        "rounds_sampled": n,
        "E_max": int(src.shape[1]),
        "undirected_edges": {
            "min": float(directed.min() / 2.0),
            "mean": mean_directed / 2.0,
            "max": float(directed.max() / 2.0),
        },
        "degree": {
            "min": int(degs.min()),
            "mean": float(degs.mean()),
            "max": int(degs.max()),
        },
        "dense_vs_edge_flop_ratio": (
            float(K * K) / mean_directed if mean_directed else float("inf")
        ),
        "dense_vs_edge_byte_ratio": 12.0 / (8.0 + 2.0 * wire_itemsize),
    }
